"""Traffic kind ``open_loop_http_xing4``: the served path of the hybrid
layer function in its ``xing4_0`` layout (Xing4.0-29B-A4B: a residual of
four streams mixed by manifold-constrained hyper-connections round every
latent-attention and feed-forward sublayer, a leading dense layer, 64
sigmoid-and-bias-routed gated experts top-4 all HELD plus one shared;
``ray_tpu/models/hybrid.py``) under a fixed offered rate of cold,
unshared long prompts.

The same run as ``open_loop_http_deepseek_v2`` — ``serve.run(
build_gpt_deployment(...), use_actors=False, http=True)`` in this
process, streamed ``POST /v1/generate`` from the ``loadgen.py`` child,
greedy, ``eos`` off, the lead-in / window / drain of
``traffic_gen.chat_requests``, times taken at the client from when each
request was DUE, the gauges read at the window's two ends, that kind's
warm-up (two chunks, decode steps, an adopted chain and its
copy-on-write) and its counters of the latent pool.  What this kind
owns: ``model_config`` (the configuration file's published keys: every
expert and the whole vocabulary are held), ``make_params`` (the program's
init, then the routers' selection bias balanced by the published
recipe's sign update THROUGH the maps, as the ``lfm2`` kind does and for
its reason), the reference it checks against (``chipbench/reference/
xing4.py``), the label table of its trace (``chipbench/xing4_trace.py``)
and the counters over the traced seconds themselves (as the ``lfm2``
kind takes them).  The load generator, the client reduction, the
engine's counters, the sample, the verdict, the judge (two quantiles of
the checked tokens' margins: this family's router is discrete too) and
the host watch are imported from the files that have them.

The model module is imported FIRST, and asked for the layout: a checkout
whose program lacks it stops here, at once, with a message, before any
weight or pool is allocated.
"""

from __future__ import annotations

from ray_tpu.models import hybrid          # noqa: E402  (first: see above)

if "hc_mult" not in getattr(hybrid.HybridConfig, "__dataclass_fields__", {}):
    raise SystemExit("ray_tpu/models/hybrid.py of this checkout has no "
                     "xing4_0 layout (a residual of hc_mult streams, "
                     "manifold-constrained hyper-connections): the cell "
                     "cannot run here")

import gc                                   # noqa: E402
import json                                 # noqa: E402
import os                                   # noqa: E402
import tempfile                             # noqa: E402
import time                                 # noqa: E402

from chipbench.traffic.open_loop_http import (COUNTERS, ROUTE,    # noqa: E402
                                              client_metrics,
                                              engine_counters, pick_checked,
                                              run_loadgen, verdict)
from chipbench.traffic.open_loop_http_afmoe import loop_ms_per_pass  # noqa: E402
from chipbench.traffic.open_loop_http_deepseek_v2 import (   # noqa: E402
    LATENT_COUNTERS, warm_up)
from chipbench.traffic.open_loop_http_nemotron_h import (     # noqa: E402
    EXPERT_COUNTERS, balance_programs, judge)


def model_config(config: dict):
    """``chipbench/configs/<name>.json`` -> (``hybrid.HybridConfig`` of
    this chip's share, the published keys as the reference reads them,
    the held expert range: all of them)."""
    held = (0, config["n_routed_experts"])
    cfg = hybrid.HybridConfig.from_published(
        config, max_seq=config["engine"]["max_seq"],
        **config.get("hybrid_config", {}))
    return cfg, config, held


def balance_selection_bias(cfg, params, key, tokens: int, rounds: int,
                           sequences: int):
    """The tree with every experts sublayer's ``router_bias`` set so
    that, over ``tokens`` seeded ids (``sequences`` independent
    sequences of equal length) run through the program's own layer
    function, the k largest of ``score + bias`` load all the router's
    experts alike: the published recipe's sign update, sublayer by
    sublayer in order, each on the streams that the balanced sublayers
    before it leave (``open_loop_http_afmoe.balance_selection_bias``
    says why several sequences).  What the router scores is the ONE
    stream its sublayer sees: the four, mixed by that sublayer's own
    ``H_pre``."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import hyper_connections

    length = tokens // sequences
    tokens = sequences * length
    ids = jax.random.randint(key, (sequences, length), 0, cfg.vocab_size)
    n_valid = jnp.full((sequences,), length, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(length), (sequences, length))
    past = {hybrid.LATENT: (hybrid.causal_attend(cfg),
                            hybrid.rotary_tables(cfg, positions))}

    _, bias_of = balance_programs(cfg, tokens, rounds)
    seen = jax.jit(lambda lp, x: hyper_connections.mix_in(
        x, lp["hc"], iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
        clamp=cfg.hc_res_clamp)[0].reshape(1, tokens, -1))
    block = {kind: jax.jit(lambda lp, x, kind=kind: hybrid.block(
        cfg, kind, lp, x, past.get(kind), n_valid)[0])
        for kind in {kind for _, kind in cfg.sublayers}}
    x = hybrid.embed(cfg, params, ids)
    layers = [dict(lp) for lp in params["layers"]]
    for i, kind in cfg.sublayers:
        name = hybrid.slot_of(kind)
        if kind == hybrid.EXPERTS:
            layers[i][name] = {**layers[i][name], "router_bias": bias_of(
                layers[i][name], seen(layers[i][name], x))}
        x = block[kind](layers[i][name], x)
    return {**params, "layers": layers}


def make_params(cfg, config: dict, seed: int):
    """The cell's weights from the seed: ``hybrid.init_params``, the
    fixture's ``map_std`` where a toy width needs it, then
    ``balance_selection_bias`` as the configuration's ``selection_bias``
    says."""
    import jax

    from chipbench.model import fold_seed
    params = jax.jit(lambda key: seeded_maps(
        cfg, hybrid.init_params(cfg, key), config.get("map_std")))(
        jax.random.PRNGKey(fold_seed(seed, 0)))
    return jax.block_until_ready(balance_selection_bias(
        cfg, params, jax.random.PRNGKey(fold_seed(seed, 5)),
        **config["selection_bias"]))


def seeded_maps(cfg, params, map_std):
    """``init_params``' tree with every ``Phi`` scaled so that ``v Phi``
    has the standard deviation ``map_std``; None: as the program draws
    them (N(0, 0.02): 2.4 at the published 4 x 3,584 lanes, and all but
    constant maps at a toy width, which is what the CPU fixture sets
    this for)."""
    if map_std is None:
        return params
    scale = map_std / (0.02 * (cfg.hc_mult * cfg.d_model) ** 0.5)

    def scaled(sub):
        return {**sub, "hc": {**sub["hc"], "phi": sub["hc"]["phi"] * scale}}
    return {**params, "layers": [{k: scaled(v) for k, v in lp.items()}
                                 for lp in params["layers"]]}


def xing4_counters(handle) -> dict:
    st = handle.options(method_name="engine_stats").remote().result(
        timeout=30)
    return {**{k: st[k] for k in EXPERT_COUNTERS + LATENT_COUNTERS},
            "loop_account": st["loop_account"]}


def run(ctx) -> dict:
    import jax
    import numpy as np

    from ray_tpu import serve
    from ray_tpu._compile_cache import compile_cache_stats
    from ray_tpu.inference import EngineConfig, build_gpt_deployment

    from chipbench import stats, trace_reduce, xing4_trace
    from chipbench.host_watch import HostWatch
    from chipbench.model import device_memory_peak
    from chipbench.reference import xing4 as ref
    from chipbench.traffic_gen import chat_requests

    mix, config = ctx.mix, ctx.config
    if ctx.rehearse:
        # run.py's fixture knows the GPT keys only; this kind's CPU
        # sizes are a fixture of its own
        with open(os.path.join(ctx.root, "chipbench", "tests",
                               "rehearse_xing4.json")) as f:
            own = json.load(f)
        config = {**config, **own["config"]}
        mix = {**mix, **own["traffic"]}
    cfg, published, held = model_config(config)
    engine_cfg = EngineConfig(**config["engine"])
    stamps = stats.Stamps(ctx.t_start)
    stamps.mark("import_s")
    params = make_params(cfg, config, ctx.seed)
    stamps.mark("weights_s")
    ctx.log(f"weights on the device: {hybrid.num_params(params) / 1e6:.0f} M "
            f"parameters")
    handle = serve.run(
        build_gpt_deployment(name=ROUTE, cfg=cfg, engine_cfg=engine_cfg,
                             params=params, **config["deployment_args"]),
        use_actors=False, http=True)
    addr = serve.proxy_address()
    host, port = addr[len("http://"):].split(":")
    port = int(port)
    stamps.mark("programs_s")
    stamps.cache("after_programs", compile_cache_stats())
    ctx.log(f"deployment up at {addr}")
    trace = {}
    try:
        warm_up(ctx, host, port, cfg, engine_cfg.prefill_chunk)
        stamps.mark("warmup_s")
        ctx.log("warm-up done")

        # ---- the window
        requests = chat_requests(mix, ctx.seconds, ctx.seed, cfg.vocab_size)
        sent = [{k: r[k] for k in ("id", "due_s", "prompt", "max_tokens")}
                for r in requests]
        lead_s = max([0.0] + [-r["due_s"] for r in requests])
        t0 = time.monotonic() + 1.0 + lead_s
        setup_s = t0 - ctx.t_start
        stamps.mark("lead_in_s", at=t0)

        def counters():
            return {**engine_counters(handle), **xing4_counters(handle)}

        def sleep_until(t):
            time.sleep(max(0.0, t - time.monotonic()))

        def mid():
            """Runs here while the child offers the load."""
            sleep_until(t0)
            trace["at_window_start"] = counters()
            trace["compiles_at_start"] = compile_cache_stats()
            if ctx.trace:
                sleep_until(t0 + 0.45 * ctx.seconds)
                trace["dir"] = tempfile.mkdtemp(prefix="chipbench_trace_")
                # the counters over the traced seconds themselves, read
                # INSIDE the session (``open_loop_http_lfm2`` says why)
                jax.profiler.start_trace(trace["dir"])
                trace["traced_from"] = counters()
                time.sleep(min(mix["trace_s"], 0.4 * ctx.seconds))
                trace["traced_to"] = counters()
                jax.profiler.stop_trace()
            sleep_until(t0 + ctx.seconds)
            trace["at_window_end"] = counters()
            trace["compiles_at_end"] = compile_cache_stats()

        watch = HostWatch()
        watch.start(t0)
        recs = run_loadgen(ctx, host, port, sent, t0,
                           ctx.seconds + mix["drain_s"], mid=mid)
        host_watch = watch.report()
    finally:
        serve.shutdown()
    before, c0 = trace["at_window_start"], trace["compiles_at_start"]
    after, c1 = trace["at_window_end"], trace["compiles_at_end"]
    compiles_in_window = (c1["hits"] + c1["misses"]
                          - c0["hits"] - c0["misses"])
    stamps.cache("at_window_start", c0)
    del handle
    gc.collect()
    # the window's peak, read before the reference puts anything on the chip
    memory_peak = device_memory_peak(jax.devices())

    # ---- reduction (client side)
    by_id = {r["id"]: r for r in recs}
    client = client_metrics(requests, by_id, ctx.seconds)
    for r_id, why in client["failures"]:
        ctx.log(f"request {r_id} failed: {why}")
    ttft, gaps, late, failed = (client["ttft"], client["gaps"],
                                client["late"], len(client["failures"]))
    end_to_end = {}
    if ttft and gaps:
        end_to_end = {
            "ttft_p90_ms": 1e3 * stats.percentile(ttft, 90),
            "itl_p95_ms": 1e3 * stats.percentile(gaps, 95),
            "serve_tokens_per_s": client["tokens_in_window"] / ctx.seconds,
        }

    # ---- correctness: a seeded sample through the plain reference,
    # the longest among them, now that the engine's pool is freed
    done = [r for r in requests if by_id.get(r["id"], {}).get("ended")
            == "done"]
    t_ref = time.monotonic()
    picks = pick_checked(done, ctx.seed, mix["checked_requests"])
    margins, disagreed = [np.zeros(0)], 0
    for r in picks:
        emitted = by_id[r["id"]]["tokens"][:ref.MAX_EMITTED]
        m, best = ref.margins(params, r["prompt"], emitted, published, held,
                              engine_cfg.max_seq)
        margins.append(m)
        disagreed += int((best != np.asarray(emitted)).sum())
    stamps.notes["reference_check_s"] = time.monotonic() - t_ref
    margins = np.concatenate(margins)
    checked_tokens = len(margins)
    judged = judge(margins, mix)
    worst = float(margins.max()) if checked_tokens else 0.0
    ctx.log("reference: " + ", ".join(
        f"{k} {v['value']:.6f} (limit {v['limit']})"
        for k, v in judged.items())
        + f", worst {worst:.6f} over {len(picks)} requests; {disagreed} of "
        f"{checked_tokens} tokens are not the reference's argmax")
    correct, checks = verdict(failed, compiles_in_window, 0.0, 0.0,
                              len(picks), bool(end_to_end))
    del checks["worst_margin"]
    checks.update(judged)
    correct = correct and all(v["value"] <= v["limit"]
                              for v in judged.values())

    counted = COUNTERS + EXPERT_COUNTERS + LATENT_COUNTERS
    counters = {k: after[k] - before[k] for k in counted}
    counters["occupancy_sum"] = after["occupancy_sum"] \
        - before["occupancy_sum"]
    window = [r for r in requests if not r["lead"]]
    obs = {"window_s": ctx.seconds, "counters": counters,
           "max_slots": engine_cfg.max_slots, "published": published,
           "held": held, "block_size": engine_cfg.kv_block_size,
           "prefill_chunk": engine_cfg.prefill_chunk,
           "layers": cfg.n_latent,
           "expert_layers": cfg.n_layers - cfg.dense_layers}
    if ctx.trace and "dir" in trace:
        import shutil
        obs["traced_counters"] = {
            k: trace["traced_to"][k] - trace["traced_from"][k]
            for k in counted + ("occupancy_sum",)}
        path = trace_reduce.find_xplane(trace["dir"])
        obs["trace"] = trace_reduce.summarize(trace_reduce.load_events(path))
        other = {} if os.environ.get("CHIPBENCH_XING4_OPS") else None
        obs["scoped"] = xing4_trace.summarize(xing4_trace.load_events(
            path, xing4_trace.marks_of(published, engine_cfg.max_slots,
                                       engine_cfg.prefill_chunk), other))
        if other:
            # for the builder of the label table: the heaviest op texts
            # with their labels
            top = sorted(other.items(), key=lambda kv: -kv[1])[:300]
            with open(os.environ["CHIPBENCH_XING4_OPS"], "w") as f:
                json.dump({"ops": [[k[0], ns, k[1]] for k, ns in top],
                           "scoped": obs["scoped"]}, f)
        shutil.rmtree(trace["dir"], ignore_errors=True)
    passes = counters["decode_iterations"] + counters["chunk_passes"]
    notes = {
        "requests": len(requests), "rate_per_s": mix["rate_per_s"],
        "lead_in_requests": sum(1 for r in requests if r["lead"]),
        "in_flight_at_window_start": before["active_slots"]
        + before["waiting_requests"],
        "offered_tokens_per_s": sum(r["max_tokens"] for r in window)
        / ctx.seconds,
        "offered_prompt_tokens_per_s": sum(len(r["prompt"]) for r in window)
        / ctx.seconds,
        "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50) if ttft else None,
        "itl_p50_ms": 1e3 * stats.percentile(gaps, 50) if gaps else None,
        "ttft_samples": len(ttft), "itl_samples": len(gaps),
        "itl_ms_quantiles": {str(q): 1e3 * stats.percentile(gaps, q)
                             for q in (50, 80, 85, 90, 92, 94, 95, 96, 97,
                                       98, 99)} if gaps else None,
        "ttft_ms_quantiles": {str(q): 1e3 * stats.percentile(ttft, q)
                              for q in (50, 75, 80, 85, 88, 90, 92, 95)}
        if ttft else None,
        "lateness_p99_ms": 1e3 * stats.percentile(late, 99) if late else None,
        "in_flight_at_window_end": after["active_slots"]
        + after["waiting_requests"],
        "waiting_at_window_end": after["waiting_requests"],
        "blocks_free_at_window_end": after["blocks_free"],
        "cache_bytes": after["cache_bytes"],
        "counters": counters, "compiles_in_window": compiles_in_window,
        "chunk_pass_share": counters["chunk_passes"] / passes
        if passes else None,
        "worst_margin": worst,
        **{k: v["value"] for k, v in judged.items()},
        "margin_p50": float(np.median(margins)) if checked_tokens else 0.0,
        "tie_tolerance": mix["tie_tolerance"],
        "tail_tolerance": mix["tail_tolerance"],
        "checked_requests": len(picks), "checked_tokens": checked_tokens,
        "checked_context_lengths": [len(r["prompt"]) + len(
            by_id[r["id"]]["tokens"]) for r in picks],
        "tokens_not_reference_argmax": disagreed,
        "setup_stamps": stamps.notes, "host_watch": host_watch,
        "loop_ms_per_pass": loop_ms_per_pass(before["loop_account"],
                                             after["loop_account"]),
    }
    return {"correct": correct, "attempted": len(requests),
            "failed": failed, "setup_s": setup_s,
            "end_to_end": end_to_end, "obs": obs, "notes": notes,
            "checks": checks, "memory_peak_bytes": memory_peak}
