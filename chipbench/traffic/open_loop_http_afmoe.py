"""Traffic kind ``open_loop_http_afmoe``: the served path of the hybrid
layer function in its ``afmoe`` layout (Arcee Trinity: window and full
attention layers 3 : 1 over TWO groups of K/V pools, rotary on the
window layers only, per-head q/k norms, a sigmoid output gate, sandwich
norms, a leading dense layer, sigmoid-routed gated experts and a shared
one; ``ray_tpu/models/hybrid.py``) under a fixed offered rate.

The same run as ``open_loop_http_nemotron_h`` — ``serve.run(
build_gpt_deployment(...), use_actors=False, http=True)`` in this
process, streamed ``POST /v1/generate`` from the ``loadgen.py`` child,
greedy, ``eos`` off, the lead-in / window / drain of
``traffic_gen.chat_requests``, times taken at the client from when each
request was DUE, the gauges read at the window's two ends.  What this
kind owns: ``model_config`` (the configuration file's published keys,
the layers it holds, this chip's share of the experts and the
vocabulary), ``make_params`` (the program's init, the configuration's
``seeded_values`` laid over it, then the routers' selection bias
balanced by the published recipe's sign update, as the ``nemotron_h``
kind does and for its reason, over SEVERAL sequences), the reference it checks
against (``chipbench/reference/afmoe.py``), which requests it checks
(``pick_checked``: the two longest contexts first, so that a window
ignored, off by one, or a rotation on the wrong layers cannot pass), a
warm-up that runs a prompt LONGER than the window (both walks start
behind it), the label table of its trace (``chipbench/afmoe_trace.py``)
and the counters of the two pools (``WINDOW_COUNTERS``).  The load
generator, the client reduction, the engine's counters, the verdict,
the judge (two quantiles of the checked tokens' margins: this family's
router is discrete too) and the host watch are imported from the files
that have them.

The model module is imported FIRST, and asked for the layout: a checkout
whose program lacks it stops here, at once, with a message, before any
weight or pool is allocated.
"""

from __future__ import annotations

from ray_tpu.models import hybrid          # noqa: E402  (first: see above)

if not hasattr(hybrid, "WINDOW"):
    raise SystemExit("ray_tpu/models/hybrid.py of this checkout has no "
                     "afmoe layout (window attention layers over a second "
                     "group of K/V pools): the cell cannot run here")

import gc                                   # noqa: E402
import json                                 # noqa: E402
import os                                   # noqa: E402
import tempfile                             # noqa: E402
import time                                 # noqa: E402

from chipbench.traffic.open_loop_http import (COUNTERS, ROUTE,    # noqa: E402
                                              client_metrics,
                                              engine_counters, run_loadgen,
                                              verdict)
from chipbench.traffic.open_loop_http_nemotron_h import (     # noqa: E402
    EXPERT_COUNTERS, balance_programs, judge)

# the two pools' blocks (handed out, given back behind the window, held
# a pass beside what one table would hold), what the two window programs
# must read and multiply, and the chunk passes
WINDOW_COUNTERS = ("window_blocks_allocated", "window_blocks_returned",
                   "kv_blocks_allocated", "window_blocks_resident_sum",
                   "window_blocks_one_table_sum", "window_blocks_attended",
                   "window_chunk_keys", "window_query_keys",
                   "kv_blocks_attended", "kv_blocks_tabled", "chunk_passes",
                   "prefill_tokens", "chunk_keys", "chunk_query_keys",
                   "admissions")
GAUGES = ("window_blocks_held", "window_blocks_total")


def model_config(config: dict):
    """``chipbench/configs/<name>.json`` -> (``hybrid.HybridConfig`` of
    this chip's share, the published keys as the reference reads them,
    the held expert range).  The file keeps ``layer_types`` whole as
    published; ``layers_held`` names the published layers this chip
    holds, in order, of which the first ``num_dense_layers`` have the
    dense MLP."""
    # the router keeps its published width; the file's own key counts
    # the experts HELD here
    published = {**config,
                 "num_experts": config["published"]["num_experts"],
                 "layer_types": [config["layer_types"][i]
                                 for i in config["layers_held"]]}
    held = (0, config["num_experts"])
    cfg = hybrid.HybridConfig.from_published(
        published, vocab_size=config["vocab_size"], experts_held=held,
        max_seq=config["engine"]["max_seq"],
        **config.get("hybrid_config", {}))
    return cfg, published, held


def balance_selection_bias(cfg, params, key, tokens: int, rounds: int,
                           sequences: int):
    """The tree with every experts sublayer's ``router_bias`` set so
    that, over ``tokens`` seeded ids run through the program's own layer
    function, the k largest of ``score + bias`` load all the router's
    experts alike: the published recipe's sign update, sublayer by
    sublayer in order, each on the stream that the balanced sublayers
    before it leave (``open_loop_http_nemotron_h.balance_selection_bias``
    says why; here 0.5 held experts meet a token a layer, so a crowded
    router moves a decode pass's expert bytes more, not less).

    The ``tokens`` ids are ``sequences`` independent sequences of equal
    length, and the load that is evened is that of all of them together.
    Under seeded weights the tokens of ONE sequence share part of what
    the router sees (what attention brings them of their context): a
    bias balanced on one sequence cancels THAT sequence's share, and
    every other sequence meets its negative, the same one for a whole
    run (my chip runs, PR 48, the weights as first seeded: the held
    experts' part of a run's assignments read 9.8-19.2 % over eight
    seeds where the deployment says 12.5 %, and ``itl_p95_ms`` followed
    it)."""
    import jax
    import jax.numpy as jnp

    length = tokens // sequences
    tokens = sequences * length
    ids = jax.random.randint(key, (sequences, length), 0, cfg.vocab_size)
    n_valid = jnp.full((sequences,), length, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(length), (sequences, length))
    tables = hybrid.rotary_tables(cfg, positions)
    past = {hybrid.ATTENTION: hybrid.causal_attend(cfg),
            hybrid.WINDOW: (hybrid.causal_attend(cfg, cfg.window), tables)}

    # the sign update itself is the nemotron kind's; the sublayers it
    # runs between the routers are this layout's
    _, bias_of = balance_programs(cfg, tokens, rounds)
    block = {kind: jax.jit(lambda lp, x, kind=kind: hybrid.block(
        cfg, kind, lp, x, past.get(kind), n_valid)[0])
        for kind in {kind for _, kind in cfg.sublayers}}
    x = hybrid.embed(cfg, params, ids)
    layers = [dict(lp) for lp in params["layers"]]
    for i, kind in cfg.sublayers:
        name = hybrid.slot_of(kind)
        if kind == hybrid.EXPERTS:
            layers[i][name] = {**layers[i][name], "router_bias": bias_of(
                layers[i][name], x.reshape(1, tokens, -1))}
        x = block[kind](layers[i][name], x)
    return {**params, "layers": layers}


def seeded_values(cfg, params, embedding_std: float, post_norm_gain: float):
    """``hybrid.init_params``' tree with the embedding at
    ``embedding_std`` and every sublayer's output norm's gain at
    ``post_norm_gain`` (the configuration's ``seeded_values`` says why:
    a stream in which a token's own embedding is as large as what the
    sublayers add to it, so that a token's experts follow the token and
    not its sequence)."""
    import jax.numpy as jnp
    wte = params["wte"]
    # init_params draws N(0, 0.02 / embedding_multiplier)
    scale = embedding_std * cfg.embedding_multiplier / 0.02
    layers = [{slot: {**sub, "post_norm": jnp.full_like(sub["post_norm"],
                                                        post_norm_gain)}
               if "post_norm" in sub else sub for slot, sub in lp.items()}
              for lp in params["layers"]]
    return {**params, "layers": layers,
            "wte": (wte.astype(jnp.float32) * scale).astype(wte.dtype)}


def make_params(cfg, config: dict, seed: int):
    """The cell's weights from the seed: ``hybrid.init_params``, the
    configuration's ``seeded_values``, then ``balance_selection_bias``
    as its ``selection_bias`` says."""
    import jax

    from chipbench.model import fold_seed
    params = jax.jit(lambda key: seeded_values(
        cfg, hybrid.init_params(cfg, key), **config["seeded_values"]))(
        jax.random.PRNGKey(fold_seed(seed, 0)))
    return jax.block_until_ready(balance_selection_bias(
        cfg, params, jax.random.PRNGKey(fold_seed(seed, 5)),
        **config["selection_bias"]))


def pick_checked(done: list, seed: int, n: int) -> list:
    """The requests the reference follows: the TWO longest finished ones
    (prompt + served tokens; a run that finished fewer than two contexts
    past two windows is not ``correct``: ``run`` counts them) and ``n -
    2`` others drawn from the seed."""
    import numpy as np
    by_len = sorted(range(len(done)), key=lambda i: (
        -(len(done[i]["prompt"]) + done[i]["max_tokens"]), i))
    first = by_len[:2]
    rng = np.random.default_rng([int(seed), 11])
    rest = [int(i) for i in rng.permutation(len(done)) if int(i) not in first]
    return [done[i] for i in first + rest[:max(0, n - 2)]]


def window_counters(handle) -> dict:
    st = handle.options(method_name="engine_stats").remote().result(
        timeout=30)
    return {**{k: st[k] for k in EXPERT_COUNTERS + WINDOW_COUNTERS + GAUGES},
            "loop_account": st["loop_account"]}


def loop_ms_per_pass(before: dict, after: dict) -> dict:
    """The loop thread's time over the window by phase
    (``engine._LOOP_PHASES``), a pass: which phase a slow process is
    slow in."""
    passes = max(1, after["passes"] - before["passes"])
    out = {n: (ns - before["ns"][n]) / passes / 1e6
           for n, ns in after["ns"].items()}
    out["unaccounted"] = (after["unaccounted_ns"]
                          - before["unaccounted_ns"]) / passes / 1e6
    out["passes"] = passes
    return out


def run(ctx) -> dict:
    import jax
    import numpy as np

    from ray_tpu import serve
    from ray_tpu._compile_cache import compile_cache_stats
    from ray_tpu.inference import EngineConfig, build_gpt_deployment

    from chipbench import afmoe_trace, stats, trace_reduce
    from chipbench.host_watch import HostWatch
    from chipbench.model import device_memory_peak
    from chipbench.reference import afmoe as ref
    from chipbench.traffic_gen import chat_requests

    mix, config = ctx.mix, ctx.config
    if ctx.rehearse:
        # run.py's fixture knows the GPT keys only; this kind's CPU
        # sizes are a fixture of its own
        with open(os.path.join(ctx.root, "chipbench", "tests",
                               "rehearse_afmoe.json")) as f:
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
        # ---- warm-up (set-up): a prompt longer than the window plus a
        # chunk, its last chunk partial (both window walks start behind
        # the window, blocks go back to their pool), then decode steps
        rng = np.random.default_rng([int(ctx.seed), 9])
        warm = rng.integers(
            0, cfg.vocab_size,
            min(cfg.window + engine_cfg.prefill_chunk + 44,
                engine_cfg.max_seq - 8)).tolist()
        got = run_loadgen(ctx, host, port, [
            {"id": 0, "due_s": 0.0, "prompt": warm, "max_tokens": 4}],
            time.monotonic(), 900.0)[0]
        if got["ended"] != "done":
            raise RuntimeError(f"warm-up request failed: {got}")
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
            return {**engine_counters(handle), **window_counters(handle)}

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
                # the counters over the traced seconds themselves: with
                # prompts of 128-32,768 tokens the keys a pass attends in
                # those 4 s are not the window's mean, and a roofline
                # share divides work by the time of the SAME passes
                trace["traced_from"] = counters()
                jax.profiler.start_trace(trace["dir"])
                time.sleep(min(mix["trace_s"], 0.4 * ctx.seconds))
                jax.profiler.stop_trace()
                trace["traced_to"] = counters()
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
    # now that the engine's pools are freed
    done = [r for r in requests if by_id.get(r["id"], {}).get("ended")
            == "done"]
    t_ref = time.monotonic()
    long_over = mix["long_context_windows"] * cfg.window
    picks = pick_checked(done, ctx.seed, mix["checked_requests"])
    margins, disagreed, long_checked = [np.zeros(0)], 0, 0
    for r in picks:
        emitted = by_id[r["id"]]["tokens"][:ref.MAX_EMITTED]
        m, best = ref.margins(params, r["prompt"], emitted, published, held,
                              engine_cfg.max_seq)
        margins.append(m)
        disagreed += int((best != np.asarray(emitted)).sum())
        long_checked += len(r["prompt"]) > long_over
    stamps.notes["reference_check_s"] = time.monotonic() - t_ref
    # two QUANTILES of the checked tokens' margins, not their maximum
    # (``judge``: a router's near-tie flipped by a rounding moves a logit
    # in the served path and in the float8 control alike)
    margins = np.concatenate(margins)
    checked_tokens = len(margins)
    judged = judge(margins, mix)
    worst = float(margins.max()) if checked_tokens else 0.0
    ctx.log("reference: " + ", ".join(
        f"{k} {v['value']:.6f} (limit {v['limit']})"
        for k, v in judged.items())
        + f", worst {worst:.6f} over {len(picks)} requests ({long_checked} "
        f"past {long_over} tokens); {disagreed} of {checked_tokens} tokens "
        f"are not the reference's argmax")
    correct, checks = verdict(failed, compiles_in_window, 0.0, 0.0,
                              len(picks), bool(end_to_end))
    del checks["worst_margin"]
    checks.update(judged)
    checks["checked_requests"]["at_least"] = mix["checked_at_least"]
    checks["checked_past_two_windows"] = {
        "value": long_checked, "at_least": mix["long_checked_at_least"]}
    correct = (correct and len(picks) >= mix["checked_at_least"]
               and long_checked >= mix["long_checked_at_least"]
               and all(v["value"] <= v["limit"] for v in judged.values()))

    counters = {k: after[k] - before[k]
                for k in COUNTERS + EXPERT_COUNTERS + WINDOW_COUNTERS
                if k in after}
    counters["occupancy_sum"] = after["occupancy_sum"] \
        - before["occupancy_sum"]
    window = [r for r in requests if not r["lead"]]
    obs = {"window_s": ctx.seconds, "counters": counters,
           "max_slots": engine_cfg.max_slots, "published": published,
           "held": held, "block_size": engine_cfg.kv_block_size,
           "prefill_chunk": engine_cfg.prefill_chunk,
           "expert_layers": cfg.n_layers - cfg.dense_layers}
    if ctx.trace and "dir" in trace:
        import shutil
        from ray_tpu.inference.cache import PoolLayout
        obs["traced_counters"] = {
            k: trace["traced_to"][k] - trace["traced_from"][k]
            for k in COUNTERS + EXPERT_COUNTERS + WINDOW_COUNTERS
            if k in trace["traced_to"]}
        path = trace_reduce.find_xplane(trace["dir"])
        obs["trace"] = trace_reduce.summarize(trace_reduce.load_events(path))
        bs = engine_cfg.kv_block_size
        other = {} if os.environ.get("CHIPBENCH_AFMOE_OPS") else None
        rows = afmoe_trace.load_events(
            path, afmoe_trace.marks_of(
                config["engine"],
                PoolLayout(cfg.n_attention, engine_cfg.n_blocks + 1, bs,
                           *cfg.kv_geometry[1:]).shape,
                PoolLayout(cfg.n_window, after["window_blocks_total"] + 1,
                           bs, *cfg.window_geometry[1:3]).shape,
                cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                cfg.experts_per_token, cfg.n_experts), other)
        obs["scoped"] = afmoe_trace.summarize(rows)
        if other:
            # for the builder of the label table and of the recorded
            # fixture: the heaviest op texts with their labels, and the
            # rows of the first run of each program
            top = sorted(other.items(), key=lambda kv: -kv[1])[:160]
            first = {}
            for r in rows:
                if r[1] == trace_reduce.MODULES_LINE:
                    first.setdefault(r[2], (r[3], r[3] + r[4]))
            kept = [r for r in rows if any(
                lo <= r[3] < hi for lo, hi in first.values())]
            with open(os.environ["CHIPBENCH_AFMOE_OPS"], "w") as f:
                json.dump({"ops": [[k[0], ns, k[1]] for k, ns in top],
                           "rows": kept}, f)
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
        "prompts_past_the_window": sum(len(r["prompt"]) > cfg.window
                                       for r in window),
        "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50) if ttft else None,
        "itl_p50_ms": 1e3 * stats.percentile(gaps, 50) if gaps else None,
        "ttft_samples": len(ttft), "itl_samples": len(gaps),
        "itl_ms_quantiles": {str(q): 1e3 * stats.percentile(gaps, q)
                             for q in (50, 80, 85, 90, 92, 94, 95, 96, 97,
                                       98, 99)} if gaps else None,
        "ttft_ms_quantiles": {str(q): 1e3 * stats.percentile(ttft, q)
                              for q in (50, 75, 80, 85, 90, 95)}
        if ttft else None,
        # the judged p90 is ONE of these (nearest rank): [id, prompt
        # tokens, due s, ms], the longest first
        "ttft_longest": sorted(
            ([r["id"], len(r["prompt"]), r["due_s"],
              1e3 * (by_id[r["id"]]["token_s"][0] - r["due_s"])]
             for r in window if by_id.get(r["id"], {}).get("token_s")),
            key=lambda t: -t[3])[:16],
        "lateness_p99_ms": 1e3 * stats.percentile(late, 99) if late else None,
        "in_flight_at_window_end": after["active_slots"]
        + after["waiting_requests"],
        "waiting_at_window_end": after["waiting_requests"],
        "blocks_free_at_window_end": after["blocks_free"],
        "window_blocks_held_at_window_ends": [before["window_blocks_held"],
                                              after["window_blocks_held"]],
        "window_blocks_total": after["window_blocks_total"],
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
        "checked_past_two_windows": long_checked,
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
