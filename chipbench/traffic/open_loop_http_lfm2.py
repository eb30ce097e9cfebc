"""Traffic kind ``open_loop_http_lfm2``: the served path of the hybrid
layer function in its ``lfm2_moe`` layout (Liquid AI LFM2-8B-A1B: gated
short-convolution layers beside rotary grouped-query attention 3 : 1,
two leading dense layers, sigmoid-and-bias-routed gated experts with no
shared expert, a tied head; ``ray_tpu/models/hybrid.py``) under a fixed
offered rate, with prefixes ADOPTED across the recurrent state.

The same run as ``open_loop_http_afmoe`` — ``serve.run(
build_gpt_deployment(...), use_actors=False, http=True)`` in this
process, streamed ``POST /v1/generate`` from the ``loadgen.py`` child,
greedy, ``eos`` off, the lead-in / window / drain of
``traffic_gen.chat_requests``, times taken at the client from when each
request was DUE, the gauges read at the window's two ends.  What this
kind owns: ``model_config`` (the configuration file's published keys and
the layers it holds: every expert, the whole vocabulary), ``make_params``
(the program's init, the configuration's ``seeded_values`` laid over it,
then the routers' selection bias balanced by the published recipe's sign
update over several sequences, as the ``afmoe`` kind does and for its
reason), the reference it checks against (``chipbench/reference/
lfm2.py``), which requests it checks (``pick_checked``: requests that
ADOPTED a tenant's head and requests that prefilled cold, both, by
construction: a state restored wrongly moves the first, a window form or
a snapshot written wrongly the second), a warm-up that serves one head
twice (the second adopts it), the label table of its trace
(``chipbench/lfm2_trace.py``) and the snapshots' counters
(``SNAPSHOT_COUNTERS``).  The load generator, the client reduction, the
engine's counters, the verdict, the judge (two quantiles of the checked
tokens' margins: this family's router is discrete too) and the host
watch are imported from the files that have them.

The model module is imported FIRST, and asked for the layout: a checkout
whose program lacks it stops here, at once, with a message, before any
weight or pool is allocated.
"""

from __future__ import annotations

from ray_tpu.models import hybrid          # noqa: E402  (first: see above)

if not hasattr(hybrid, "SHORT_CONV"):
    raise SystemExit("ray_tpu/models/hybrid.py of this checkout has no "
                     "lfm2_moe layout (gated short-convolution layers, "
                     "state snapshots kept with the K/V blocks): the cell "
                     "cannot run here")

import gc                                   # noqa: E402
import json                                 # noqa: E402
import os                                   # noqa: E402
import tempfile                             # noqa: E402
import time                                 # noqa: E402

from chipbench.traffic.open_loop_http import (COUNTERS, ROUTE,    # noqa: E402
                                              client_metrics,
                                              engine_counters, run_loadgen,
                                              verdict)
from chipbench.traffic.open_loop_http_afmoe import loop_ms_per_pass  # noqa: E402
from chipbench.traffic.open_loop_http_nemotron_h import (     # noqa: E402
    EXPERT_COUNTERS, balance_programs, judge)

# the snapshots (written with a block's last token, restored by an
# adopting admission), what adoption saved, and the passes
SNAPSHOT_COUNTERS = ("state_snapshots_written", "state_snapshots_restored",
                     "admissions", "prefix_blocks_adopted", "chunk_passes",
                     "chunks_in_step", "prefill_tokens", "kv_blocks_attended",
                     "kv_blocks_tabled", "chunk_keys", "chunk_query_keys")
GAUGES = ("state_rows_in_use", "state_bytes", "state_snapshot_bytes",
          "prefix_cached_blocks")


def model_config(config: dict):
    """``chipbench/configs/<name>.json`` -> (``hybrid.HybridConfig`` of
    this chip's share, the published keys as the reference reads them,
    the held expert range).  The file keeps ``layer_types`` whole as
    published; ``layers_held`` names the published layers this chip
    holds, in order, of which the first ``num_dense_layers`` have the
    dense MLP."""
    published = {**config, "layer_types": [config["layer_types"][i]
                                           for i in config["layers_held"]]}
    held = (0, config["num_experts"])
    cfg = hybrid.HybridConfig.from_published(
        published, max_seq=config["engine"]["max_seq"],
        **config.get("hybrid_config", {}))
    return cfg, published, held


def balance_selection_bias(cfg, params, key, tokens: int, rounds: int,
                           sequences: int):
    """The tree with every experts sublayer's ``router_bias`` set so
    that, over ``tokens`` seeded ids (``sequences`` independent
    sequences of equal length) run through the program's own layer
    function, the k largest of ``score + bias`` load all the router's
    experts alike: the published recipe's sign update, sublayer by
    sublayer in order, each on the stream that the balanced sublayers
    before it leave (``open_loop_http_afmoe.balance_selection_bias``
    says why several sequences)."""
    import jax
    import jax.numpy as jnp

    length = tokens // sequences
    tokens = sequences * length
    ids = jax.random.randint(key, (sequences, length), 0, cfg.vocab_size)
    n_valid = jnp.full((sequences,), length, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(length), (sequences, length))
    past = {hybrid.ATTENTION: (hybrid.causal_attend(cfg),
                               hybrid.rotary_tables(cfg, positions)),
            hybrid.SHORT_CONV: hybrid.zero_state(cfg, sequences)}

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


def seeded_values(cfg, params, embedding_std: float):
    """``hybrid.init_params``' tree with the tied embedding at
    ``embedding_std`` (the configuration's ``seeded_values`` says
    why)."""
    import jax.numpy as jnp
    wte = params["wte"]
    # init_params draws N(0, 0.02 / embedding_multiplier)
    scale = embedding_std * cfg.embedding_multiplier / 0.02
    return {**params,
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


def adopters(requests: list) -> dict:
    """{request id: True where the request carries a tenant's head that
    an EARLIER request of the run (lead-in included) asked, so that its
    admission finds the head cached; False where it prefills cold: no
    head, or the first to ask its own}."""
    seen, out = set(), {}
    for r in sorted(requests, key=lambda r: r["due_s"]):
        out[r["id"]] = r["head"] is not None and r["head"] in seen
        seen.add(r["head"])
    return out


def cold_share(requests: list) -> float:
    """Share of the WINDOW's requests that prefill their whole prompt."""
    adopts = adopters(requests)
    window = [r for r in requests if not r["lead"]]
    return sum(not adopts[r["id"]] for r in window) / max(1, len(window))


def pick_checked(done: list, adopts: dict, seed: int, n: int) -> list:
    """The requests the reference follows: ``n`` finished ones drawn
    from the seed, half of them adopters and half cold (as far as each
    group reaches), the longest context of each group first."""
    import numpy as np
    rng = np.random.default_rng([int(seed), 11])
    picks = []
    for want, group in ((n - n // 2, True), (n // 2, False)):
        own = [r for r in done if adopts[r["id"]] == group]
        if not own:
            continue
        longest = max(own, key=lambda r: (len(r["prompt"])
                                          + r["max_tokens"], -r["id"]))
        rest = [own[int(i)] for i in rng.permutation(len(own))
                if own[int(i)] is not longest]
        picks += [longest] + rest[:max(0, want - 1)]
    return picks


def snapshot_counters(handle) -> dict:
    st = handle.options(method_name="engine_stats").remote().result(
        timeout=30)
    return {**{k: st[k] for k in EXPERT_COUNTERS + SNAPSHOT_COUNTERS
               + GAUGES}, "loop_account": st["loop_account"]}


def run(ctx) -> dict:
    import jax
    import numpy as np

    from ray_tpu import serve
    from ray_tpu._compile_cache import compile_cache_stats
    from ray_tpu.inference import EngineConfig, build_gpt_deployment

    from chipbench import lfm2_trace, stats, trace_reduce
    from chipbench.host_watch import HostWatch
    from chipbench.model import device_memory_peak
    from chipbench.reference import lfm2 as ref
    from chipbench.traffic_gen import chat_requests

    mix, config = ctx.mix, ctx.config
    if ctx.rehearse:
        # run.py's fixture knows the GPT keys only; this kind's CPU
        # sizes are a fixture of its own
        with open(os.path.join(ctx.root, "chipbench", "tests",
                               "rehearse_lfm2.json")) as f:
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
        # ---- warm-up (set-up): a prompt of a head and a tail over
        # several chunks, the last partial, then decode steps; then the
        # same head with another tail WHILE the first still decodes: it
        # adopts the head's blocks and restores the state from the last
        # one's snapshot, and its chunk rides a decode step
        rng = np.random.default_rng([int(ctx.seed), 9])
        bs, C = engine_cfg.kv_block_size, engine_cfg.prefill_chunk
        n_head = min(2 * C, (engine_cfg.max_seq // 2) // bs * bs)
        head = rng.integers(0, cfg.vocab_size, n_head).tolist()

        def tail(n):
            return rng.integers(0, cfg.vocab_size, n).tolist()
        t_warm = time.monotonic()
        got = run_loadgen(ctx, host, port, [
            {"id": 0, "due_s": 0.0, "prompt": head + tail(C // 2 + 5),
             "max_tokens": 48},
            {"id": 1, "due_s": 0.05, "prompt": tail(C + 9),
             "max_tokens": 8},
            {"id": 2, "due_s": 0.1, "prompt": head + tail(C // 4 + 3),
             "max_tokens": 8}], t_warm + 1.0, 900.0)
        if any(g["ended"] != "done" for g in got):
            raise RuntimeError(f"warm-up requests failed: {got}")
        warm = snapshot_counters(handle)
        if not warm["state_snapshots_restored"]:
            raise RuntimeError(f"warm-up adopted nothing: {warm}")
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
            return {**engine_counters(handle), **snapshot_counters(handle)}

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
                # the counters over the traced seconds themselves: a
                # roofline share divides work by the time of the SAME
                # passes.  Read INSIDE the session: ``stop_trace`` holds
                # the interpreter for seconds while it writes, the loop
                # falls behind, and the passes that catch up carry more
                # rows and touch more experts than any traced one (read
                # around the session, the experts' share read 101-102 %
                # where the window's own counters give 97-98: my chip
                # runs, PR 52, call 4)
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
    # now that the engine's pools are freed: adopters AND cold requests
    done = [r for r in requests if by_id.get(r["id"], {}).get("ended")
            == "done"]
    adopts = adopters(requests)
    t_ref = time.monotonic()
    picks = pick_checked(done, adopts, ctx.seed, mix["checked_requests"])
    margins, disagreed = [np.zeros(0)], 0
    checked = {True: 0, False: 0}
    for r in picks:
        emitted = by_id[r["id"]]["tokens"][:ref.MAX_EMITTED]
        m, best = ref.margins(params, r["prompt"], emitted, published, held,
                              engine_cfg.max_seq)
        margins.append(m)
        disagreed += int((best != np.asarray(emitted)).sum())
        checked[adopts[r["id"]]] += 1
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
        + f", worst {worst:.6f} over {len(picks)} requests "
        f"({checked[True]} adopted a head, {checked[False]} cold); "
        f"{disagreed} of {checked_tokens} tokens are not the reference's "
        f"argmax")
    correct, checks = verdict(failed, compiles_in_window, 0.0, 0.0,
                              len(picks), bool(end_to_end))
    del checks["worst_margin"]
    checks.update(judged)
    checks["checked_adopted"] = {"value": checked[True],
                                 "at_least": mix["checked_adopted_at_least"]}
    checks["checked_cold"] = {"value": checked[False],
                              "at_least": mix["checked_cold_at_least"]}
    counters = {k: after[k] - before[k]
                for k in COUNTERS + EXPERT_COUNTERS + SNAPSHOT_COUNTERS}
    # the cell is ABOUT adoption: a window in which no state was
    # restored measured something else
    checks["state_snapshots_restored"] = {
        "value": counters["state_snapshots_restored"], "at_least": 1}
    correct = (correct
               and all(v["value"] >= v["at_least"] for v in checks.values()
                       if "at_least" in v)
               and all(v["value"] <= v["limit"] for v in judged.values()))

    counters["occupancy_sum"] = after["occupancy_sum"] \
        - before["occupancy_sum"]
    window = [r for r in requests if not r["lead"]]
    polls = [trace[k] for k in ("at_window_start", "traced_from",
                                "traced_to", "at_window_end") if k in trace]
    obs = {"window_s": ctx.seconds, "counters": counters,
           "max_slots": engine_cfg.max_slots, "published": published,
           "held": held, "block_size": engine_cfg.kv_block_size,
           "prefill_chunk": engine_cfg.prefill_chunk,
           "expert_layers": cfg.n_layers - cfg.dense_layers,
           "conv_layers": cfg.n_short_conv,
           "state_rows_mean": sum(p["state_rows_in_use"] for p in polls)
           / len(polls)}
    if ctx.trace and "dir" in trace:
        import shutil
        obs["traced_counters"] = {
            k: trace["traced_to"][k] - trace["traced_from"][k]
            for k in COUNTERS + EXPERT_COUNTERS + SNAPSHOT_COUNTERS}
        obs["traced_counters"]["occupancy_sum"] = (
            trace["traced_to"]["occupancy_sum"]
            - trace["traced_from"]["occupancy_sum"])
        path = trace_reduce.find_xplane(trace["dir"])
        obs["trace"] = trace_reduce.summarize(trace_reduce.load_events(path))
        other = {} if os.environ.get("CHIPBENCH_LFM2_OPS") else None
        rows = lfm2_trace.load_events(path, lfm2_trace.marks_of(
            config["engine"], cfg), other)
        obs["scoped"] = lfm2_trace.summarize(rows)
        if other:
            # for the builder of the label table: the heaviest op texts
            # with their labels
            top = sorted(other.items(), key=lambda kv: -kv[1])[:200]
            with open(os.environ["CHIPBENCH_LFM2_OPS"], "w") as f:
                json.dump({"ops": [[k[0], ns, k[1]] for k, ns in top],
                           "scoped": obs["scoped"]}, f)
        shutil.rmtree(trace["dir"], ignore_errors=True)
    passes = counters["decode_iterations"] + counters["chunk_passes"] \
        - counters["chunks_in_step"]
    notes = {
        "requests": len(requests), "rate_per_s": mix["rate_per_s"],
        "lead_in_requests": sum(1 for r in requests if r["lead"]),
        "in_flight_at_window_start": before["active_slots"]
        + before["waiting_requests"],
        "offered_tokens_per_s": sum(r["max_tokens"] for r in window)
        / ctx.seconds,
        "offered_prompt_tokens_per_s": sum(len(r["prompt"]) for r in window)
        / ctx.seconds,
        "cold_request_share": cold_share(requests),
        "heads_first_asked_in_window": len(
            {r["head"] for r in window if r["head"] is not None}
            - {r["head"] for r in requests if r["lead"]}),
        "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50) if ttft else None,
        "itl_p50_ms": 1e3 * stats.percentile(gaps, 50) if gaps else None,
        "ttft_samples": len(ttft), "itl_samples": len(gaps),
        "itl_ms_quantiles": {str(q): 1e3 * stats.percentile(gaps, q)
                             for q in (50, 80, 85, 90, 92, 94, 95, 96, 97,
                                       98, 99)} if gaps else None,
        "ttft_ms_quantiles": {str(q): 1e3 * stats.percentile(ttft, q)
                              for q in (50, 70, 75, 80, 85, 88, 90, 92, 95)}
        if ttft else None,
        "lateness_p99_ms": 1e3 * stats.percentile(late, 99) if late else None,
        "in_flight_at_window_end": after["active_slots"]
        + after["waiting_requests"],
        "waiting_at_window_end": after["waiting_requests"],
        "blocks_free_at_window_end": after["blocks_free"],
        "prefix_cached_blocks_at_window_end": after["prefix_cached_blocks"],
        "cache_bytes": after["cache_bytes"],
        "state_bytes": after["state_bytes"],
        "state_snapshot_bytes": after["state_snapshot_bytes"],
        "counters": counters, "compiles_in_window": compiles_in_window,
        "chunk_pass_share": (counters["chunk_passes"]
                             - counters["chunks_in_step"]) / passes
        if passes else None,
        "worst_margin": worst,
        **{k: v["value"] for k, v in judged.items()},
        "margin_p50": float(np.median(margins)) if checked_tokens else 0.0,
        "tie_tolerance": mix["tie_tolerance"],
        "tail_tolerance": mix["tail_tolerance"],
        "checked_requests": len(picks), "checked_tokens": checked_tokens,
        "checked_adopted": checked[True], "checked_cold": checked[False],
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
