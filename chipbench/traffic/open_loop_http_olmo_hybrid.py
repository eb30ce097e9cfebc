"""Traffic kind ``open_loop_http_olmo_hybrid``: the served path of the
hybrid layer function in its ``olmo_hybrid`` layout (gated delta-rule
linear attention over a matrix state beside full attention with q/k
norms over 30 K/V heads, a dense gated MLP in every layer, the norms on
the sublayers' outputs; ``ray_tpu/models/hybrid.py``) under a fixed
offered rate.

The same run as ``open_loop_http_recurrent`` — ``serve.run(
build_gpt_deployment(...), use_actors=False, http=True)`` in this
process, streamed ``POST /v1/generate`` from the ``loadgen.py`` child,
greedy, ``eos`` off, the lead-in / window / drain of
``traffic_gen.chat_requests``, times taken at the client from when each
request was DUE, warm-up of both programs, the gauges read at the
window's two ends.  What this kind owns: ``model_config`` (the
``olmo_hybrid`` keys of the configuration file: nothing is held back
but depth), the reference it checks against
(``chipbench/reference/olmo_hybrid.py``), the label table of its trace
(``chipbench/olmo_hybrid_trace.py``), the counters of the state pool and
of the two forms of the delta rule (``LINEAR_COUNTERS``) and
``make_params``.  The model has no router, so the check is the MAXIMUM
margin over the checked tokens (``tie_tolerance``, as the XL and granite
cells).  The load generator, the client reduction, the engine's
counters, the sample, the verdict and the host watch are imported from
the files that have them.

The model module is imported FIRST, and asked for the layout: a checkout
whose program lacks it stops here, at once, with a message, before any
weight or pool is allocated.
"""

from __future__ import annotations

from ray_tpu.models import hybrid          # noqa: E402  (first: see above)

if not hasattr(hybrid, "LINEAR"):
    raise SystemExit("ray_tpu/models/hybrid.py of this checkout has no "
                     "olmo_hybrid layout (linear attention over a matrix "
                     "state): the cell cannot run here")

import gc                                   # noqa: E402
import json                                 # noqa: E402
import os                                   # noqa: E402
import tempfile                             # noqa: E402
import time                                 # noqa: E402

from chipbench.traffic.open_loop_http import (COUNTERS, ROUTE,    # noqa: E402
                                              client_metrics,
                                              engine_counters, pick_checked,
                                              run_loadgen, verdict)

# the state pool's gauges, the chunk passes and what they ran, and the
# two forms of the delta rule: rows whose matrix state a decode pass
# wrote, real prompt tokens through the window form
LINEAR_COUNTERS = ("chunk_passes", "prefill_tokens", "chunk_keys",
                   "chunk_query_keys", "linear_state_rows_advanced",
                   "linear_chunk_tokens", "kv_blocks_attended",
                   "admissions")
GAUGES = ("state_rows_in_use", "state_bytes")


def model_config(config: dict):
    """``chipbench/configs/<name>.json`` -> (``hybrid.HybridConfig``,
    the published keys as the reference reads them).  The file's keys
    ARE the served model's: the cut is depth alone."""
    cfg = hybrid.HybridConfig.from_published(
        config, max_seq=config["engine"]["max_seq"],
        **config.get("hybrid_config", {}))
    return cfg, config


def make_params(cfg, seed: int):
    """The cell's weights from the seed: the program's init, one jitted
    call."""
    import jax

    from chipbench.model import fold_seed
    return jax.block_until_ready(jax.jit(
        lambda key: hybrid.init_params(cfg, key))(
            jax.random.PRNGKey(fold_seed(seed, 0))))


def linear_counters(handle) -> dict:
    st = handle.options(method_name="engine_stats").remote().result(
        timeout=30)
    return {k: st[k] for k in LINEAR_COUNTERS + GAUGES}


def run(ctx) -> dict:
    import jax
    import numpy as np

    from ray_tpu import serve
    from ray_tpu._compile_cache import compile_cache_stats
    from ray_tpu.inference import EngineConfig, build_gpt_deployment

    from chipbench import olmo_hybrid_trace, stats, trace_reduce
    from chipbench.host_watch import HostWatch
    from chipbench.model import device_memory_peak
    from chipbench.reference import olmo_hybrid as ref
    from chipbench.traffic_gen import chat_requests

    mix, config = ctx.mix, ctx.config
    if ctx.rehearse:
        # run.py's fixture knows the GPT keys only; this kind's CPU
        # sizes are a fixture of its own
        with open(os.path.join(ctx.root, "chipbench", "tests",
                               "rehearse_olmo_hybrid.json")) as f:
            own = json.load(f)
        config = {**config, **own["config"]}
        mix = {**mix, **own["traffic"]}
    cfg, published = model_config(config)
    engine_cfg = EngineConfig(**config["engine"])
    stamps = stats.Stamps(ctx.t_start)
    stamps.mark("import_s")
    params = make_params(cfg, ctx.seed)
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
        # ---- warm-up (set-up): two chunks, the second partial; decode
        rng = np.random.default_rng([int(ctx.seed), 9])
        warm = rng.integers(0, cfg.vocab_size,
                            engine_cfg.prefill_chunk + 44).tolist()
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
            return {**engine_counters(handle), **linear_counters(handle)}

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
                jax.profiler.start_trace(trace["dir"])
                time.sleep(min(mix["trace_s"], 0.4 * ctx.seconds))
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
    # now that the engine's pools are freed
    done = [r for r in requests if by_id.get(r["id"], {}).get("ended")
            == "done"]
    t_ref = time.monotonic()
    picks = pick_checked(done, ctx.seed, mix["checked_requests"])
    margins, disagreed = [np.zeros(0)], 0
    for r in picks:
        emitted = by_id[r["id"]]["tokens"]
        m, best = ref.margins(params, r["prompt"], emitted, published,
                              engine_cfg.max_seq)
        margins.append(m)
        disagreed += int((best != np.asarray(emitted)).sum())
    stamps.notes["reference_check_s"] = time.monotonic() - t_ref
    margins = np.concatenate(margins)
    checked_tokens = len(margins)
    worst = float(margins.max()) if checked_tokens else 0.0
    ctx.log(f"reference: worst margin {worst:.6f} over {len(picks)} "
            f"requests (tolerance {mix['tie_tolerance']}); {disagreed} of "
            f"{checked_tokens} tokens are not the reference's argmax")
    correct, checks = verdict(failed, compiles_in_window, worst,
                              mix["tie_tolerance"], len(picks),
                              bool(end_to_end))
    checks["checked_requests"]["at_least"] = mix["checked_at_least"]
    correct = correct and len(picks) >= mix["checked_at_least"]

    counters = {k: after[k] - before[k] for k in COUNTERS + LINEAR_COUNTERS}
    counters["occupancy_sum"] = after["occupancy_sum"] \
        - before["occupancy_sum"]
    window = [r for r in requests if not r["lead"]]
    obs = {"window_s": ctx.seconds, "counters": counters,
           "max_slots": engine_cfg.max_slots, "published": published,
           "block_size": engine_cfg.kv_block_size,
           "prefill_chunk": engine_cfg.prefill_chunk}
    if ctx.trace and "dir" in trace:
        import shutil
        path = trace_reduce.find_xplane(trace["dir"])
        obs["trace"] = trace_reduce.summarize(trace_reduce.load_events(path))
        obs["scoped"] = olmo_hybrid_trace.summarize(
            olmo_hybrid_trace.load_events(path, olmo_hybrid_trace.marks_of(
                published, engine_cfg.max_slots, engine_cfg.prefill_chunk,
                [i for i, k in enumerate(cfg.layer_types)
                 if k == hybrid.ATTENTION])))
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
        # the judged tails' neighbourhood: a p95 that sits on the edge
        # between two clusters of gaps shows here as a jump
        "itl_ms_quantiles": {str(q): 1e3 * stats.percentile(gaps, q)
                             for q in (50, 80, 85, 90, 92, 94, 95, 96, 97,
                                       98, 99)} if gaps else None,
        "ttft_ms_quantiles": {str(q): 1e3 * stats.percentile(ttft, q)
                              for q in (50, 75, 80, 85, 90, 95)}
        if ttft else None,
        "lateness_p99_ms": 1e3 * stats.percentile(late, 99) if late else None,
        "in_flight_at_window_end": after["active_slots"]
        + after["waiting_requests"],
        "waiting_at_window_end": after["waiting_requests"],
        "blocks_free_at_window_end": after["blocks_free"],
        "cache_bytes": after["cache_bytes"],
        "state_bytes": after["state_bytes"],
        "state_rows_at_window_ends": [before["state_rows_in_use"],
                                      after["state_rows_in_use"]],
        "counters": counters, "compiles_in_window": compiles_in_window,
        "chunk_pass_share": counters["chunk_passes"] / passes
        if passes else None,
        "worst_margin": worst, "tie_tolerance": mix["tie_tolerance"],
        "margin_p50": float(np.median(margins)) if checked_tokens else 0.0,
        "margin_p99": float(np.percentile(margins, 99))
        if checked_tokens else 0.0,
        "checked_requests": len(picks), "checked_tokens": checked_tokens,
        "tokens_not_reference_argmax": disagreed,
        "setup_stamps": stamps.notes, "host_watch": host_watch,
    }
    return {"correct": correct, "attempted": len(requests),
            "failed": failed, "setup_s": setup_s,
            "end_to_end": end_to_end, "obs": obs, "notes": notes,
            "checks": checks, "memory_peak_bytes": memory_peak}
