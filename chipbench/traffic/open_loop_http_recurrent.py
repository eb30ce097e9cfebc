"""Traffic kind ``open_loop_http_recurrent``: the served path of a model
with recurrent layers (``ray_tpu/models/hybrid.py``) under a fixed
offered rate.

The same run as ``open_loop_http`` — ``serve.run(build_gpt_deployment(
...), use_actors=False, http=True)`` in this process, streamed ``POST
/v1/generate`` from the ``loadgen.py`` child, greedy, ``eos`` off, the
lead-in / window / drain of ``traffic_gen.chat_requests``, times taken at
the client from when each request was DUE — for the other model family:
the configuration file holds the published ``config.json`` keys, the
weights come from ``hybrid.init_params`` (one jitted call, bfloat16), the
check is ``chipbench/reference/hybrid_ssm_moe.py`` given this chip's share
of the experts and of the vocabulary.  The load generator, the client
reduction, the engine's counters and the traffic generator are imported
from the files that have them, not copied.

The model module is imported FIRST: a checkout whose program has no such
family fails here, at once, before any weight or pool is allocated.

Warm-up (set-up): ``warm_on_init``'s own request, then one prompt of two
chunks (the second partial) and a few decode steps — every program this
family's window can use: it has no full-width prefill and adopts no
prefix.  With ``--trace 1`` the engine's gauges are also polled once a
second during the window (``state_rows_share.serve`` is their mean).
"""

from __future__ import annotations

from ray_tpu.models import hybrid          # noqa: E402  (first: see above)

import gc                                   # noqa: E402
import json                                 # noqa: E402
import os                                   # noqa: E402
import tempfile                             # noqa: E402
import time                                 # noqa: E402

from chipbench.traffic.open_loop_http import (COUNTERS, ROUTE,    # noqa: E402
                                              client_metrics,
                                              engine_counters, pick_checked,
                                              run_loadgen, verdict)

# the load of every pass, and the held experts that a pass's tokens (of
# its decode rows alone: ``_decode``) really chose, summed over the
# expert layers (the roofline shares count the touched experts' bytes)
EXPERT_COUNTERS = ("expert_assignments_held", "expert_assignments_total",
                   "expert_load_max", "expert_touched_held",
                   "expert_touched_held_decode")


def model_config(config: dict):
    """``chipbench/configs/<name>.json`` -> (``hybrid.HybridConfig`` of
    this chip's share, the published keys as the reference reads them,
    the held expert range)."""
    # the router keeps its published width; the file's own key counts
    # the experts HELD here
    published = {**config, "num_local_experts":
                 config["published"]["num_local_experts"]}
    held = (0, config["num_local_experts"])
    cfg = hybrid.HybridConfig.from_published(
        published, vocab_size=config["vocab_size"], experts_held=held,
        max_seq=config["engine"]["max_seq"], **config.get("hybrid_config",
                                                          {}))
    return cfg, published, held


def expert_counters(handle) -> dict:
    st = handle.options(method_name="engine_stats").remote().result(
        timeout=30)
    out = {k: st[k] for k in EXPERT_COUNTERS}
    out["state_rows_in_use"] = st["state_rows_in_use"]
    out["state_bytes"] = st["state_bytes"]
    return out


def run(ctx) -> dict:
    import jax
    import numpy as np

    from ray_tpu import serve
    from ray_tpu._compile_cache import compile_cache_stats
    from ray_tpu.inference import EngineConfig, build_gpt_deployment

    from chipbench import scoped_trace, stats, trace_reduce
    from chipbench.model import device_memory_peak, fold_seed
    from chipbench.reference import hybrid_ssm_moe as ref
    from chipbench.traffic_gen import chat_requests

    mix, config = ctx.mix, ctx.config
    if ctx.rehearse:
        # run.py's fixture knows the other family's keys only; this
        # kind's CPU sizes are a fixture of its own
        with open(os.path.join(ctx.root, "chipbench", "tests",
                               "rehearse_recurrent.json")) as f:
            own = json.load(f)
        config = {**config, **own["config"]}
        mix = {**mix, **own["traffic"]}
    cfg, published, held = model_config(config)
    engine_cfg = EngineConfig(**config["engine"])
    stamps = stats.Stamps(ctx.t_start)
    stamps.mark("import_s")
    params = jax.jit(lambda key: hybrid.init_params(cfg, key))(
        jax.random.PRNGKey(fold_seed(ctx.seed, 0)))
    jax.block_until_ready(params)
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
    trace = {"polls": []}
    try:
        # ---- warm-up (set-up): two chunks, the second partial; decode
        rng = np.random.default_rng([int(ctx.seed), 9])
        warm = rng.integers(0, cfg.vocab_size,
                            engine_cfg.prefill_chunk + 44).tolist()
        got = run_loadgen(ctx, host, port, [
            {"id": 0, "due_s": 0.0, "prompt": warm, "max_tokens": 4}],
            time.monotonic(), 600.0)[0]
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
            return {**engine_counters(handle), **expert_counters(handle)}

        def sleep_until(t):
            """Sleep to ``t``; with a trace asked for, poll the gauges
            once a second on the way."""
            while True:
                left = t - time.monotonic()
                if left <= 0:
                    return
                if ctx.trace and "at_window_start" in trace and left > 1.0:
                    time.sleep(1.0)
                    trace["polls"].append(expert_counters(handle))
                else:
                    time.sleep(left)

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
                # the interpreter for seconds while it writes, and the
                # passes that catch up carry more rows than any traced
                # one (``open_loop_http_lfm2`` has the readings)
                jax.profiler.start_trace(trace["dir"])
                trace["traced_from"] = counters()
                time.sleep(min(mix["trace_s"], 0.4 * ctx.seconds))
                trace["traced_to"] = counters()
                jax.profiler.stop_trace()
            sleep_until(t0 + ctx.seconds)
            trace["at_window_end"] = counters()
            trace["compiles_at_end"] = compile_cache_stats()

        recs = run_loadgen(ctx, host, port, sent, t0,
                           ctx.seconds + mix["drain_s"], mid=mid)
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
    worst, disagreed, checked_tokens = 0.0, 0, 0
    for r in picks:
        emitted = by_id[r["id"]]["tokens"]
        m, best = ref.margins(params, r["prompt"], emitted, published, held,
                              engine_cfg.max_seq)
        worst = max(worst, float(m.max()))
        disagreed += int((best != np.asarray(emitted)).sum())
        checked_tokens += len(emitted)
    stamps.notes["reference_check_s"] = time.monotonic() - t_ref
    ctx.log(f"reference: worst margin {worst:.6f} over {len(picks)} "
            f"requests (tolerance {mix['tie_tolerance']}); {disagreed} of "
            f"{checked_tokens} tokens are not the reference's argmax")
    correct, checks = verdict(failed, compiles_in_window, worst,
                              mix["tie_tolerance"], len(picks),
                              bool(end_to_end))

    counters = {k: after[k] - before[k]
                for k in COUNTERS + EXPERT_COUNTERS}
    counters["occupancy_sum"] = after["occupancy_sum"] \
        - before["occupancy_sum"]
    polls = trace["polls"] or [before, after]
    obs = {"window_s": ctx.seconds, "counters": counters,
           "state_rows_mean": sum(p["state_rows_in_use"] for p in polls)
           / len(polls),
           "max_slots": engine_cfg.max_slots, "published": published,
           "held": held}
    if ctx.trace and "dir" in trace:
        import shutil
        obs["traced_counters"] = {
            k: trace["traced_to"][k] - trace["traced_from"][k]
            for k in COUNTERS + EXPERT_COUNTERS}
        path = trace_reduce.find_xplane(trace["dir"])
        obs["trace"] = trace_reduce.summarize(trace_reduce.load_events(path))
        k = published["num_experts_per_tok"]
        obs["scoped"] = scoped_trace.summarize(scoped_trace.load_events(
            path, assignment_rows=(engine_cfg.max_slots * k,
                                   engine_cfg.prefill_chunk * k)))
        shutil.rmtree(trace["dir"], ignore_errors=True)
    notes = {
        "requests": len(requests), "rate_per_s": mix["rate_per_s"],
        "lead_in_requests": sum(1 for r in requests if r["lead"]),
        "in_flight_at_window_start": before["active_slots"]
        + before["waiting_requests"],
        "offered_tokens_per_s": sum(r["max_tokens"] for r in requests
                                    if not r["lead"]) / ctx.seconds,
        "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50) if ttft else None,
        "itl_p50_ms": 1e3 * stats.percentile(gaps, 50) if gaps else None,
        "ttft_samples": len(ttft), "itl_samples": len(gaps),
        "lateness_p99_ms": 1e3 * stats.percentile(late, 99) if late else None,
        "in_flight_at_window_end": after["active_slots"]
        + after["waiting_requests"],
        "waiting_at_window_end": after["waiting_requests"],
        "blocks_free_at_window_end": after["blocks_free"],
        "cache_bytes": after["cache_bytes"],
        "state_bytes": after["state_bytes"],
        "counters": counters, "compiles_in_window": compiles_in_window,
        "worst_margin": worst, "tie_tolerance": mix["tie_tolerance"],
        "checked_requests": len(picks), "checked_tokens": checked_tokens,
        "tokens_not_reference_argmax": disagreed,
        "setup_stamps": stamps.notes,
    }
    return {"correct": correct, "attempted": len(requests),
            "failed": failed, "setup_s": setup_s,
            "end_to_end": end_to_end, "obs": obs, "notes": notes,
            "checks": checks, "memory_peak_bytes": memory_peak}
