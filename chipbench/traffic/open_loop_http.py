"""Traffic kind ``open_loop_http``: the served path under a fixed offered rate.

``serve.run(build_gpt_deployment(...), use_actors=False, http=True)`` as
the README's quick start has it, in this process (which holds the
chip); requests arrive over real HTTP as streamed ``POST
/v1/generate`` from ``chipbench/loadgen.py``, a child process that
never imports JAX.  Greedy decoding, ``eos`` off, so ``max_tokens``
fixes every output length.

Set-up: weights made on the device in one jitted call from the seed,
in the type they are served in (the configuration's
``weights_served_as``: the engine then holds the very arrays it was
handed, and no float32 copy of them lives through the window),
the deployment built, then four warm-up requests that visit every
program the window can use (full-width prefill, chunk prefill with a
partial last chunk, decode, prefix adoption with a copy-on-write tail).
Then a lead-in of ``lead_s`` (still set-up): the same cycle of requests
at the same rate, so that the window opens on a system in its steady
state.  The window: requests due over ``--seconds`` at the mix's fixed
rate; after it, a drain of ``drain_s``; what is unfinished then,
refused or failed counts as ``failed``.  Times are taken at the client,
from when each request was DUE.  After the engine is shut down, its pool
freed and the peak memory read, a seeded sample of completed requests
(the longest among them) is teacher-forced through the plain reference.

``notes.setup_stamps`` splits ``setup_s`` into ``import_s`` (process
start to this kind's imports done), ``weights_s``, ``programs_s``
(``serve.run``: the engine built and, with ``warm_on_init``, its
programs compiled or loaded; compile-cache hits and misses beside it),
``warmup_s`` and ``lead_in_s``; ``reference_check_s`` follows the window
and is not part of ``setup_s``.
"""

from __future__ import annotations

import gc
import json
import os
import subprocess
import sys
import tempfile
import time

ROUTE = "v1"
COUNTERS = ("decode_iterations", "prefix_hit_tokens", "prefix_lookup_tokens",
            "preemptions", "generated_tokens", "requests_completed",
            "row_steps")


def engine_counters(handle) -> dict:
    """The engine's own counts, through the deployment handle."""
    st = handle.options(method_name="engine_stats").remote().result(
        timeout=30)
    out = {k: st[k] for k in COUNTERS}
    out["occupancy_sum"] = st["batch_occupancy"] * st["decode_iterations"]
    out["active_slots"] = st["active_slots"]
    out["waiting_requests"] = st["waiting_requests"]
    out["blocks_free"] = st["blocks_free"]
    out["cache_bytes"] = st["cache_bytes"]
    return out


def run_loadgen(ctx, host: str, port: int, requests: list, t0: float,
                deadline_s: float, mid=None) -> list:
    """Run the child over ``requests``; ``mid()`` runs here meanwhile."""
    fd, path = tempfile.mkstemp(prefix="chipbench_sched_", suffix=".json")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump({"host": host, "port": port, "route": ROUTE,
                       "t0": t0, "deadline_s": deadline_s,
                       "requests": requests}, f)
        child = subprocess.Popen(
            [sys.executable, os.path.join(ctx.root, "chipbench",
                                          "loadgen.py"), path],
            stdout=subprocess.PIPE, stderr=sys.stderr)
        try:
            if mid is not None:
                mid()
            raw, _ = child.communicate(timeout=deadline_s + 60)
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
    finally:
        os.unlink(path)
    if child.returncode != 0:
        raise RuntimeError(f"load generator exited {child.returncode}")
    return json.loads(raw)["requests"]


def client_metrics(requests: list, by_id: dict, seconds: float) -> dict:
    """What the client saw, by the cell's definitions.  Time to first
    token: every request DUE in the window, from its due time (the
    lead-in met an emptier system).  Gaps and tokens: all that ARRIVED
    inside the window, from any request; the drain, when the system
    empties, is not the steady state.  A request fails unless its stream
    ended with the done chunk and ``max_tokens`` tokens."""
    from chipbench import stats
    out = {"ttft": [], "gaps": [], "late": [], "tokens_in_window": 0,
           "failures": []}
    for r in requests:
        rec = by_id.get(r["id"])
        if not (rec is not None and rec["ended"] == "done"
                and len(rec["tokens"]) == r["max_tokens"]):
            out["failures"].append((r["id"], rec and (
                rec["ended"], rec["detail"], len(rec["tokens"]))))
        if rec is None or rec["sent_s"] is None:
            continue
        out["late"].append(rec["sent_s"] - rec["due_s"])
        if rec["token_s"]:
            if not r["lead"]:
                out["ttft"].append(rec["token_s"][0] - rec["due_s"])
            out["gaps"].extend(
                g for g, t in zip(stats.gaps(rec["token_s"]),
                                  rec["token_s"][1:]) if 0.0 <= t <= seconds)
            out["tokens_in_window"] += sum(
                1 for t in rec["token_s"] if 0.0 <= t <= seconds)
    return out


def pick_checked(done: list, seed: int, n: int) -> list:
    """The requests the reference follows: the longest finished one
    (prompt + served tokens) and ``n - 1`` others drawn from the seed."""
    import numpy as np
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: (
        len(done[i]["prompt"]) + done[i]["max_tokens"], -i))
    rng = np.random.default_rng([int(seed), 11])
    rest = [int(i) for i in rng.permutation(len(done)) if int(i) != longest]
    return [done[i] for i in [longest] + rest[:max(0, n - 1)]]


def verdict(failed: int, compiles_in_window: int, worst: float,
            tolerance: float, checked: int, measured: bool):
    """``correct`` and the numbers it was decided from, each beside its
    limit (``run.py`` prints them last)."""
    checks = {
        "worst_margin": {"value": worst, "limit": tolerance},
        "failed_requests": {"value": failed, "limit": 0},
        "compiles_in_window": {"value": compiles_in_window, "limit": 0},
        "checked_requests": {"value": checked, "at_least": 1},
    }
    correct = (failed == 0 and checked > 0 and worst <= tolerance
               and compiles_in_window == 0 and measured)
    return correct, checks


def warm_up(send, cfg, seed: int) -> None:
    """Four requests, one after another (set-up), that visit every
    program the window can use.  ``send(prompt, max_tokens)`` returns
    the emitted tokens."""
    import numpy as np
    rng = np.random.default_rng([int(seed), 9])

    def ids(n):
        return rng.integers(0, cfg.vocab_size, n).tolist()
    # cold, longer than half the cache width, idle engine: the one-pass
    # full-width prefill and its scatter through the block table
    send(ids(min(cfg.max_seq - 8, cfg.max_seq // 2 + 90)), 4)
    # two chunks, the second partial; then decode steps
    send(ids(40), 4)
    # A, then B = A's prompt + A's first tokens + a new tail: B adopts
    # A's chain, whose last block is partial -> copy-on-write
    a = ids(50)
    out = send(a, 6)
    send(a + out[:2] + ids(10), 4)


def run(ctx) -> dict:
    import jax

    from ray_tpu import serve
    from ray_tpu._compile_cache import compile_cache_stats
    from ray_tpu.inference import EngineConfig, build_gpt_deployment

    from chipbench import stats, trace_reduce
    from chipbench.model import (device_memory_peak, fold_seed, gpt_config,
                                 make_params)
    from chipbench.reference import gpt2 as ref
    from chipbench.traffic_gen import chat_requests

    mix, config = ctx.mix, ctx.config
    cfg = gpt_config(config)
    engine_cfg = EngineConfig(**config["engine"])
    stamps = stats.Stamps(ctx.t_start)
    stamps.mark("import_s")
    params = make_params(cfg, fold_seed(ctx.seed, 0),
                         config.get("weights_served_as"))
    jax.block_until_ready(params)
    stamps.mark("weights_s")
    ctx.log("weights on the device")
    handle = serve.run(
        build_gpt_deployment(name=ROUTE, cfg=cfg, engine_cfg=engine_cfg,
                             params=params, **config["deployment"]),
        use_actors=False, http=True)
    addr = serve.proxy_address()
    host, port = addr[len("http://"):].split(":")
    port = int(port)
    stamps.mark("programs_s")
    stamps.cache("after_programs", compile_cache_stats())
    ctx.log(f"deployment up at {addr}")
    trace = {}
    try:
        # ---- warm-up (set-up): one request at a time
        def send(prompt, max_tokens):
            got = run_loadgen(ctx, host, port, [
                {"id": 0, "due_s": 0.0, "prompt": prompt,
                 "max_tokens": max_tokens}], time.monotonic(), 300.0)[0]
            if got["ended"] != "done":
                raise RuntimeError(f"warm-up request failed: {got}")
            return got["tokens"]
        warm_up(send, cfg, ctx.seed)
        stamps.mark("warmup_s")
        ctx.log("warm-up done")

        # ---- the window
        requests = chat_requests(mix, ctx.seconds, ctx.seed, cfg.vocab_size)
        sent = [{k: r[k] for k in ("id", "due_s", "prompt", "max_tokens")}
                for r in requests]
        lead_s = max([0.0] + [-r["due_s"] for r in requests])
        # the child needs a moment to start; the lead-in is set-up
        t0 = time.monotonic() + 1.0 + lead_s
        setup_s = t0 - ctx.t_start
        stamps.mark("lead_in_s", at=t0)

        def mid():
            """Runs here while the child offers the load."""
            time.sleep(max(0.0, t0 - time.monotonic()))
            trace["at_window_start"] = engine_counters(handle)
            trace["compiles_at_start"] = compile_cache_stats()
            if ctx.trace:
                start = t0 + 0.45 * ctx.seconds
                time.sleep(max(0.0, start - time.monotonic()))
                trace["dir"] = tempfile.mkdtemp(prefix="chipbench_trace_")
                jax.profiler.start_trace(trace["dir"])
                time.sleep(min(mix["trace_s"], 0.4 * ctx.seconds))
                jax.profiler.stop_trace()
            time.sleep(max(0.0, t0 + ctx.seconds - time.monotonic()))
            trace["at_window_end"] = engine_counters(handle)
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
    # now that the engine's pool is freed
    done = [r for r in requests if by_id.get(r["id"], {}).get("ended")
            == "done"]
    t_ref = time.monotonic()
    picks = pick_checked(done, ctx.seed, mix["checked_requests"])
    worst, checked_tokens = 0.0, 0
    for r in picks:
        emitted = by_id[r["id"]]["tokens"]
        m = ref.margins(params, r["prompt"], emitted, config["n_head"],
                        cfg.max_seq)
        worst = max(worst, float(m.max()))
        checked_tokens += len(emitted)
    stamps.notes["reference_check_s"] = time.monotonic() - t_ref
    ctx.log(f"reference: worst margin {worst:.5f} over {len(picks)} "
            f"requests, {checked_tokens} tokens "
            f"(tolerance {mix['tie_tolerance']})")
    correct, checks = verdict(failed, compiles_in_window, worst,
                              mix["tie_tolerance"], len(picks),
                              bool(end_to_end))

    counters = {k: after[k] - before[k] for k in COUNTERS}
    counters["occupancy_sum"] = after["occupancy_sum"] \
        - before["occupancy_sum"]
    obs = {"window_s": ctx.seconds, "counters": counters}
    if ctx.trace and "dir" in trace:
        import shutil
        rows = trace_reduce.load_events(
            trace_reduce.find_xplane(trace["dir"]))
        shutil.rmtree(trace["dir"], ignore_errors=True)
        # the span is that of the device events themselves: the ends of
        # a serving trace are not synchronized with the host's stamps
        obs["trace"] = trace_reduce.summarize(rows)
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
        "counters": counters, "compiles_in_window": compiles_in_window,
        "worst_margin": worst, "tie_tolerance": mix["tie_tolerance"],
        "checked_requests": len(picks), "checked_tokens": checked_tokens,
        "setup_stamps": stamps.notes,
    }
    return {"correct": correct, "attempted": len(requests),
            "failed": failed, "setup_s": setup_s,
            "end_to_end": end_to_end, "obs": obs, "notes": notes,
            "checks": checks, "memory_peak_bytes": memory_peak}
