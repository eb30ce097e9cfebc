"""What the always-on counting costs where it is counted (PR 54): the
host time of one phase site of the engine loop's account (PR 40 read
0.87 us), of one ``Account.pass_done`` alone and with the counter
arithmetic the engine does around it (``engine._pass_ended``, the
``engine.account`` span left out: that is one a second), of one
``Histogram.add``, and of
the serve front's stamp a streamed chunk (``AsyncHttpProxy.
_chunk_written``, the ``front.account`` span left out too), each as
the best of ``--repeats`` timings of ``--calls`` calls in a row.  With
``--profiled`` the same inside a ``jax.profiler`` session, where a
phase site also opens its span and annotation.

    python benchmarks/account_sites.py [--profiled] [--out FILE]

Host code only: it runs wherever Python does, and a reading belongs to
the machine it was taken on (``chiprun -- python benchmarks/
account_sites.py`` for the chip's host).  Readings: PERF.md section 6,
PR 54."""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_tpu.inference import engine as engine_mod     # noqa: E402
from ray_tpu.serve import asgi, engine_stats           # noqa: E402
from ray_tpu.util import tracing                       # noqa: E402


# a chain that ended in the far future: no link falls due in a timed call
NEVER_DUE_NS = time.monotonic_ns() + 10 ** 15


def best_us(fn, calls: int, repeats: int) -> float:
    """us a call: the best of ``repeats`` loops of ``calls`` calls, the
    empty loop's own time taken off."""
    def loop(f):
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter_ns()
            for _ in range(calls):
                f()
            best = min(best, time.perf_counter_ns() - t0)
        return best / calls / 1e3
    return loop(fn) - loop(lambda: None)


def sites():
    """name -> a call of the site, on objects made as the program makes
    them."""
    acct = tracing.Account(engine_mod._LOOP_PHASES, launch=("dispatch",),
                           land=("wait",), waits=("wait", "parked"))
    emit = acct.phase("emit")

    def phase_site():
        with emit:
            pass

    # ``engine._pass_ended`` itself, on an engine that is only what the
    # method reads; the chain's next link is never due, so no span
    eng = engine_mod.InferenceEngine.__new__(engine_mod.InferenceEngine)
    eng._counts, eng._acct = engine_stats.Counters(), acct
    eng._emitted, eng._account_t1_ns = 0, NEVER_DUE_NS
    counts = eng._counts

    def pass_ended():
        counts.tokens_greedy_on_device += 20
        eng._launched, eng._first_tokens = engine_mod._STEP_CHUNK, 1
        eng._pass_ended()
        with emit:          # a stamp: the next pass is not 0 ns long
            pass

    alone = tracing.Account(engine_mod._LOOP_PHASES, launch=("dispatch",),
                            land=("wait",), waits=("wait", "parked"))

    def pass_done():
        alone.pass_done("step_chunk", 19, tokens=20)

    hist = tracing.Histogram()
    ns = [12_345_678]

    def histogram_add():
        hist.add(ns[0], 20)

    proxy = asgi.AsyncHttpProxy.__new__(asgi.AsyncHttpProxy)
    proxy._write_gaps = tracing.Histogram()
    proxy._profiled = False
    proxy.host, proxy.port = "127.0.0.1", 0
    proxy._account_t1_ns = NEVER_DUE_NS
    last = [0]

    def chunk_written():
        last[0] = proxy._chunk_written(last[0])

    return {"phase_site": phase_site, "pass_done": pass_done,
            "pass_ended_less_a_phase_site": pass_ended,
            "histogram_add": histogram_add, "chunk_written": chunk_written}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calls", type=int, default=200_000)
    ap.add_argument("--repeats", type=int, default=7)
    ap.add_argument("--profiled", action="store_true",
                    help="time the sites inside a jax.profiler session")
    ap.add_argument("--out", help="append the readings to this .jsonl")
    args = ap.parse_args()
    calls = args.calls // 20 if args.profiled else args.calls
    trace_dir = None
    if args.profiled:
        import jax
        trace_dir = tempfile.mkdtemp(prefix="account_sites_")
        jax.profiler.start_trace(trace_dir)
    try:
        got = {name: best_us(fn, calls, args.repeats)
               for name, fn in sites().items()}
    finally:
        if trace_dir is not None:
            import shutil
            jax.profiler.stop_trace()
            shutil.rmtree(trace_dir, ignore_errors=True)
    got["pass_ended_less_a_phase_site"] -= got["phase_site"]
    line = {"profiled": args.profiled, "calls": calls,
            "repeats": args.repeats, "us_a_call": got}
    print(json.dumps(line))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
