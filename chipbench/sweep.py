"""Finding a serving cell's knee: the cell's own run at a list of rates.

    python3 chipbench/sweep.py --workload <cell> --rates 1,2,3 --seed <n>
        [--seconds <s>] [--set '{"lead_s": 15}'] [--out <file.jsonl>]

A tool for a ``benchmark`` PR, run by hand on the chip; the driver never
runs it and ``run.py`` never imports it.  Each rate is one ``run.py``
process (this parent never touches JAX, so the child gets the chip) with
``--mix-override {"rate_per_s": r}``: the same mix, engine, warm-up,
lead-in, window and check as the cell, at another offered rate.  Every
result line is appended to ``--out`` and one table row is printed a
rate; the last lines apply the knee's rule (``PERF.md`` section 2): the
highest rate with at least 97 % of the offered tokens received, no
request waiting at the window's end, in-flight growth of at most 2 over
the window, and both tails within 1.25 x of the lowest swept rate's.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

COLUMNS = ("rate", "due", "failed", "received_%", "waiting_end", "growth",
           "ttft_p50", "ttft_p90", "itl_p50", "itl_p95", "occupancy_%",
           "preempt", "correct")


def row_of(rate: float, line: dict) -> dict:
    notes, m = line["notes"], line["metrics"]
    c = notes["counters"]
    return {
        "rate": rate, "due": notes["ttft_samples"], "failed": line["failed"],
        "received_%": 100.0 * m["serve_tokens_per_s"]["value"]
        / notes["offered_tokens_per_s"],
        "waiting_end": notes["waiting_at_window_end"],
        "growth": notes["in_flight_at_window_end"]
        - notes["in_flight_at_window_start"],
        "ttft_p50": notes["ttft_p50_ms"],
        "ttft_p90": m["ttft_p90_ms"]["value"],
        "itl_p50": notes["itl_p50_ms"], "itl_p95": m["itl_p95_ms"]["value"],
        "occupancy_%": 100.0 * c["occupancy_sum"]
        / max(1, c["decode_iterations"]),
        "preempt": c["preemptions"], "correct": line["correct"],
    }


def knee(rows: list) -> dict:
    """The rule, row by row; rows sorted by rate."""
    rows = sorted(rows, key=lambda r: r["rate"])
    base = rows[0]
    best, why = None, {}
    for r in rows:
        broken = [name for name, bad in (
            ("received < 97 %", r["received_%"] < 97.0),
            ("queue at the end", r["waiting_end"] > 0),
            ("in-flight growth > 2", r["growth"] > 2),
            ("ttft_p90 > 1.25 x lowest", r["ttft_p90"]
             > 1.25 * base["ttft_p90"]),
            ("itl_p95 > 1.25 x lowest", r["itl_p95"]
             > 1.25 * base["itl_p95"]),
            ("failed requests", r["failed"] > 0)) if bad]
        why[r["rate"]] = broken
        if not broken:
            best = r["rate"]
    return {"knee": best, "broken_by_rate": why}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests/s")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="default: run_seconds of BENCHMARK.json")
    ap.add_argument("--set", type=json.loads, default={},
                    help="further mix keys laid over every run")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "sweep.jsonl"))
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    rows = []
    print(" ".join(f"{c:>11}" for c in COLUMNS), flush=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        override = {**args.set, "rate_per_s": rate}
        p = subprocess.run(
            [sys.executable, os.path.join(HERE, "run.py"), "--workload",
             args.workload, "--seed", str(args.seed + i), "--seconds",
             str(seconds), "--trace", "0", "--mix-override",
             json.dumps(override)],
            cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        if p.returncode != 0 or not p.stdout.strip():
            print(f"rate {rate}: run.py exited {p.returncode}\n"
                  f"{p.stderr[-3000:]}", flush=True)
            continue
        line = json.loads(p.stdout.strip().splitlines()[-1])
        with open(args.out, "a") as f:
            f.write(json.dumps({"rate": rate, "seed": args.seed + i,
                                "line": line}) + "\n")
        if not line["metrics"]:
            print(f"rate {rate}: no metric (nothing received)", flush=True)
            continue
        rows.append(row_of(rate, line))
        print(" ".join(
            f"{rows[-1][c]:>11.3f}" if isinstance(rows[-1][c], float)
            else f"{rows[-1][c]!s:>11}" for c in COLUMNS), flush=True)
    if rows:
        print(json.dumps(knee(rows)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
