"""Finding a serving cell's knee: the cell's own run at a list of rates.

    python3 chipbench/sweep.py --workload <cell> --rates 1,2,3 --seed <n>
        [--seeds-a-rate 2] [--seconds <s>] [--set '{"lead_s": 15}']
        [--out <file.jsonl>]
    python3 chipbench/sweep.py --read <file.jsonl> [...]   (no chip: the
        tables and the knee again from recorded sweeps)

A tool for a ``benchmark`` PR, run by hand on the chip; the driver never
runs it and ``run.py`` never imports it.  Each rate is one ``run.py``
process (this parent never touches JAX, so the child gets the chip) with
``--mix-override {"rate_per_s": r}``: the same mix, engine, warm-up,
lead-in, window and check as the cell, at another offered rate.  Every
result line is appended to ``--out`` and one table row is printed a
rate; the last lines apply the knee's rule (``PERF.md`` section 2): the
highest rate with at least 97 % of the offered tokens received, no
request waiting at the window's end, in-flight growth of at most 2 over
the window, and neither tail more than 1.25 x its reading at the NEXT
LOWER swept rate.  With ``--seeds-a-rate`` n every rate is run on n
seeds, one whole pass over the rates a seed (``--seed`` + 100 j + i),
and the rule reads a rate's MEAN over its runs (a request waiting only
where every run left one): one run a rate cannot read the growth and
neighbour clauses (``PERF.md`` section 7 (AD)).  The ``gap_share_%``
column is ``chunk_pass_gap_share.serve`` as the run's notes hold it
(``chipbench/edge.py``), ``edge_share_%`` is ``p95_edge_gap_share.serve``
where the cell reads it (the boundary between ANY two kinds of pass
nearest the rank): with either between 2.5 and 8 % the judged p95's rank
lies on an edge, and the row says so.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from chipbench import edge              # noqa: E402  (no JAX)

COLUMNS = ("rate", "due", "failed", "received_%", "waiting_end", "growth",
           "ttft_p50", "ttft_p90", "itl_p50", "itl_p95", "occupancy_%",
           "preempt", "gap_share_%", "edge_share_%", "correct")
MEANT = ("received_%", "growth", "ttft_p90", "itl_p95", "gap_share_%",
         "edge_share_%")


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
        "preempt": c["preemptions"],
        "gap_share_%": notes.get("untraced_per_layer", {}).get(edge.NOTE),
        "edge_share_%": notes.get("untraced_per_layer", {}).get(
            edge.NEAREST),
        "correct": line["correct"],
    }


def by_rate(rows: list) -> list:
    """One row a rate from its runs' rows: the mean of what the rule
    reads, the least ``waiting_end`` (a request waits at the rate only
    where every run left one), the failed summed."""
    out = []
    for rate in sorted({r["rate"] for r in rows}):
        runs = [r for r in rows if r["rate"] == rate]
        row = {"rate": rate, "runs": len(runs),
               "waiting_end": min(r["waiting_end"] for r in runs),
               "failed": sum(r["failed"] for r in runs)}
        for key in MEANT:
            got = [r[key] for r in runs if r.get(key) is not None]
            row[key] = sum(got) / len(got) if got else None
        out.append(row)
    return out


def knee(rows: list) -> dict:
    """The rule, rate by rate (``by_rate``'s rows, or one run a rate);
    a rate's neighbour is the next lower swept one, the lowest has
    none."""
    rows = sorted(rows, key=lambda r: r["rate"])
    best, why = None, {}
    for below, r in zip([rows[0]] + rows, rows):
        broken = [name for name, bad in (
            ("received < 97 %", r["received_%"] < 97.0),
            ("queue at the end", r["waiting_end"] > 0),
            ("in-flight growth > 2", r["growth"] > 2),
            ("ttft_p90 > 1.25 x next lower", r["ttft_p90"]
             > 1.25 * below["ttft_p90"]),
            ("itl_p95 > 1.25 x next lower", r["itl_p95"]
             > 1.25 * below["itl_p95"]),
            ("failed requests", r["failed"] > 0)) if bad]
        why[r["rate"]] = broken
        if not broken:
            best = r["rate"]
    return {"knee": best, "broken_by_rate": why,
            "on_an_edge": [r["rate"] for r in rows if marked(r)]}


def marked(row: dict) -> str:
    return edge.mark(row.get("gap_share_%")) \
        or edge.mark(row.get("edge_share_%"))


def show(row: dict, columns=COLUMNS) -> str:
    mark = marked(row)
    return " ".join(
        f"{row[c]:>11.3f}" if isinstance(row.get(c), float)
        else f"{row.get(c)!s:>11}" for c in columns) \
        + ("  <- " + mark if mark else "")


def conclude(rows: list) -> None:
    """The rate-by-rate table where a rate has several runs, and the
    knee."""
    rates = by_rate(rows)
    if len(rates) < len(rows):
        columns = ("rate", "runs", "failed", "waiting_end") + MEANT
        print("by rate (means; the least waiting_end):", flush=True)
        print(" ".join(f"{c:>11}" for c in columns), flush=True)
        for r in rates:
            print(show(r, columns), flush=True)
    print(json.dumps(knee(rates)), flush=True)


def read_back(paths: list) -> None:
    """Recorded sweeps (``--out`` files) printed as they were, seed by
    seed, and concluded together."""
    rows = []
    for path in paths:
        with open(path) as f:
            recs = [json.loads(x) for x in f if x.strip()]
        rows += [{**row_of(r["rate"], r["line"]), "seed": r["seed"]}
                 for r in recs if r["line"]["metrics"]]
    for r in sorted(rows, key=lambda r: (r["seed"], r["rate"])):
        print(show(r) + f"  seed {r['seed']}", flush=True)
    conclude(rows)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--read", nargs="+", default=None,
                    help="recorded sweeps (--out files): print their "
                    "tables and the knee, run nothing")
    ap.add_argument("--workload")
    ap.add_argument("--rates", help="comma-separated requests/s")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seeds-a-rate", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None,
                    help="default: run_seconds of BENCHMARK.json")
    ap.add_argument("--set", type=json.loads, default={},
                    help="further mix keys laid over every run")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "sweep.jsonl"))
    args = ap.parse_args()
    print(" ".join(f"{c:>11}" for c in COLUMNS), flush=True)
    if args.read:
        read_back(args.read)
        return 0
    if not (args.workload and args.rates and args.seed is not None):
        ap.error("--workload, --rates and --seed, or --read")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    rows = []
    rates = [float(r) for r in args.rates.split(",")]
    for seed, rate in ((args.seed + 100 * j + i, rate)
                       for j in range(args.seeds_a_rate)
                       for i, rate in enumerate(rates)):
        override = {**args.set, "rate_per_s": rate}
        p = subprocess.run(
            [sys.executable, os.path.join(HERE, "run.py"), "--workload",
             args.workload, "--seed", str(seed), "--seconds",
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
            f.write(json.dumps({"rate": rate, "seed": seed,
                                "line": line}) + "\n")
        if not line["metrics"]:
            print(f"rate {rate}: no metric (nothing received)", flush=True)
            continue
        rows.append(row_of(rate, line))
        print(show(rows[-1]) + f"  seed {seed}", flush=True)
    if rows:
        conclude(rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())
