"""Repeat runs of cells, for the spreads that the bounds are set from.

    python3 chipbench/repeat.py --cells a,b --seeds 1,2,3 --set A
        [--trace 0|1] [--seconds <s>] [--out <file.jsonl>]

A tool for a ``benchmark`` PR, run by hand on the chip; the driver never
runs it.  One ``run.py`` process a run (this parent never touches JAX),
the cells by turns seed by seed, each result line appended to ``--out``
with its cell, set label and seed, and one short line printed a run.
``chipbench/spreads.py`` reduces the file.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--set", required=True, help="label of this set of runs")
    ap.add_argument("--trace", default="0")
    ap.add_argument("--seconds", default=None)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "runs.jsonl"))
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = args.seconds or str(json.load(f)["run_seconds"])
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    for seed in args.seeds.split(","):
        for cell in args.cells.split(","):
            t = time.monotonic()
            p = subprocess.run(
                [sys.executable, os.path.join(HERE, "run.py"), "--workload",
                 cell, "--seed", seed, "--seconds", seconds, "--trace",
                 args.trace], cwd=ROOT, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True)
            took = time.monotonic() - t
            if p.returncode != 0 or not p.stdout.strip():
                print(f"{cell} seed {seed}: exit {p.returncode}\n"
                      f"{p.stderr[-3000:]}", flush=True)
                continue
            line = json.loads(p.stdout.strip().splitlines()[-1])
            with open(args.out, "a") as f:
                f.write(json.dumps({"cell": cell, "set": args.set,
                                    "seed": int(seed), "trace":
                                    int(args.trace), "process_s": took,
                                    "line": line}) + "\n")
            print(json.dumps({
                "cell": cell, "seed": int(seed), "correct": line["correct"],
                "failed": line["failed"], "process_s": round(took, 1),
                "metrics": {k: v["value"] for k, v in
                            line["metrics"].items()},
                "untraced": line["notes"].get("untraced_per_layer", {}),
                "compiles_in_window": line["notes"].get(
                    "compiles_in_window"),
                "peak_gb": line["device"]["memory_peak_bytes"] / 1e9,
                "checks": {k: v["value"] for k, v in
                           line["checks"].items()},
                "stamps": {k: round(v, 2) for k, v in line["notes"].get(
                    "setup_stamps", {}).items()}}), flush=True)
            if not line["correct"]:
                print(p.stderr[-1500:], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
