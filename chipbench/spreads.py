"""The bounds table of ``PERF.md`` section 2 from recorded result lines.

    python3 chipbench/spreads.py <runs.jsonl> [...]      (no JAX, no chip)

A tool for a ``benchmark`` PR.  Each input line is ``{"cell": ..,
"set": .., "seed": .., "line": <a run.py result line>}`` as
``chipbench/repeat.py`` writes them.  For every cell and end-to-end
metric: the number of runs, the median, each set's spread (the distance
between the quartiles over the median, ``stats.spread``), the mean of
the sets' spreads with each set's farthest run left out (what the driver
holds a bound's tightness to), the widest spread, five times the widest,
and the notes' set-up stamps by their own medians and spreads.
Then one line a run that read ``chunk_pass_gap_share.serve`` (an
untraced run's notes, a traced run's metrics): the share of its gaps
behind a pass that held prompt work (and, where the cell reads it,
``p95_edge_gap_share.serve``: the same for the boundary between ANY two
kinds of pass that lies nearest the rank), marked where either lies
between 2.5 and 8 % — there the judged p95's rank lies on an edge
between two levels of gaps and the run's ``itl_p95_ms`` says nothing of
the tree (``chipbench/edge.py``; ``PERF.md`` section 2).
"""

from __future__ import annotations

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import edge, stats      # noqa: E402


def table(records: list) -> list:
    by = {}
    for r in records:
        for name, m in r["line"]["metrics"].items():
            by.setdefault((r["cell"], name), {}).setdefault(
                r["set"], []).append(m["value"])
        for name, v in r["line"]["notes"].get("setup_stamps", {}).items():
            if name.endswith("_s"):
                by.setdefault((r["cell"], "stamp." + name), {}).setdefault(
                    r["set"], []).append(v)
    rows = []
    for (cell, name), sets in sorted(by.items()):
        every = [v for vs in sets.values() for v in vs]
        per_set = {k: stats.spread(vs) for k, vs in sets.items()
                   if len(vs) >= 3}
        tight = [stats.spread(vs, drop_farthest=True)
                 for vs in sets.values() if len(vs) >= 4]
        widest = max(list(per_set.values()) + [stats.spread(every)])
        rows.append({
            "cell": cell, "metric": name, "runs": len(every),
            "median": statistics.median(every),
            "min": min(every), "max": max(every),
            "set_medians": {k: statistics.median(vs)
                            for k, vs in sets.items()},
            "set_spreads": per_set,
            "tightness_spread": statistics.mean(tight) if tight else None,
            "widest_spread": widest, "five_times_widest": 5 * widest})
    return rows


def gap_shares(records: list) -> list:
    """One row a run that read the share, in the records' order; beside
    it the boundary between ANY two kinds of pass nearest the p95's
    rank, where the cell reads that too."""
    rows = []
    for r in records:
        line = r["line"]
        noted = line["notes"].get("untraced_per_layer", {})
        share, nearest = (noted.get(name, line["metrics"].get(
            name, {}).get("value")) for name in (edge.NOTE, edge.NEAREST))
        if share is not None:
            rows.append({"cell": r["cell"], "set": r["set"],
                         "seed": r["seed"], edge.NOTE: share,
                         edge.NEAREST: nearest,
                         "mark": edge.mark(share) or edge.mark(nearest)})
    return rows


def main() -> int:
    records = []
    for path in sys.argv[1:]:
        with open(path) as f:
            records += [json.loads(x) for x in f if x.strip()]
    records = [r for r in records if r["line"]["metrics"]]
    for row in table(records):
        print(json.dumps(row))
    shares = gap_shares(records)
    for row in shares:
        print(json.dumps(row))
    bad = [(r["cell"], r["seed"]) for r in records
           if not r["line"]["correct"] or r["line"]["failed"]]
    print(json.dumps({"runs": len(records), "not_correct_or_failed": bad,
                      "on_an_edge": [(r["cell"], r["seed"]) for r in shares
                                     if r["mark"]]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
