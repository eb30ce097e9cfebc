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
"""

from __future__ import annotations

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import stats            # noqa: E402


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


def main() -> int:
    records = []
    for path in sys.argv[1:]:
        with open(path) as f:
            records += [json.loads(x) for x in f if x.strip()]
    records = [r for r in records if r["line"]["metrics"]]
    for row in table(records):
        print(json.dumps(row))
    bad = [(r["cell"], r["seed"]) for r in records
           if not r["line"]["correct"] or r["line"]["failed"]]
    print(json.dumps({"runs": len(records), "not_correct_or_failed": bad}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
