"""Cells and metrics of ``BENCHMARK.json`` found BY NAME, for the tests
of one cell or one family of metrics.

A later PR may only ADD entries and list more cells under a metric, so a
test that pins the LAST entries of ``per_layer``, a cell's exact set of
metrics, a metric's exact ``workloads`` or the number of cells fails on
the next PR that adds one, with nothing wrong (PR 59 found ten such).
What a test may hold: that a named cell exists with its files, that it
is listed under the metrics it needs (and may be under more), that a
named metric lists the cells it needs (and may list more) with the
fields it was given, and that each has its reader.
"""

from __future__ import annotations

import json
import os

from chipbench.run import metrics_of_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def metric(b: dict, name: str) -> dict:
    found = [m for m in b["per_layer"] + b["end_to_end"]
             if m["name"] == name]
    assert len(found) == 1, (name, len(found))
    return found[0]


def metrics_of(b: dict, cell_name: str, section: str = "per_layer") -> dict:
    """{metric name: entry} of the section's metrics that the cell
    reports (an entry without ``workloads`` is every cell's)."""
    return {m["name"]: m for m in metrics_of_cell(b, section, cell_name)}


def has_reader(name: str) -> bool:
    return os.path.exists(os.path.join(
        ROOT, "chipbench", "layer_metrics", name + ".py"))


def check_listed(b: dict, cell_name: str, names, **fields) -> None:
    """Every one of ``names`` is a per-layer metric with a reader that
    lists the cell (beside whichever others) and has ``fields``;
    ``names`` may be a dict {name: the end-to-end metric it moves}."""
    listed = metrics_of(b, cell_name)
    for name in names:
        assert name in listed, (name, "does not list", cell_name)
        assert has_reader(name), name
        want = dict(fields)
        if isinstance(names, dict):
            want["moves"] = names[name]
        got = {k: listed[name][k] for k in want}
        assert got == want, (name, got, want)
