"""The tools that show where a judged percentile's rank lies: the share
of a run's gaps behind a pass with prompt work in an untraced run's
notes, ``spreads.py``'s line a run and ``sweep.py``'s column a rate with
the mark between 2.5 and 8 %, and the knee's rule over several seeds a
rate.  ``python -m pytest chipbench/tests -q``; not part of tier-1; no
number here is a device number.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import edge, spreads, sweep          # noqa: E402

CELL = "serve-granite-h-chat2k-r50"


def line(itl, share=None, traced=False, **notes):
    metrics = {"itl_p95_ms": {"value": itl, "unit": "ms"}}
    if traced:
        metrics = {edge.NOTE: {"value": share, "unit": "%"}}
    elif share is not None:
        notes["untraced_per_layer"] = {edge.NOTE: share}
    return {"correct": True, "failed": 0, "metrics": metrics,
            "notes": notes}


def test_spreads_marks_a_share_on_the_edge_and_not_one_past_it():
    records = [
        {"cell": CELL, "set": "A", "seed": 1, "line": line(16.1, 5.8)},
        {"cell": CELL, "set": "A", "seed": 2, "line": line(22.4, 12.0)},
        {"cell": CELL, "set": "A", "seed": 3, "line": line(9.3, 1.9)},
        {"cell": CELL, "set": "T", "seed": 4,
         "line": line(None, 7.99, traced=True)},
        # a parent's line: no share, no row
        {"cell": CELL, "set": "A", "seed": 5, "line": line(21.0)},
        # 46 % behind a pass with prompt work, 4.4 % behind the passes
        # that ran two chunks: the rank lies between one chunk and two
        {"cell": CELL, "set": "A", "seed": 6, "line": line(
            31.9, untraced_per_layer={edge.NOTE: 46.0,
                                      edge.NEAREST: 4.4})}]
    rows = spreads.gap_shares(records)
    assert [(r["seed"], bool(r["mark"])) for r in rows] == [
        (1, True), (2, False), (3, False), (4, True), (6, True)]
    assert rows[0]["mark"] == edge.MARK == "the judged rank lies on an edge"
    assert rows[0][edge.NOTE] == 5.8
    assert not edge.on_an_edge(2.5) and not edge.on_an_edge(8.0)
    assert not edge.on_an_edge(None)


def row(rate, received=100.0, waiting=0, growth=0, ttft=100.0, itl=20.0,
        share=12.0, failed=0):
    return {"rate": rate, "failed": failed, "received_%": received,
            "waiting_end": waiting, "growth": growth, "ttft_p90": ttft,
            "itl_p95": itl, "gap_share_%": share}


def test_the_knee_compares_neighbours_and_reads_a_rate_by_its_mean():
    # a tail may climb 1.2 x a step without end: the NEXT LOWER rate is
    # the neighbour, not the lowest (PERF.md section 2, restated by PR 37)
    rows = [row(2, ttft=100), row(3, ttft=120), row(4, ttft=144),
            row(5, ttft=200)]
    got = sweep.knee(rows)
    assert got["knee"] == 4
    assert got["broken_by_rate"][5] == ["ttft_p90 > 1.25 x next lower"]
    # two seeds a rate: one run's growth of 4 beside another's 0 holds
    # the clause, a request left waiting in ONE run is the instant's
    two = [row(2), row(2), row(3, growth=4, waiting=1), row(3, growth=0),
           row(4, growth=4, waiting=1), row(4, growth=3, waiting=2)]
    rates = sweep.by_rate(two)
    assert [r["runs"] for r in rates] == [2, 2, 2]
    assert rates[1]["growth"] == 2.0 and rates[1]["waiting_end"] == 0
    got = sweep.knee(rates)
    assert got["knee"] == 3
    assert got["broken_by_rate"][4] == ["queue at the end",
                                        "in-flight growth > 2"]
    # a rate whose judged rank lies on an edge is named
    edgy = [row(2, share=5.8), row(3, share=9.0)]
    assert sweep.knee(edgy)["on_an_edge"] == [2]
    assert sweep.show(edgy[0]).endswith(edge.MARK)
    assert not sweep.show(edgy[1]).endswith(edge.MARK)


def test_a_recorded_sweep_is_read_again_without_a_chip(tmp_path):
    def rec(rate, seed, itl, share):
        return {"rate": rate, "seed": seed, "line": {
            "correct": True, "failed": 0,
            "metrics": {"serve_tokens_per_s": {"value": 99.0},
                        "ttft_p90_ms": {"value": 110.0},
                        "itl_p95_ms": {"value": itl}},
            "notes": {"ttft_samples": 100, "offered_tokens_per_s": 100.0,
                      "waiting_at_window_end": 0,
                      "in_flight_at_window_end": 3,
                      "in_flight_at_window_start": 3, "ttft_p50_ms": 50.0,
                      "itl_p50_ms": 9.0,
                      "counters": {"occupancy_sum": 5.0,
                                   "decode_iterations": 100,
                                   "preemptions": 0},
                      "untraced_per_layer": {edge.NOTE: share}}}}
    path = tmp_path / "sweep.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in (
        rec(2.4, 1, 16.0, 5.8), rec(4.0, 2, 22.0, 9.5),
        rec(2.4, 101, 21.0, 5.9), rec(4.0, 102, 22.1, 9.7))))
    p = subprocess.run([sys.executable, os.path.join(
        ROOT, "chipbench", "sweep.py"), "--read", str(path)],
        capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, p.stderr
    out = p.stdout.splitlines()
    assert sum(edge.MARK in x for x in out) == 3    # two runs, one rate
    got = json.loads(out[-1])
    assert got["knee"] == 4.0 and got["on_an_edge"] == [2.4]


def test_an_untraced_run_notes_the_share_and_a_traced_one_reports_it(
        tmp_path):
    """The rest of a run after the look for a chip (``--rehearse``): the
    reader that says ``UNTRACED`` is read into the untraced line's notes,
    where ``spreads.py`` and ``sweep.py`` find it."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    for name in ("RAY_TPU_TRACING", "RAY_TPU_TRACE_DIR", "XLA_FLAGS"):
        env.pop(name, None)
    seen = {}
    # (ten traced seconds: the session covers 4.5 - 8.5 s of them, the
    # account's spans come a second apart, and the reader takes the
    # intervals no session touched)
    for trace, seconds in (("0", "6"), ("1", "10")):
        p = subprocess.run(
            [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
             "--workload", CELL, "--seed", "3000000059", "--seconds",
             seconds, "--trace", trace, "--rehearse"], cwd=ROOT, env=env,
            capture_output=True, text=True, timeout=900)
        assert p.returncode == 0, p.stderr[-2000:]
        seen[trace] = json.loads(p.stdout.strip().splitlines()[-1])
    noted = seen["0"]["notes"]["untraced_per_layer"]
    assert set(noted) == {edge.NOTE, edge.NEAREST}
    assert 0 < noted[edge.NEAREST] <= noted[edge.NOTE] <= 100
    rows = spreads.gap_shares([{"cell": CELL, "set": "A", "seed": 59,
                                "line": seen["0"]}])
    assert rows[0][edge.NOTE] == noted[edge.NOTE]
    traced = seen["1"]["rehearsal_metrics_not_device_numbers"]
    assert seen["1"]["notes"]["untraced_per_layer"] == {}
    assert 0 < traced[edge.NOTE]["value"] <= 100
    # the fused program ran in the traced seconds or it did not: never 0
    fused = traced.get("step_chunk_program_ms.serve")
    assert fused is None or fused["value"] > 0
