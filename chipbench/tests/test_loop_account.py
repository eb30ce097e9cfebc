"""The five readers of the engine loop's own account (PR 40) on a
hand-made ring with known answers, and the three serve cells'
rehearsals printing them.  CPU only; like the rest of
``chipbench/tests`` not part of the repo's tier-1 suite."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import loop_account            # noqa: E402
from chipbench.readers import load_reader     # noqa: E402

MS = 1_000_000
METRICS = ("loop_host_ms_per_pass.serve", "device_starved_share.serve",
           "block_hunt_ms_per_pass.serve", "emit_ms_per_pass.serve",
           "loop_unaccounted_share.serve")
PHASES = ("parked", "admit", "grow", "prefill_host", "pack", "dispatch",
          "wait", "emit")
CELLS = ("serve-xl-chat-r80-v2", "serve-granite-h-chat2k-r50",
         "serve-nemotron3-nano-reason1k-r80")


class Loop:
    """An engine's loop thread as its account sees it: ``spend`` adds
    time to the cumulative counters, ``span`` writes them out."""

    def __init__(self, engine: str, t_ms: float):
        self.engine, self.t_ns, self.last_ns = engine, int(t_ms * MS), None
        self.passes = 0
        self.ns = dict.fromkeys(PHASES, 0)
        self.starved = dict.fromkeys(PHASES, 0)
        self.rest = self.rest_starved = 0
        self.spans = []

    def spend(self, passes: int, unaccounted_ms=0.0, **ms_by_phase):
        """``passes`` passes that spent so much in each phase; with a
        synchronous loop all of it but ``wait`` is starved."""
        self.passes += passes
        for phase, ms in ms_by_phase.items():
            self.ns[phase] += int(ms * MS)
            if phase != "wait":
                self.starved[phase] += int(ms * MS)
            self.t_ns += int(ms * MS)
        self.rest += int(unaccounted_ms * MS)
        self.rest_starved += int(unaccounted_ms * MS)
        self.t_ns += int(unaccounted_ms * MS)
        return self

    def span(self, profiling=False):
        t0 = self.last_ns if self.last_ns is not None else self.t_ns - MS
        self.spans.append({
            "name": "engine.account", "t0_ns": t0, "t1_ns": self.t_ns,
            "span_id": f"a{len(self.spans)}{self.engine}", "parent_id": None,
            "trace_id": f"t{len(self.spans)}{self.engine}",
            "attributes": {
                "engine": self.engine, "passes": self.passes,
                "ns": dict(self.ns), "starved_ns": dict(self.starved),
                "count": dict.fromkeys(PHASES, self.passes),
                "unaccounted_ns": self.rest,
                "unaccounted_starved_ns": self.rest_starved,
                "decode_iterations": self.passes, "chunk_passes": 0,
                "profiling": profiling, "ring_dropped": 0}})
        self.last_ns = self.t_ns
        return self


def serve_obs(spans):
    # T_START 100 s, set-up 20 s, window 10 s: [120 s, 130 s]
    return {"spans": spans, "t_start": 100.0, "window_s": 10.0,
            "end_to_end": {"setup_s": 20.0}}


def read(name, obs):
    return load_reader(name).read(obs)


def a_second(loop, passes=100, scale=1.0, **kw):
    """One second of loop time: ``passes`` passes of 10 ms, 6 of them the
    wait, 3.8 of host work by phase (x ``scale``), 0.2 nobody's."""
    host = dict(admit=0.5, grow=1.0, prefill_host=0.2, pack=0.1,
                dispatch=1.5, emit=0.5)
    spent = sum(host.values()) * scale + 0.2
    return loop.spend(
        passes, unaccounted_ms=0.2 * passes, wait=(10.0 - spent) * passes,
        **{p: ms * scale * passes for p, ms in host.items()}).span(**kw)


def test_readers_difference_the_unprofiled_intervals_of_the_window():
    loop = Loop("engine-0", 118_500.0)
    a_second(loop, scale=3.0)           # ends 119.5 s: the lead-in, out
    a_second(loop, scale=3.0)           # ends 120.5 s: the baseline only
    for _ in range(4):                  # 121.5 .. 124.5
        a_second(loop)
    for _ in range(4):                  # 125.5 .. 128.5: a session
        a_second(loop, scale=2.0, profiling=True)
    a_second(loop)                      # ends 129.5
    a_second(loop, scale=3.0)           # ends 130.5: the drain, out
    obs = serve_obs(loop.spans)
    pairs = loop_account.intervals(obs)
    assert [(a["t1_ns"] // MS, b["t1_ns"] // MS) for a, b in pairs] \
        == [(120_500 + 1000 * i, 121_500 + 1000 * i) for i in range(9)]
    acct = loop_account.read(obs)
    assert acct["passes"] == 500 and acct["wall_ns"] == 5_000 * MS
    # host 3.8 + the 0.2 nobody's a pass; the profiled seconds' 7.8 and
    # the 11.6 outside the window never show
    assert read("loop_host_ms_per_pass.serve", obs) == pytest.approx(4.0)
    assert read("block_hunt_ms_per_pass.serve", obs) == pytest.approx(1.5)
    assert read("emit_ms_per_pass.serve", obs) == pytest.approx(0.5)
    # everything but the wait is starved: 4 of 10 ms
    assert read("device_starved_share.serve", obs) == pytest.approx(40.0)
    assert read("loop_unaccounted_share.serve", obs) == pytest.approx(2.0)
    # the profiled intervals alone, by hand (what a builder differences
    # to read the profiler's stretch)
    traced = loop_account.total(
        [(a, b) for a, b in pairs if b["attributes"]["profiling"]])
    assert traced["passes"] == 400
    assert loop_account.ms_per_pass(traced, ("admit", "grow")) \
        == pytest.approx(3.0)


def test_parked_time_is_nobodys_fault_and_leaves_the_shares():
    loop = Loop("engine-0", 120_000.0).span()
    # half of every second parked: 50 passes of 10 ms, 500 ms waiting
    # for requests
    for _ in range(3):
        loop.spend(0, parked=500.0)
        a_second(loop, passes=50)
    obs = serve_obs(loop.spans)
    assert read("loop_host_ms_per_pass.serve", obs) == pytest.approx(4.0)
    # starved 4 ms x 50 of a second; unaccounted 0.2 x 50 of the half
    # second the loop worked
    assert read("device_starved_share.serve", obs) == pytest.approx(20.0)
    assert read("loop_unaccounted_share.serve", obs) == pytest.approx(2.0)
    # nothing but parked time: no pass to divide by
    idle = Loop("engine-0", 120_000.0).span()
    idle.spend(0, parked=1_000.0).span()
    obs = serve_obs(idle.spans)
    assert read("loop_host_ms_per_pass.serve", obs) is None
    assert read("device_starved_share.serve", obs) == pytest.approx(0.0)
    assert read("loop_unaccounted_share.serve", obs) is None


def test_two_engines_are_differenced_apart_and_a_lost_span_breaks_the_chain():
    one, two = Loop("engine-0", 120_000.0).span(), \
        Loop("engine-1", 120_300.0).span()
    for _ in range(3):
        a_second(one)
        a_second(two, scale=2.0)
    both = sorted(one.spans + two.spans, key=lambda s: s["t1_ns"])
    obs = serve_obs(both)
    assert len(loop_account.intervals(obs)) == 6
    # 300 passes of 4.0 and 300 of 7.8: never one engine's counters
    # minus the other's
    assert read("loop_host_ms_per_pass.serve", obs) == pytest.approx(5.9)
    assert read("device_starved_share.serve", obs) == pytest.approx(59.0)
    # the ring lost the second span of engine-0: the pair around the
    # hole does not chain, and nothing says whether a session touched it
    holed = serve_obs([s for s in both if s is not one.spans[2]])
    assert len(loop_account.intervals(holed)) == 4
    assert read("loop_host_ms_per_pass.serve", holed) \
        == pytest.approx((100 * 4.0 + 300 * 7.8) / 400)


@pytest.mark.parametrize("name", METRICS)
def test_no_account_no_window_no_metric(name):
    # a parent commit's program writes no such span
    assert read(name, serve_obs([])) is None
    assert read(name, serve_obs([{
        "name": "engine.pass", "t0_ns": 121_000 * MS, "t1_ns": 121_010 * MS,
        "span_id": "p", "parent_id": None, "trace_id": "t",
        "attributes": {}}])) is None
    loop = Loop("engine-0", 120_000.0).span()
    # ONE span in the window: nothing to difference
    assert read(name, serve_obs(loop.spans)) is None
    a_second(loop)
    assert read(name, serve_obs(loop.spans)) is not None
    # every interval profiled: the untraced host was not seen
    prof = Loop("engine-0", 120_000.0).span()
    a_second(prof, profiling=True)
    assert read(name, serve_obs(prof.spans)) is None
    # spans but no T_START to place the window
    assert read(name, {"spans": loop.spans, "window_s": 10.0,
                       "end_to_end": {"setup_s": 20.0}}) is None
    # the program's own ring, empty in this process
    assert read(name, {"window_s": 10.0, "t_start": 0.0,
                       "end_to_end": {"setup_s": 0.0}}) is None


def test_benchmark_json_gives_the_five_to_the_three_serve_cells():
    """By name, wherever in ``per_layer`` they stand and whichever cells
    they have come to list since."""
    from chipbench.tests import by_name
    bench = by_name.bench()
    engine_layer = by_name.metric(bench, "engine_host_ms_per_pass.serve")
    for cell in CELLS:
        by_name.check_listed(
            bench, cell, METRICS,
            **{k: engine_layer[k] for k in ("layer", "source", "moves")})
    for name in METRICS:
        assert by_name.metric(bench, name)["better"] == "lower"


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_new_metrics(cell, tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    # one CPU device, tracing off, whatever the caller's shell says
    for name in ("RAY_TPU_TRACING", "RAY_TPU_TRACE_DIR", "XLA_FLAGS"):
        env.pop(name, None)
    # ten seconds: the profiler session covers 4.5 - 8.5 s of them, and
    # the account's spans come a second apart
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", cell, "--seed", "3000000040", "--seconds", "10",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["metrics"] == {}
    assert line["rehearsal_verdict_not_a_result"] is True, line["checks"]
    seen = line["rehearsal_metrics_not_device_numbers"]
    for name in METRICS:
        assert seen[name]["value"] is not None, (name, seen)
        assert seen[name]["value"] >= 0
    assert seen["device_starved_share.serve"]["value"] <= 100.0
    assert seen["loop_unaccounted_share.serve"]["value"] <= 100.0
