"""``chunk_in_step_share.serve`` (PR 41) on a hand-made chain of
``engine.account`` spans with known answers: two engines, a broken
chain, a profiled interval, a program that counts no such chunks.  CPU
only; like the rest of ``chipbench/tests`` not part of the repo's tier-1
suite."""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import loop_account            # noqa: E402
from chipbench.readers import load_reader     # noqa: E402

NAME = "chunk_in_step_share.serve"
MS = 1_000_000


class Loop:
    """An engine's cumulative chunk counters, written out as chained
    account spans a second apart."""

    def __init__(self, engine: str, t_ms: float, counts: bool = True):
        self.engine, self.t_ns, self.counts = engine, int(t_ms * MS), counts
        self.passes = self.chunks = self.in_step = 0
        self.spans = []
        self.second(0, 0)

    def second(self, chunks: int, in_step: int, profiling=False):
        self.passes += 100
        self.chunks += chunks
        self.in_step += in_step
        self.t_ns += 1000 * MS
        at = {"engine": self.engine, "passes": self.passes,
              "decode_iterations": self.passes,
              "chunk_passes": self.chunks, "profiling": profiling,
              "ring_dropped": 0, "ns": {}, "starved_ns": {}, "count": {},
              "unaccounted_ns": 0, "unaccounted_starved_ns": 0}
        if self.counts:
            at["chunks_in_step"] = self.in_step
        self.spans.append({
            "name": "engine.account", "t0_ns": self.t_ns - 1000 * MS,
            "t1_ns": self.t_ns, "parent_id": None,
            "span_id": f"a{len(self.spans)}{self.engine}",
            "trace_id": f"t{len(self.spans)}{self.engine}",
            "attributes": at})
        return self


def serve_obs(spans):
    # T_START 100 s, set-up 20 s, window 10 s: [120 s, 130 s]
    return {"spans": spans, "t_start": 100.0, "window_s": 10.0,
            "end_to_end": {"setup_s": 20.0}}


def read(obs):
    return load_reader(NAME).read(obs)


def test_share_is_differenced_over_the_window_s_chained_spans():
    loop = Loop("engine-0", 117_500.0)  # ends 118.5 s
    loop.second(40, 0)                  # ends 119.5: the lead-in, out
    loop.second(40, 0)                  # ends 120.5: the baseline only
    for _ in range(4):                  # 121.5 .. 124.5
        loop.second(25, 20)
    for _ in range(4):                  # 125.5 .. 128.5: a session; a
        loop.second(15, 15, profiling=True)     # count is a count
    loop.second(20, 0)                  # ends 129.5
    loop.second(40, 0)                  # ends 130.5: the drain, out
    obs = serve_obs(loop.spans)
    assert len(loop_account.intervals(obs)) == 9
    assert read(obs) == pytest.approx(100.0 * (80 + 60) / (100 + 60 + 20))


def test_two_engines_apart_and_a_lost_span_breaks_the_chain():
    one, two = Loop("engine-0", 119_000.0), Loop("engine-1", 119_300.0)
    for _ in range(3):
        one.second(10, 10)
        two.second(30, 0)
    both = sorted(one.spans + two.spans, key=lambda s: s["t1_ns"])
    assert read(serve_obs(both)) == pytest.approx(25.0)
    # the ring lost the second span of engine-0: the pair around the
    # hole does not chain, and its chunks are nobody's
    holed = serve_obs([s for s in both if s is not one.spans[2]])
    assert len(loop_account.intervals(holed)) == 4
    assert read(holed) == pytest.approx(100.0 * 10 / 100)


def test_no_counter_no_chunk_no_window_no_metric():
    # a parent commit's program writes no account, or one without the
    # counter: nothing, and nothing raised
    assert read(serve_obs([])) is None
    parent = Loop("engine-0", 119_000.0, counts=False)
    parent.second(10, 0).second(10, 0)
    assert len(loop_account.intervals(serve_obs(parent.spans))) == 2
    assert read(serve_obs(parent.spans)) is None
    # ONE span in the window: nothing to difference
    assert read(serve_obs(Loop("engine-0", 119_000.0).spans)) is None
    # a window without a chunk: no share of nothing
    idle = Loop("engine-0", 119_000.0).second(0, 0).second(0, 0)
    assert read(serve_obs(idle.spans)) is None
    # spans but no T_START to place the window
    busy = Loop("engine-0", 119_000.0).second(10, 5)
    assert read(serve_obs(busy.spans)) == pytest.approx(50.0)
    assert read({"spans": busy.spans, "window_s": 10.0,
                 "end_to_end": {"setup_s": 20.0}}) is None


def test_benchmark_json_gives_it_to_the_cells_whose_family_has_the_program():
    """By name: the GPT cell it was made for and every layout of the
    hybrid family with the fused program (PR 59 folded olmo's
    ``linear_chunk_in_step_share.serve`` twin into it); a later PR may
    list more."""
    from chipbench.tests import by_name
    bench = by_name.bench()
    engine_layer = by_name.metric(bench, "engine_host_ms_per_pass.serve")
    for cell in ("serve-xl-chat-r80-v2", "serve-lfm2-agent4k-r80",
                 "serve-olmo-hybrid-doc3k-r80",
                 "serve-granite-h-chat2k-r50",
                 "serve-nemotron3-nano-reason1k-r80"):
        by_name.check_listed(
            bench, cell, [NAME], unit="%", better="higher",
            source="program_counter", layer=engine_layer["layer"],
            moves="itl_p95_ms")
    # the layouts without the program (a latent or window sublayer)
    for cell in ("serve-deepseek-v2-docqa8k-r80",
                 "serve-trinity-large-mixlen32k-r80"):
        assert cell not in by_name.metric(bench, NAME)["workloads"]
