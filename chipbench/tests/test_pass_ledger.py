"""The thirteen readers of PR 54 (``chipbench/pass_ledger.py``) on a
hand-made ring with known answers: the loop's account by kind of pass,
the engine's and the front's gap histograms, the counter table on
``engine.account`` and the first token's hops.  CPU only; like the
rest of ``chipbench/tests`` not part of the repo's tier-1 suite."""

from __future__ import annotations

import bisect
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import pass_ledger             # noqa: E402
from chipbench.readers import load_reader     # noqa: E402
from chipbench.tests import by_name           # noqa: E402
from ray_tpu.util.tracing import Histogram    # noqa: E402

MS = 1_000_000
XL, GRANITE, NEMOTRON, DEEPSEEK, OLMO, TRINITY, LFM2 = (
    "serve-xl-chat-r80-v2", "serve-granite-h-chat2k-r50",
    "serve-nemotron3-nano-reason1k-r80", "serve-deepseek-v2-docqa8k-r80",
    "serve-olmo-hybrid-doc3k-r80", "serve-trinity-large-mixlen32k-r80",
    "serve-lfm2-agent4k-r80")
SERVING = [XL, GRANITE, NEMOTRON, DEEPSEEK, OLMO, TRINITY, LFM2]
FUSED = [XL, GRANITE, NEMOTRON, LFM2, OLMO]     # olmo since PR 58
# metric -> (cells it lists, beside whichever others; the end-to-end
# metric it moves)
METRICS = {
    "step_chunk_pass_ms.serve": (FUSED, "itl_p95_ms"),
    "chunk_then_step_pass_ms.serve": ([DEEPSEEK, TRINITY], "itl_p95_ms"),
    "step_pass_ms.serve": (SERVING, "itl_p95_ms"),
    "engine_gap_p95_ms.serve": (SERVING, "itl_p95_ms"),
    "front_gap_p95_ms.serve": (SERVING, "itl_p95_ms"),
    "dispatch_ms_per_pass.serve": (SERVING, "itl_p95_ms"),
    "first_token_wake_p90_ms.serve": (SERVING, "ttft_p90_ms"),
    "first_token_write_p90_ms.serve": (SERVING, "ttft_p90_ms"),
    "chunk_key_blocks_per_chunk.serve": (
        [GRANITE, NEMOTRON, OLMO, TRINITY, LFM2], "itl_p95_ms"),
    "step_chunk_pass_host_ms.serve": (FUSED, "itl_p95_ms"),
    "step_chunk_pass_wait_ms.serve": (FUSED, "itl_p95_ms"),
    "chunk_then_step_pass_host_ms.serve": ([DEEPSEEK, TRINITY],
                                           "itl_p95_ms"),
    "chunk_then_step_pass_wait_ms.serve": ([DEEPSEEK, TRINITY],
                                           "itl_p95_ms"),
}
FIELDS = ("count", "ns", "host_ns", "wait_ns", "tokens")


def bucket(ms: float) -> int:
    edges = [Histogram.edge_ns(i) for i in range(1, Histogram.N)]
    return bisect.bisect_right(edges, int(ms * MS))


class Engine:
    """An engine's loop as its account sees it, a second at a time:
    ``second`` adds passes of each kind to the cumulative counters and
    writes them out as the chain's next ``engine.account`` span."""

    def __init__(self, t_ms: float, keys: bool = True):
        self.t_ns, self.keys = int(t_ms * MS), keys
        self.passes = 0
        self.ns = {"dispatch": 0, "wait": 0, "parked": 0}
        self.kinds, self.gaps = {}, {}
        self.counters = {"chunk_passes": 0, "chunk_key_blocks_walked": 0,
                         "decode_iterations": 0, "chunks_in_step": 0}
        self.spans = []

    def second(self, profiling=False, stretch=1.0, **passes_by_kind):
        """``kind=(passes, ms a pass, rows)``: each pass hands a token
        to ``rows`` rows that already had one; a kind named ``chunk..``
        or ``step_chunk`` runs one chunk a pass that walks 6 blocks."""
        t0 = self.t_ns
        for kind, (n, ms, rows) in passes_by_kind.items():
            kind = kind.replace("_then_", "+")
            row = self.kinds.setdefault(kind, dict.fromkeys(FIELDS, 0))
            ns = int(ms * stretch * MS)
            row["count"] += n
            row["ns"] += n * ns
            row["wait_ns"] += n * ns // 4
            row["host_ns"] += n * ns - n * ns // 4
            row["tokens"] += n * rows
            self.passes += n
            self.ns["dispatch"] += int(n * 1.5 * stretch * MS)
            self.ns["wait"] += n * ns // 4
            b = bucket(ms * stretch)
            self.gaps[b] = self.gaps.get(b, 0) + n * rows
            self.counters["decode_iterations"] += n
            if "chunk" in kind:
                self.counters["chunk_passes"] += n
                self.counters["chunk_key_blocks_walked"] += 6 * n
        self.t_ns = t0 + 1_000 * MS
        attributes = {
            "engine": "engine-0", "passes": self.passes,
            "ns": dict(self.ns), "starved_ns": dict(self.ns),
            "count": dict.fromkeys(self.ns, self.passes),
            "unaccounted_ns": 0, "unaccounted_starved_ns": 0,
            "decode_iterations": self.counters["decode_iterations"],
            "chunk_passes": self.counters["chunk_passes"],
            "chunks_in_step": 0, "profiling": profiling, "ring_dropped": 0}
        if self.keys:
            attributes.update(
                by_kind={k: dict(r) for k, r in self.kinds.items()},
                gaps=dict(self.gaps), counters=dict(self.counters))
        self.spans.append({
            "name": "engine.account", "t0_ns": t0, "t1_ns": self.t_ns,
            "span_id": f"e{len(self.spans)}", "parent_id": None,
            "trace_id": f"te{len(self.spans)}", "attributes": attributes})
        return self


class Front:
    """A proxy's chain: ``second`` writes ``n`` chunks ``ms`` apart."""

    def __init__(self, t_ms: float, proxy: str = "127.0.0.1:1"):
        self.t_ns, self.proxy = int(t_ms * MS), proxy
        self.hist, self.spans = {}, []

    def second(self, profiling=False, **gaps):
        t0 = self.t_ns
        for n, ms in gaps.values():
            self.hist[bucket(ms)] = self.hist.get(bucket(ms), 0) + n
        self.t_ns = t0 + 1_000 * MS
        self.spans.append({
            "name": "front.account", "t0_ns": t0, "t1_ns": self.t_ns,
            "span_id": f"f{self.proxy}{len(self.spans)}", "parent_id": None,
            "trace_id": f"tf{self.proxy}{len(self.spans)}",
            "attributes": {"proxy": self.proxy,
                           "write_gaps": dict(self.hist),
                           "profiling": profiling, "ring_dropped": 0}})
        return self


def request(i: int, emitted_ms: float, wake_ms=None, write_ms=None):
    """A request through the front, submitted 50 ms before its first
    token: emitted at ``emitted_ms``, its stream awake ``wake_ms`` later,
    the chunk written ``write_ms`` after that."""
    t = int(emitted_ms * MS)
    trace = f"r{i}"

    def span(name, t0, t1, **attributes):
        return {"name": name, "t0_ns": t0, "t1_ns": t1, "trace_id": trace,
                "span_id": f"{trace}{name}", "parent_id": None,
                "attributes": attributes}
    decode, front = {"output_tokens": 9}, {"route": "v1"}
    if wake_ms is not None:
        decode["first_yield_ns"] = t + int(wake_ms * MS)
        if write_ms is not None:
            front["first_chunk_ns"] = t + int((wake_ms + write_ms) * MS)
    return [span("front.request", t - 51 * MS, t + 500 * MS, **front),
            span("request.queue", t - 50 * MS, t - 40 * MS, req=i),
            span("request.prefill", t - 40 * MS, t, req=i),
            span("request.decode", t, t + 400 * MS, req=i, **decode)]


def serve_obs(spans):
    # T_START 100 s, set-up 20 s, window 10 s: [120 s, 130 s]
    return {"spans": spans, "t_start": 100.0, "window_s": 10.0,
            "end_to_end": {"setup_s": 20.0}}


def a_ring(keys: bool = True, hole: bool = False):
    """Ten seconds of window.  The engine: a baseline second, four
    plain ones, four under a profiler session (every pass 3 x as long),
    a plain one, the drain.  A plain second: 60 steps of 10 ms with 8
    rows, 10 fused passes of 30 ms with 8 rows, 2 chunk-then-step passes
    of 80 ms with 4 rows."""
    eng, front = Engine(118_500.0, keys), Front(118_500.0)
    plain = dict(step=(60, 10.0, 8), step_chunk=(10, 30.0, 8),
                 chunk_then_step=(2, 80.0, 4))
    wrote = dict(a=(300, 11.0), b=(40, 33.0), c=(4, 90.0))
    for profiling in [False] * 6 + [True] * 4 + [False] * 2:
        eng.second(profiling=profiling, stretch=3.0 if profiling else 1.0,
                   **plain)
        front.second(profiling=profiling,
                     **({k: (n, 3 * ms) for k, (n, ms) in wrote.items()}
                        if profiling else wrote))
    spans = eng.spans + front.spans
    if hole:      # the ring lost the span that ended at 122.5 s
        spans = [s for s in spans if s["t1_ns"] != 122_500 * MS]
    # first tokens: ten in plain seconds (wake 0.1 .. 1.0 ms, write
    # 2 x that), three in the session (50 ms each way), one whose
    # stream never woke before it finished, one that was not streamed
    for i in range(10):
        spans += request(i, 121_000.0 + 100 * i, 0.1 * (i + 1),
                         0.2 * (i + 1))
    for i in range(10, 13):
        spans += request(i, 126_000.0 + 100 * i, 50.0, 50.0)
    spans += request(13, 121_250.0)
    spans += request(14, 121_260.0, 0.3)
    if not keys:
        for s in spans:
            s["attributes"].pop("first_yield_ns", None)
        spans = [s for s in spans if s["name"] != "front.account"]
    return serve_obs(spans)


def read(name, obs):
    return load_reader(name).read(obs)


# the width of the bucket that holds ``ms``, in ms
def width(ms):
    return (Histogram.edge_ns(bucket(ms) + 1)
            - Histogram.edge_ns(bucket(ms))) / MS


def test_each_metric_reads_its_known_answer():
    obs = a_ring()
    assert read("step_chunk_pass_ms.serve", obs) == pytest.approx(30.0)
    assert read("chunk_then_step_pass_ms.serve", obs) == pytest.approx(80.0)
    assert read("step_pass_ms.serve", obs) == pytest.approx(10.0)
    assert read("dispatch_ms_per_pass.serve", obs) == pytest.approx(1.5)
    # a pass's host and wait parts add up to it
    assert read("step_chunk_pass_host_ms.serve", obs) == pytest.approx(22.5)
    assert read("step_chunk_pass_wait_ms.serve", obs) == pytest.approx(7.5)
    assert read("chunk_then_step_pass_host_ms.serve", obs) \
        == pytest.approx(60.0)
    assert read("chunk_then_step_pass_wait_ms.serve", obs) \
        == pytest.approx(20.0)
    # 480 tokens a second waited 10 ms, 80 waited 30, 8 waited 80: the
    # 95th percentile lies among the 30 ms ones
    assert abs(read("engine_gap_p95_ms.serve", obs) - 30.0) <= width(30.0)
    # 300 gaps of 11 ms, 40 of 33, 4 of 90
    assert abs(read("front_gap_p95_ms.serve", obs) - 33.0) <= width(33.0)
    # ten requests outside the session: the nearest-rank p90 of 0.1 ..
    # 1.0 and of 0.2 .. 2.0
    assert read("first_token_wake_p90_ms.serve", obs) == pytest.approx(0.9)
    assert read("first_token_write_p90_ms.serve", obs) == pytest.approx(1.8)
    # a count is not stretched: every interval, 6 blocks a chunk
    assert read("chunk_key_blocks_per_chunk.serve", obs) == pytest.approx(6.0)


@pytest.mark.parametrize("name", list(METRICS))
def test_a_ring_without_the_keys_reads_none(name):
    """A parent commit: ``engine.account`` without ``by_kind`` / ``gaps``
    / ``counters``, no ``front.account``, no ``first_yield_ns``."""
    assert read(name, a_ring(keys=False)) is None
    assert read(name, serve_obs([])) is None
    assert read(name, {"spans": []}) is None


def test_profiled_intervals_and_requests_are_left_out():
    obs = a_ring()
    plain = pass_ledger.engine(obs)
    every = pass_ledger.engine(obs, every=True)
    # five plain seconds and four traced ones lie in the window
    assert plain["passes"] == 5 * 72 and every["passes"] == 9 * 72
    assert plain["by_kind"]["step"]["count"] == 5 * 60
    assert every["by_kind"]["step"]["ns"] \
        == (5 + 4 * 3) * 60 * 10 * MS
    assert sum(plain["gaps"].values()) == 5 * (480 + 80 + 8)
    def grew(hist):
        return {b for b, n in hist.items() if n}
    assert grew(plain["gaps"]) == {bucket(10.0), bucket(30.0), bucket(80.0)}
    assert grew(every["gaps"]) > grew(plain["gaps"])
    assert plain["counters"]["chunk_passes"] == 5 * 12
    assert every["counters"]["chunk_key_blocks_walked"] == 9 * 12 * 6
    wrote = pass_ledger.front_gaps(obs)
    assert sum(wrote.values()) == 5 * 344
    assert grew(wrote) == {bucket(11.0), bucket(33.0), bucket(90.0)}
    # the session's three requests would have set both p90s to 50 ms
    firsts = pass_ledger.first_tokens(obs)
    assert len(firsts) == 10
    assert max(r["wake"] for r in firsts) == pytest.approx(1.0)


def test_a_broken_chain_is_skipped():
    """The ring lost a span: nothing says whether a session touched the
    time around it, so both pairs it was part of are left out, of the
    engine's chain and of the front's; a first token inside the lost
    interval has no interval to vouch for it."""
    whole, holed = a_ring(), a_ring(hole=True)
    assert pass_ledger.engine(whole)["passes"] == 5 * 72
    assert pass_ledger.engine(holed)["passes"] == 3 * 72
    assert sum(pass_ledger.front_gaps(holed).values()) == 3 * 344
    assert read("step_pass_ms.serve", holed) == pytest.approx(10.0)
    # the five requests emitted in (121.5 s, 122.5 s] go with the span
    assert len(pass_ledger.first_tokens(holed)) == 5
    assert read("first_token_wake_p90_ms.serve", holed) \
        == pytest.approx(0.5)


def test_two_proxies_chains_are_differenced_apart():
    one, two = Front(119_500.0, "h:1"), Front(119_700.0, "h:2")
    for _ in range(4):
        one.second(a=(100, 11.0))
        two.second(a=(10, 90.0))
    got = pass_ledger.front_gaps(serve_obs(one.spans + two.spans))
    assert got == {bucket(11.0): 300, bucket(90.0): 30}


def test_histogram_keys_may_have_come_through_json():
    obs = a_ring()
    text = json.loads(json.dumps(obs))
    for name in ("engine_gap_p95_ms.serve", "front_gap_p95_ms.serve"):
        assert read(name, text) == pytest.approx(read(name, obs))


def test_quantile_interpolates_inside_the_bucket():
    b = bucket(10.0)
    lo, hi = Histogram.edge_ns(b) / MS, Histogram.edge_ns(b + 1) / MS
    assert pass_ledger.quantile_ms({b: 10}, 50) == pytest.approx(
        (lo + hi) / 2)
    assert pass_ledger.quantile_ms({b: 10}, 100) == pytest.approx(hi)
    assert pass_ledger.quantile_ms({b: 10, b + 5: 0}, 10) \
        == pytest.approx(lo + (hi - lo) / 10)
    # the bucket past the last edge has no end: its start
    assert pass_ledger.quantile_ms({Histogram.N - 1: 3}, 95) \
        == pytest.approx(10_000.0)
    assert pass_ledger.quantile_ms({}, 95) is None


def test_the_report_prints_what_the_ledger_read():
    """``benchmarks/pass_ledger_report.py``: every kind's passes and
    mean time, the three views of the gap, the two hops."""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from pass_ledger_report import report
    got = report(a_ring(), {50: 10.4, 95: 31.0})
    assert got["passes"] == 5 * 72
    assert got["kinds"]["step_chunk"] == {
        "passes": 50, "ms": pytest.approx(30.0),
        "host_ms": pytest.approx(22.5), "wait_ms": pytest.approx(7.5),
        "tokens": 8}
    assert set(got["kinds"]) == {"step", "step_chunk", "chunk+step"}
    p95 = got["gaps_ms"]["p95"]
    assert abs(p95["engine"] - 30.0) <= width(30.0)
    assert abs(p95["front"] - 33.0) <= width(33.0) and p95["client"] == 31.0
    assert got["first_token_ms"]["requests"] == 10
    assert got["first_token_ms"]["write_p90"] == pytest.approx(1.8)
    assert report(a_ring(keys=False), {})["kinds"] == {}


def test_benchmark_json_has_the_thirteen_by_name_each_with_its_reader():
    bench = by_name.bench()
    for name, (cells, moves) in METRICS.items():
        for cell in cells:
            by_name.check_listed(
                bench, cell, [name], moves=moves, source="program_counter",
                better="lower", unit="blocks" if "blocks" in name else "ms")
        assert callable(load_reader(name).read)
    # one pass a window runs a chunk and THEN a step at olmo since its
    # chunks ride (PR 58): that kind's metrics do not list the cell
    assert OLMO not in by_name.metric(
        bench, "chunk_then_step_pass_ms.serve")["workloads"]


SHARE = "chunk_pass_gap_share.serve"


def test_chunk_pass_gap_share_is_what_the_kinds_with_prompt_work_emitted():
    # a plain second of the ring: 60 x 8 gaps behind a step, 10 x 8
    # behind a fused pass, 2 x 4 behind a chunk and then a step
    assert read(SHARE, a_ring()) == pytest.approx(100 * 88 / 568)
    assert read(SHARE, a_ring(keys=False)) is None     # a parent commit
    assert read(SHARE, serve_obs([])) is None
    by_name.check_listed(by_name.bench(), XL, [SHARE], moves="itl_p95_ms",
                         source="program_counter", unit="%")
    assert set(by_name.metric(by_name.bench(), SHARE)["workloads"]) \
        >= set(SERVING)
    assert load_reader(SHARE).UNTRACED is True
    # the boundary between ANY two kinds nearest the p95's 5 %: 1.4 %
    # of the gaps lie behind the slowest kind (a chunk and then a step),
    # 15.5 % behind it and the fused pass
    nearest = "p95_edge_gap_share.serve"
    assert read(nearest, a_ring()) == pytest.approx(100 * 8 / 568)
    assert read(nearest, a_ring(keys=False)) is None
    assert load_reader(nearest).UNTRACED is True


@pytest.mark.parametrize("kinds,gaps,want,marked", [
    # granite at 2.4 req/s: 207 fused passes of ~4 rows among 4,600 steps
    ({"step": 12_400, "step_chunk": 890, "idle": 0}, 13_168, 5.83, True),
    # the same engine with the fused passes' share well past the rank
    ({"step": 22_000, "step_chunk": 3_150, "chunk": 150}, 25_000, 12.0,
     False),
    ({"step": 9_900, "spec": 50, "host": 0, "prefill": 60}, 10_000, 0.5,
     False),
    ({"step": 0}, 0, None, False),
])
def test_the_edge_is_marked_between_two_and_a_half_and_eight(
        kinds, gaps, want, marked):
    """``tokens`` of a kind with prompt work hold its first tokens too
    (122 + the gaps here): the share is taken from the kinds WITHOUT,
    whose tokens are gaps alone."""
    from chipbench import edge
    by_kind = {k: {"count": 1, "tokens": n} for k, n in kinds.items()}
    share = edge.prompt_gap_share(by_kind, {bucket(10.0): gaps})
    assert share == (None if want is None else pytest.approx(want, abs=0.01))
    assert edge.on_an_edge(share) is marked
    assert bool(edge.mark(share)) is marked
    for kind in ("step_chunk", "chunk+step", "chunk+step_chunk", "chunk",
                 "prefill"):
        assert edge.holds_prompt_work(kind)
    for kind in ("step", "spec", "host", "idle"):
        assert not edge.holds_prompt_work(kind)


def test_a_rank_between_one_chunk_and_two_is_seen_by_the_nearest_boundary():
    """The granite cell at 11.2 req/s as the account read it (my chip
    run, PR 59, call 6, seed 3000059301): 46 % of the gaps behind a pass
    with prompt work, far from 5 %, and 4.4 % behind the passes that ran
    a lone chunk and THEN the fused step: the rank lay between those and
    the fused pass, and six seeds read 29.6-33.3 ms."""
    from chipbench import edge
    by_kind = {
        "chunk+step_chunk": {"count": 100, "ns": 100 * 50_460_000,
                             "tokens": 3_100},
        "step_chunk": {"count": 874, "ns": 874 * 26_410_000,
                       "tokens": 27_200},
        "step": {"count": 1190, "ns": 1190 * 18_100_000, "tokens": 34_600},
        "idle": {"count": 3, "ns": 9_000_000, "tokens": 0}}
    gaps = {bucket(20.0): 34_600 + 3_100 + 27_200 - 571}
    assert edge.prompt_gap_share(by_kind, gaps) == pytest.approx(46.2,
                                                                 abs=0.1)
    assert not edge.on_an_edge(edge.prompt_gap_share(by_kind, gaps))
    nearest = edge.nearest_boundary(by_kind, gaps)
    assert nearest == pytest.approx(4.73, abs=0.05)
    assert edge.on_an_edge(nearest)
    kinds = [level[0] for level in edge.levels(by_kind, gaps)]
    assert kinds == ["chunk+step_chunk", "step_chunk", "step"]
    assert edge.levels(by_kind, gaps)[-1][3] == pytest.approx(100.0)
    assert edge.nearest_boundary({"step": by_kind["step"]}, gaps) is None
