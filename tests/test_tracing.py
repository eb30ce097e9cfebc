"""Tracing tests (reference test model:
python/ray/tests/test_tracing.py — task/actor spans, context
propagation, trace stitching)."""

import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.util import tracing


@pytest.fixture
def traced(tmp_path):
    tracing.enable_tracing(str(tmp_path / "traces"))
    tracing.clear()
    yield str(tmp_path / "traces")
    tracing.disable_tracing()
    tracing.clear()


def test_span_nesting_and_ids(traced):
    with tracing.span("outer") as outer:
        with tracing.span("inner") as inner:
            pass
    spans = tracing.get_finished_spans()
    by_name = {s["name"]: s for s in spans}
    assert by_name["inner"]["parent_id"] == by_name["outer"]["span_id"]
    assert by_name["inner"]["trace_id"] == by_name["outer"]["trace_id"]
    assert by_name["outer"]["parent_id"] is None
    assert by_name["outer"]["end"] >= by_name["outer"]["start"]


def test_span_error_status(traced):
    with pytest.raises(ValueError):
        with tracing.span("boom"):
            raise ValueError("x")
    (span,) = tracing.get_finished_spans("boom")
    assert span["status"].startswith("error")


def test_disabled_is_noop():
    tracing.disable_tracing()
    tracing.clear()
    with tracing.span("nothing") as s:
        assert s is tracing.NOOP and not s
    assert tracing.get_finished_spans() == []


def test_task_spans_stitch_across_processes(traced, rt_init):
    @ray_tpu.remote
    def work(x):
        return x + 1

    with tracing.span("driver_root"):
        ref = work.remote(1)
        assert ray_tpu.get(ref, timeout=60) == 2

    spans = tracing.collect_spans(traced)
    names = {s["name"] for s in spans}
    assert any("work.remote" in n for n in names)
    assert any("work.execute" in n for n in names)
    submit = next(s for s in spans if "work.remote" in s["name"])
    execute = next(s for s in spans if "work.execute" in s["name"])
    # one trace across submission and (worker-side) execution, with
    # correct PARENTAGE: the worker's execute span is a child of the
    # client's submit span (not merely a sibling under the root), and
    # the submit span is a child of the ambient driver span
    assert execute["trace_id"] == submit["trace_id"]
    assert execute["parent_id"] == submit["span_id"]
    assert execute["pid"] != submit["pid"]   # a REAL process boundary
    root = next(s for s in spans if s["name"] == "driver_root")
    assert submit["parent_id"] == root["span_id"]


def test_collect_spans_skips_truncated_tail(tmp_path):
    """A writer killed mid-write leaves a truncated trailing JSONL line;
    collection must skip it, not raise."""
    d = tmp_path / "traces"
    d.mkdir()
    good = {"name": "ok", "trace_id": "t", "span_id": "s",
            "start": 1.0, "end": 2.0}
    import json as _json
    (d / "spans-12345.jsonl").write_text(
        _json.dumps(good) + "\n" + '{"name": "trunca')
    spans = tracing.collect_spans(str(d))
    assert [s["name"] for s in spans] == ["ok"]


def test_trace_dir_change_after_disable_reopens_file(tmp_path):
    """disable_tracing() then enable_tracing(new_dir) must re-point the
    cached span file at the NEW dir (the old cached handle is stale)."""
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    tracing.enable_tracing(a)
    with tracing.span("in_a"):
        pass
    tracing.disable_tracing()
    tracing.enable_tracing(b)
    with tracing.span("in_b"):
        pass
    tracing.flush_spans()
    names_a = {s["name"] for s in tracing.collect_spans(a)}
    names_b = {s["name"] for s in tracing.collect_spans(b)}
    tracing.disable_tracing()
    tracing.clear()
    assert names_a == {"in_a"}
    assert names_b == {"in_b"}


def test_emit_batches_are_flushed_by_collect(tmp_path):
    """Batched emission: collect_spans force-drains this process's
    pending spans so nothing is lost to the write batch."""
    d = str(tmp_path / "traces")
    tracing.enable_tracing(d)
    for i in range(5):
        with tracing.span(f"s{i}"):
            pass
    spans = tracing.collect_spans(d)
    tracing.disable_tracing()
    tracing.clear()
    assert {s["name"] for s in spans} >= {f"s{i}" for i in range(5)}


# -- the off/on contract, ids, clock ----------------------------------------

def test_off_span_site_is_the_shared_noop_and_leaves_the_ring():
    tracing.disable_tracing()
    tracing.clear()
    assert not tracing.active()
    a = tracing.span("engine.pass", rows=3)
    with a as sp:
        sp.set(anything=1)
    assert a is tracing.NOOP and tracing.span("other") is a
    assert tracing.get_finished_spans() == []
    # always-on spans record whatever the flag says
    with tracing.span("front.request", always=True, route="v1") as front:
        assert tracing.inject_context() == front.context()
        # core/runtime.py ships a context in a task spec only if so
        assert not tracing.active()
    assert [s["name"] for s in tracing.get_finished_spans()] \
        == ["front.request"]
    tracing.clear()


def test_ids_parents_and_wall_clock_export(traced):
    before = time.time()
    with tracing.span("outer", rows=2) as outer:
        ctx = tracing.inject_context()
        with tracing.span("inner"):
            pass
        # the receiving end of a thread hop: the context goes explicitly
        def hop():
            with tracing.span("hop"):
                pass
        t = threading.Thread(target=tracing.call_in_context,
                             args=(ctx, hop))
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    stamped = tracing.record_span("request.queue", outer.t0_ns,
                                  outer.t1_ns, parent=outer.context(),
                                  prompt_tokens=5)
    after = time.time()
    spans = {s["name"]: s for s in tracing.get_finished_spans()}
    o = spans["outer"]
    assert o["parent_id"] is None and o["attributes"] == {"rows": 2}
    for child in ("inner", "hop", "request.queue"):
        assert spans[child]["parent_id"] == o["span_id"]
        assert spans[child]["trace_id"] == o["trace_id"]
    assert len(o["trace_id"]) == 32 and len(o["span_id"]) == 16
    assert len({s["span_id"] for s in spans.values()}) == len(spans)
    # stamps are the monotonic clock, start/end the wall clock
    assert o["t0_ns"] == outer.t0_ns <= o["t1_ns"]
    assert abs((o["t1_ns"] - o["t0_ns"]) / 1e9
               - (o["end"] - o["start"])) < 1e-6
    assert before - 0.01 <= o["start"] <= o["end"] <= after + 0.01
    assert stamped.attributes == {"prompt_tokens": 5}
    assert tracing.inject_context() is None      # outside any span


def test_timeline_renders_span_names_and_attributes(traced):
    from ray_tpu.util.timeline import build_trace
    with tracing.span("engine.pass", active=3):
        with tracing.span("engine.fetch", bytes=128):
            pass
    trace = build_trace(spans=tracing.collect_spans(traced))
    by_name = {e["name"]: e for e in trace["traceEvents"]}
    assert by_name["engine.fetch"]["args"]["bytes"] == 128
    assert by_name["engine.pass"]["args"]["active"] == 3
    assert by_name["engine.fetch"]["args"]["parent_id"] \
        == by_name["engine.pass"]["args"]["span_id"]
    # wall-clock microseconds, as every other source of the timeline
    assert abs(by_name["engine.pass"]["ts"] / 1e6 - time.time()) < 60


# -- the account: one thread's time by phase, fed by its span sites ----------

PHASES = {"admit": "engine.schedule", "dispatch": "engine.dispatch",
          "wait": "engine.fetch", "host": None}


@pytest.fixture
def clock(monkeypatch):
    """``time.monotonic_ns`` as ``tracing`` sees it, set by hand: every
    stamp a phase takes is the value the test put there."""
    class Clock:
        now = 1_000

        def monotonic_ns(self):
            return self.now

        def __getattr__(self, name):
            return getattr(time, name)
    c = Clock()
    monkeypatch.setattr(tracing, "time", c)
    return c


def _account():
    return tracing.Account(PHASES, launch=("dispatch",), land=("wait",))


def test_account_partitions_the_threads_time_exactly(clock):
    """Sum of the phases' self times + unaccounted = the time since the
    account was made, to the nanosecond; a phase entered inside another
    suspends the outer one."""
    tracing.disable_tracing()
    acct = _account()
    clock.now += 7                      # before any site: unaccounted
    with acct.phase("admit"):
        clock.now += 100
        with acct.phase("dispatch"):    # suspends admit
            clock.now += 30
            with acct.phase("wait"):
                clock.now += 5
            clock.now += 2
        clock.now += 10
    clock.now += 11
    with acct.phase("wait"):
        clock.now += 1_000
    snap = acct.snapshot()
    assert snap["ns"] == {"admit": 110, "dispatch": 32, "wait": 1_005,
                          "host": 0}
    assert snap["unaccounted_ns"] == 18
    assert snap["count"] == {"admit": 1, "dispatch": 1, "wait": 2, "host": 0}
    assert sum(snap["ns"].values()) + snap["unaccounted_ns"] \
        == acct.t_ns - acct.t_made_ns == clock.now - 1_000
    # a phase that raises is charged and closed like any other
    with pytest.raises(ValueError):
        with acct.phase("host"):
            clock.now += 3
            raise ValueError("x")
    with acct.phase("admit"):
        clock.now += 1
    snap = acct.snapshot()
    assert snap["ns"]["host"] == 3 and snap["ns"]["admit"] == 111
    assert sum(snap["ns"].values()) + snap["unaccounted_ns"] \
        == clock.now - 1_000


def test_account_phase_counts_off_and_yields_the_sites_span_on(clock):
    """Off: the counters move and the site gets the shared NOOP.  On:
    the span the site would have had, from the account's two stamps."""
    tracing.disable_tracing()
    tracing.clear()
    acct = _account()
    with acct.phase("admit") as sp:
        assert sp is tracing.NOOP
        sp.set(admitted=1)
        clock.now += 40
    assert acct.snapshot()["ns"]["admit"] == 40
    assert tracing.get_finished_spans() == []
    tracing.enable_tracing()
    try:
        clock.now += 5
        with tracing.span("engine.pass") as outer:
            with acct.phase("admit") as sp:
                sp.set(admitted=2)
                t0 = clock.now
                clock.now += 60
                with acct.phase("host") as inner:   # a site with no span
                    assert inner is tracing.NOOP
                    clock.now += 9
            t1 = clock.now
    finally:
        tracing.disable_tracing()
    (got,) = tracing.get_finished_spans("engine.schedule")
    tracing.clear()
    assert (got["t0_ns"], got["t1_ns"]) == (t0, t1)
    assert got["attributes"] == {"admitted": 2}
    assert got["parent_id"] == outer.span_id
    snap = acct.snapshot()
    assert snap["ns"]["admit"] == 100 and snap["ns"]["host"] == 9
    assert snap["count"]["admit"] == 2


def test_account_starved_time_follows_launches_and_landings(clock):
    """Time that passes while nothing is in flight is starved: with a
    synchronous loop everything but the wait; a step launched behind
    the one in flight leaves only what is outside both."""
    tracing.disable_tracing()
    acct = _account()

    def spend(phase, ns):
        with acct.phase(phase):
            clock.now += ns
    spend("admit", 10)          # nothing launched yet: starved
    spend("dispatch", 20)       # ... and so is the launch itself
    assert acct.in_flight == 1
    clock.now += 3              # between sites, a program in flight
    spend("wait", 500)
    assert acct.in_flight == 0
    clock.now += 4              # between sites, nothing in flight
    spend("admit", 10)
    # two launches landed by ONE fetch (a chunk and the step behind it)
    spend("dispatch", 20)
    spend("dispatch", 25)       # the first is in flight: not starved
    assert acct.in_flight == 2
    spend("host", 7)
    spend("wait", 400)
    assert acct.in_flight == 0
    snap = acct.snapshot()
    assert snap["starved_ns"] == {"admit": 20, "dispatch": 40, "wait": 0,
                                  "host": 0}
    assert snap["ns"] == {"admit": 20, "dispatch": 65, "wait": 900,
                          "host": 7}
    assert (snap["unaccounted_ns"], snap["unaccounted_starved_ns"]) == (7, 4)


def test_account_lands_by_count_where_the_thread_runs_ahead(clock):
    """A thread that launches pass N+1 before it reads pass N
    (ISSUE 55): the wait for N lands the programs it SAW the end of
    (``landed(upto)``: the first ``upto`` launches, as ``launched``
    counts them) and N+1's stay in flight, so the turn-around between
    the two waits is not starved; ``in_flight`` never goes below 0 nor
    up in a wait, whatever it claims; a wait that says nothing lands
    everything, as before."""
    tracing.disable_tracing()
    acct = _account()

    def spend(phase, ns, landed=None):
        with acct.phase(phase):
            clock.now += ns
            if landed is not None:
                acct.landed(landed)
    spend("dispatch", 20)               # pass 1: starved, nothing queued
    spend("admit", 5)                   # pass 1 in flight from here on
    spend("dispatch", 30)               # pass 2, a chunk ...
    spend("dispatch", 10)               # ... and the step behind it
    assert (acct.in_flight, acct.launched) == (3, 3)
    spend("wait", 400, landed=1)        # pass 1 read: pass 2 still queued
    assert acct.in_flight == 2
    spend("host", 7)                    # the row loop, beside the device
    spend("dispatch", 30)               # pass 3
    with acct.phase("wait"):            # pass 2 read: its chunk, then
        clock.now += 300                # its step (the newest counts)
        acct.landed(2)
        acct.landed(3)
    assert acct.in_flight == 1
    spend("wait", 50, landed=2)         # an older claim lands nothing
    assert acct.in_flight == 1
    spend("wait", 150, landed=9)        # claims more than was launched
    assert (acct.in_flight, acct.launched) == (0, 4)
    spend("host", 9)                    # nothing queued: starved again
    spend("dispatch", 10)
    spend("dispatch", 10)
    spend("wait", 100)                  # says nothing: lands them all
    assert acct.in_flight == 0
    snap = acct.snapshot()
    assert snap["starved_ns"] == {"admit": 0, "dispatch": 20 + 10,
                                  "wait": 0, "host": 9}
    assert snap["ns"] == {"admit": 5, "dispatch": 110, "wait": 1_000,
                          "host": 16}
    assert sum(snap["ns"].values()) + snap["unaccounted_ns"] \
        == clock.now - 1_000


def test_histogram_edges_are_a_function_of_the_index():
    """0.1 ms to 10 s in steps of at most a tenth, one bucket under and
    one over; a duration on an edge belongs to the bucket that starts
    there."""
    H = tracing.Histogram
    edges = [H.edge_ns(i) for i in range(H.N)]
    assert edges[:2] == [0, 100_000] and edges[-1] == 10_000_000_000
    assert H.N <= 125
    assert all(a < b <= a * 11 // 10 for a, b in zip(edges[1:], edges[2:]))
    h = H()
    for i in range(1, H.N):
        h.add(edges[i])
        h.add(edges[i] - 1, weight=2)
    h.add(0)
    h.add(10 ** 12)
    snap = h.snapshot()
    assert snap[0] == 3 and snap[H.N - 1] == 2
    assert all(snap[i] == 3 for i in range(1, H.N - 1))
    assert H().snapshot() == {}          # sparse: only what was counted


@pytest.mark.parametrize("q", [50, 90, 95, 99])
def test_histogram_quantile_is_within_a_bucket_of_the_sorted_list_s(q):
    """Weighted durations in, a snapshot differenced against an earlier
    one out: the reader's interpolated quantile lies within one bucket's
    width (a tenth) of the quantile of the weighted list itself."""
    from chipbench import pass_ledger
    rng = np.random.default_rng(q)
    h = tracing.Histogram()
    for ns in rng.integers(100_000, 10 ** 9, 50):      # an earlier window
        h.add(int(ns))
    before = h.snapshot()
    durations = np.exp(rng.uniform(np.log(2e5), np.log(5e9), 3_000))
    weights = rng.integers(0, 40, durations.size)
    listed = []
    for ns, w in zip(durations.astype(int), weights):
        h.add(int(ns), int(w))
        listed += [int(ns)] * int(w)
    after = h.snapshot()
    hist = {i: after[i] - before.get(i, 0) for i in after}
    assert sum(hist.values()) == len(listed) == int(weights.sum())
    want = float(np.percentile(listed, q)) / 1e6
    got = pass_ledger.quantile_ms(hist, q)
    assert abs(got - want) <= 0.1 * want
    assert pass_ledger.quantile_ms({}, q) is None


def test_account_by_kind_partitions_the_threads_time_exactly(clock):
    """A unit runs from the last one's end to the newest stamp, a
    stretch with no work being a unit too: the kinds' ``ns`` add up to
    the account's time; a kind's ``host_ns`` + ``wait_ns`` is its
    ``ns``, the ``waits`` phases being the wait; what the caller counts
    is summed a kind; ``gaps`` weighs a unit's time by the tokens that
    waited for it."""
    tracing.disable_tracing()
    acct = tracing.Account({**PHASES, "parked": None}, launch=("dispatch",),
                           land=("wait",), waits=("wait", "parked"))

    def spend(phase, ns):
        with acct.phase(phase):
            clock.now += ns
    clock.now += 4                       # the account was made: unit 1
    spend("admit", 10)
    spend("dispatch", 20)
    clock.now += 1
    spend("wait", 300)
    spend("host", 6)
    clock.now += 2      # after the newest stamp: the NEXT unit's time
    acct.pass_done("step", 4, tokens=5)
    spend("dispatch", 30)                # unit 2 starts 2 ns back
    spend("dispatch", 40)
    spend("wait", 700)
    acct.pass_done("chunk+step", 2, tokens=3, chunks=1)
    clock.now += 9                       # turn-around, then no work
    spend("parked", 5_000)
    acct.pass_done("idle")
    clock.now += 3                       # woken for nothing, parks again
    spend("parked", 1_000)
    acct.pass_done("idle")
    clock.now += 7                       # unit 5 starts where it woke
    spend("admit", 10)
    acct.pass_done("step", tokens=1)
    snap = acct.snapshot()
    assert snap["by_kind"] == {
        "step": {"count": 2, "ns": 341 + 17, "host_ns": 41 + 17,
                 "wait_ns": 300, "tokens": 6},
        "chunk+step": {"count": 1, "ns": 772, "host_ns": 72,
                       "wait_ns": 700, "tokens": 3, "chunks": 1},
        "idle": {"count": 2, "ns": 6_012, "host_ns": 9 + 3,
                 "wait_ns": 6_000}}
    assert snap["by_kind"]["idle"]["wait_ns"] == snap["ns"]["parked"]
    assert sum(k["ns"] for k in snap["by_kind"].values()) \
        == acct.t_ns - acct.t_made_ns == clock.now - 1_000
    assert all(k["host_ns"] + k["wait_ns"] == k["ns"]
               for k in snap["by_kind"].values())
    # the phases' own partition is untouched by the units
    assert sum(snap["ns"].values()) + snap["unaccounted_ns"] \
        == clock.now - 1_000
    # 4 tokens waited 341 ns, 2 waited 772; a first token is no gap
    assert snap["gaps"] == {0: 6}
    big = tracing.Account(PHASES, land=("wait",), waits=("wait",))
    clock.now += 250_000
    spend_big = big.phase("wait")
    with spend_big:
        clock.now += 3_000_000
    big.pass_done("spec", 9, tokens=9)
    (bucket,) = big.snapshot()["gaps"]
    assert tracing.Histogram.edge_ns(bucket) <= 3_250_000 \
        < tracing.Histogram.edge_ns(bucket + 1)
    assert big.snapshot()["gaps"][bucket] == 9


def test_account_snapshot_from_another_thread_while_kinds_appear():
    """One writer, no lock: a reader on another thread copies the rows
    by kind while the writer adds kinds it has not seen before, and
    every copy is whole (a row's five fields, counts that only grow)."""
    import sys
    acct = tracing.Account({"wait": None}, land=("wait",), waits=("wait",))
    errors, seen = [], []
    done = threading.Event()

    def read():
        try:
            while not done.is_set():
                snap = acct.snapshot()
                assert all(set(row) == {"count", "ns", "host_ns", "wait_ns",
                                        "tokens"}
                           for row in snap["by_kind"].values())
                seen.append(sum(r["count"] for r in snap["by_kind"].values()))
        except BaseException as e:         # handed to the test's thread
            errors.append(e)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    reader = threading.Thread(target=read, daemon=True)
    try:
        reader.start()
        for i in range(20_000):
            with acct.phase("wait"):
                pass
            acct.pass_done(f"kind-{i % 997}", 1, tokens=1)
    finally:
        done.set()
        reader.join(timeout=60)
        sys.setswitchinterval(interval)
    assert not reader.is_alive() and not errors
    assert seen == sorted(seen) and seen[-1] <= 20_000
    snap = acct.snapshot()
    assert len(snap["by_kind"]) == 997
    assert sum(r["count"] for r in snap["by_kind"].values()) == 20_000 \
        == sum(snap["gaps"].values())


def test_record_account_chains_and_says_what_touched_it(clock):
    """A link runs from where the writer's last one ended to the stamp
    it is given, carries the writer's counters and says whether a
    profiler session touched its interval."""
    tracing.disable_tracing()
    tracing.clear()
    end = tracing.record_account("x.account", 1_000, 1_500, True, n=2)
    assert end == 1_500
    end = tracing.record_account("x.account", end, 2_100, False, n=5)
    assert end == 2_100
    a, b = tracing.get_finished_spans("x.account")
    tracing.clear()
    assert (a["t0_ns"], a["t1_ns"], b["t0_ns"], b["t1_ns"]) \
        == (1_000, 1_500, 1_500, 2_100)
    assert a["attributes"] == {"profiling": True, "ring_dropped":
                               tracing.ring_dropped(), "n": 2}
    assert b["attributes"]["profiling"] is False and b["attributes"]["n"] == 5


def test_ring_counts_what_it_evicts(monkeypatch):
    """``deque(maxlen)`` drops the oldest span in silence; the count
    tells a reader that its window may be cut."""
    import collections
    tracing.disable_tracing()
    monkeypatch.setattr(tracing, "RING_SIZE", 4)
    monkeypatch.setattr(tracing, "_ring", collections.deque(maxlen=4))
    before = tracing.ring_dropped()
    for i in range(4):
        tracing.record_span(f"s{i}", 10 * i, 10 * i + 5)
    assert tracing.ring_dropped() == before
    for i in range(4, 7):
        tracing.record_span(f"s{i}", 10 * i, 10 * i + 5)
    assert tracing.ring_dropped() == before + 3
    assert [s["name"] for s in tracing.get_finished_spans()] \
        == ["s3", "s4", "s5", "s6"]


# -- spans where the work happens: engine, serve front, trainer -------------
# tiny CPU models, one engine for the module, no cluster, no subprocess

PASS_CHILDREN = ("engine.schedule", "engine.prefill_chunk", "engine.decode")
LEAVES = ("engine.upload", "engine.dispatch", "engine.fetch",
          "engine.sample")


@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import gpt
    cfg = gpt.GPTConfig.tiny(dtype=jnp.float32, max_seq=64)
    return cfg, gpt.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def engine(tiny):
    from ray_tpu.inference import EngineConfig, InferenceEngine
    cfg, params = tiny
    # 6 blocks of 8 under 6 concurrent requests: some are preempted
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=4, max_seq=32, kv_block_size=8, n_blocks=6,
        prefill_chunk=16))
    yield eng
    eng.shutdown()


def _burst(eng, cfg, n=6, max_new=12, seed=1):
    rng = np.random.default_rng(seed)
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size,
                                    int(rng.integers(6, 20))).tolist(),
                       max_new=max_new) for _ in range(n)]
    for r in reqs:
        r.result(timeout=300)
    return reqs


def _children(spans, parent):
    return sorted((s for s in spans if s["parent_id"] == parent["span_id"]),
                  key=lambda s: s["t0_ns"])


def test_request_spans_partition_submit_to_finish(engine, tiny):
    """Always on: queue + prefill + decode = finish - submit for every
    request of a burst, a preempted one included; with tracing off the
    ring holds nothing else but the loop's account."""
    tracing.disable_tracing()
    tracing.clear()
    before = engine.stats()
    reqs = _burst(engine, tiny[0])
    after = engine.stats()
    spans = [s for s in tracing.get_finished_spans()
             if s["name"] != "engine.account"]
    assert {s["name"] for s in spans} \
        == {"request.queue", "request.prefill", "request.decode"}
    by_req = {}
    for s in spans:
        by_req.setdefault(s["attributes"]["req"], {})[s["name"]] = s
    assert len(by_req) == len(reqs)
    for r in reqs:
        q, p, d = (by_req[r.id]["request." + k]
                   for k in ("queue", "prefill", "decode"))
        assert q["trace_id"] == p["trace_id"] == d["trace_id"]
        assert q["t1_ns"] == p["t0_ns"] and p["t1_ns"] == d["t0_ns"]
        assert sum(s["t1_ns"] - s["t0_ns"] for s in (q, p, d)) \
            == int(r.finished_s * 1e9) - int(r.created_s * 1e9)
        assert q["t0_ns"] <= q["t1_ns"] <= p["t1_ns"] <= d["t1_ns"]
        assert d["attributes"]["output_tokens"] == 12
        assert q["attributes"]["preemptions"] == r.preemptions
        assert q["attributes"]["prompt_tokens"] == r.prompt_tokens
    assert sum(r.preemptions for r in reqs) \
        == after["preemptions"] - before["preemptions"] > 0
    # the counters at the same boundaries
    assert after["admissions"] - before["admissions"] \
        == len(reqs) + sum(r.preemptions for r in reqs)
    assert after["chunk_passes"] - before["chunk_passes"] \
        == sum(p["request.prefill"]["attributes"]["chunk_passes"]
               for p in by_req.values()) > 0
    assert after["prefill_tokens"] > before["prefill_tokens"]
    # ... which an operator reads on /metrics
    from ray_tpu import inference
    snap = {n: series for n, _kind, _help, series
            in inference.metrics_snapshot()}
    key = (("engine", engine.name),)
    for counter in ("admissions", "chunk_passes", "prefill_tokens"):
        assert snap[f"ray_tpu_inference_{counter}_total"][key] \
            == after[counter]
    # submitted outside any span: roots of one trace
    assert all(s["parent_id"] is None for s in spans)


def test_decode_passes_count_the_blocks_they_attend(engine, tiny):
    """Two requests, one after the other: every one-token pass has ONE
    live row, which attends the blocks that hold its ``position + 1``
    keys, while the rows' tables name 4 rows x 4 blocks a pass."""
    rng = np.random.default_rng(5)
    before = engine.stats()
    attended = 0
    for n_prompt, max_new in ((6, 5), (13, 9)):
        engine.submit(rng.integers(0, tiny[0].vocab_size, n_prompt).tolist(),
                      max_new=max_new).result(timeout=300)
        # the prefill emits the first token; pass t writes position
        # n_prompt + t and attends the blocks of 8 up to it
        attended += sum((n_prompt + t) // 8 + 1 for t in range(max_new - 1))
    after = engine.stats()
    passes = after["decode_iterations"] - before["decode_iterations"]
    assert passes == 4 + 8
    assert after["kv_blocks_attended"] - before["kv_blocks_attended"] \
        == attended == 2 * 1 + 5 * 2 + 5 * 3
    assert after["kv_blocks_tabled"] - before["kv_blocks_tabled"] \
        == passes * 4 * 4
    from ray_tpu import inference
    snap = {n: series for n, _kind, _help, series
            in inference.metrics_snapshot()}
    for counter in ("kv_blocks_attended", "kv_blocks_tabled"):
        assert snap[f"ray_tpu_inference_{counter}_total"][
            (("engine", engine.name),)] == after[counter]


def test_engine_pass_spans_nest_and_do_not_overlap(engine, tiny, traced):
    _burst(engine, tiny[0], n=3, max_new=6, seed=2)
    tracing.disable_tracing()          # passes after this record nothing
    spans = tracing.get_finished_spans()
    passes = [s for s in spans if s["name"] == "engine.pass"]
    assert passes and all(s["parent_id"] is None for s in passes)
    seen = set()
    for p in passes:
        kids = _children(spans, p)
        # (a pass with no step left to dispatch reads the pass in
        # flight itself: a fetch and the row loop right under it)
        assert {k["name"] for k in kids} <= {*PASS_CHILDREN, "engine.fetch",
                                             "engine.sample"}
        for k in kids:
            if k["name"] not in PASS_CHILDREN:
                seen.add(k["name"])
                continue
            leaves = _children(spans, k)
            assert {s["name"] for s in leaves} <= set(LEAVES)
            seen.update(s["name"] for s in leaves)
            for inner, outer in ((leaves, k), (kids, p)):
                assert all(outer["t0_ns"] <= s["t0_ns"] and
                           s["t1_ns"] <= outer["t1_ns"] for s in inner)
                assert all(a["t1_ns"] <= b["t0_ns"]
                           for a, b in zip(inner, inner[1:]))
        seen.update(k["name"] for k in kids)
    assert seen == set(PASS_CHILDREN) | set(LEAVES)
    decode = next(s for s in spans if s["name"] == "engine.decode")
    assert decode["attributes"]["speculative"] is False
    assert decode["attributes"]["active"] >= 1
    fetch = next(s for s in spans if s["name"] == "engine.fetch"
                 and s["attributes"].get("stepped")
                 and not s["attributes"]["first_tokens"])
    # the step's own greedy tokens, one int32 a row of 4: the float32
    # logits (4 x 4 x vocab bytes until ISSUE 39) stay on the device
    assert fetch["attributes"]["bytes"] == 4 * 4


def test_a_greedy_pass_dispatches_no_sampling_program(engine, tiny, traced,
                                                      monkeypatch):
    """Greedy tokens are the programs' own argmax: chunked prompts and
    their decode passes call ``gpt.sample_token`` (``jit__argmax``, a
    program of its own between two ``jit_step``s) not once, and an
    ``engine.sample`` span is the host's loop over the rows alone, with
    no dispatch under it: ``engine.dispatch`` spans are the chunk and
    step programs, one each."""
    from ray_tpu.inference import engine as engine_mod
    from ray_tpu.models import gpt
    calls = []
    sound = gpt.sample_token
    monkeypatch.setattr(
        engine_mod.gpt, "sample_token",
        lambda *a, **kw: calls.append(kw) or sound(*a, **kw))
    rng = np.random.default_rng(9)
    before = engine.stats()
    # short enough for the chunk path (at most half the cache's 32) and
    # for the 6 blocks of 8 to hold all three: nobody is preempted
    reqs = [engine.submit(rng.integers(0, tiny[0].vocab_size, n).tolist(),
                          max_new=5) for n in (3, 5, 7)]
    for r in reqs:
        r.result(timeout=300)
    after = engine.stats()
    tracing.disable_tracing()
    assert calls == []
    assert after["tokens_sampled"] == before["tokens_sampled"]
    assert after["tokens_greedy_on_device"] \
        - before["tokens_greedy_on_device"] == 15
    spans = tracing.get_finished_spans()
    # (a chunk that rode a decode step, ISSUE 41, is no program of its
    # own: the step then brings one integer more, a prompt's first token
    # when the chunk ended it, and that prompt owes no read of its own)
    rode = after["chunks_in_step"] - before["chunks_in_step"]
    programs = after["decode_iterations"] - before["decode_iterations"] \
        + after["chunk_passes"] - before["chunk_passes"] - rode
    assert len([s for s in spans if s["name"] == "engine.dispatch"]) \
        == programs
    assert rode == sum(s["attributes"]["chunk_tokens"] > 0 for s in spans
                       if s["name"] == "engine.decode")
    fetched = sum(s["attributes"]["bytes"] for s in spans
                  if s["name"] == "engine.fetch")
    assert fetched == after["fetch_bytes"] - before["fetch_bytes"] \
        == 16 * (after["decode_iterations"] - before["decode_iterations"]) \
        + 4 * 3


def test_front_request_is_the_root_of_a_request_trace(tiny):
    """proxy loop -> executor -> handle pool -> engine loop thread: one
    trace id from front.request down to request.decode."""
    import json
    import socket
    from ray_tpu import serve
    from ray_tpu.inference import (EngineConfig, build_gpt_deployment,
                                   parse_stream_chunks)
    cfg, params = tiny
    tracing.disable_tracing()
    tracing.clear()
    serve.run(build_gpt_deployment(
        cfg=cfg, engine_cfg=EngineConfig(max_slots=2), params=params),
        use_actors=False, http=True)
    try:
        host, port = serve.proxy_address()[len("http://"):].split(":")
        body = json.dumps({"prompt": [9, 2, 6], "max_tokens": 5,
                           "stream": True}).encode()
        with socket.create_connection((host, int(port)), timeout=120) as s:
            s.sendall(b"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
                      + f"Content-Length: {len(body)}\r\n\r\n".encode()
                      + body)
            s.settimeout(120)
            buf = b""
            while b"0\r\n\r\n" not in buf:
                data = s.recv(4096)
                assert data, "connection closed before the last chunk"
                buf += data
        chunks = parse_stream_chunks(buf.split(b"\r\n\r\n", 1)[1])
        assert chunks[-1]["done"] is True
        # the span closes after the last chunk is written
        deadline = time.time() + 10
        while time.time() < deadline \
                and not tracing.get_finished_spans("front.request"):
            time.sleep(0.01)
    finally:
        serve.shutdown()
    (front,) = tracing.get_finished_spans("front.request")
    spans = tracing.get_finished_spans()
    mine = [s for s in spans if s["name"].startswith("request.")]
    assert [s["name"] for s in mine] \
        == ["request.queue", "request.prefill", "request.decode"]
    for s in mine:
        assert s["trace_id"] == front["trace_id"]
        assert s["parent_id"] == front["span_id"]
        assert front["t0_ns"] <= s["t0_ns"] and s["t1_ns"] <= front["t1_ns"]
    assert front["attributes"]["route"] == "v1"
    first_token = mine[1]["t1_ns"]
    assert first_token <= front["attributes"]["first_chunk_ns"] \
        <= front["t1_ns"]


def _post(host, port, payload) -> bytes:
    import json
    import socket
    body = json.dumps(payload).encode()
    with socket.create_connection((host, int(port)), timeout=120) as s:
        s.sendall(b"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
                  + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        s.settimeout(120)
        buf = b""
        while b"0\r\n\r\n" not in buf and not (
                b"Content-Length" in buf and buf.endswith(b"}")):
            data = s.recv(4096)
            if not data:
                break
            buf += data
    return buf.split(b"\r\n\r\n", 1)[1]


def test_front_account_chains_and_counts_one_gap_fewer_than_chunks(
        tiny, monkeypatch):
    """The proxy counts, always, the time between a streamed response's
    chunks as it has written them, and carries the counts to the ring as
    a chain of ``front.account`` spans; nothing is written while nothing
    streams.  ``request.decode`` says when the stream had its first
    token: between the engine's emit and the first chunk's write."""
    from ray_tpu import serve
    from ray_tpu.inference import (EngineConfig, build_gpt_deployment,
                                   parse_stream_chunks)
    from ray_tpu.serve import asgi
    cfg, params = tiny
    monkeypatch.setattr(asgi, "ACCOUNT_EVERY_NS", 0)   # a span a chunk
    tracing.disable_tracing()
    tracing.clear()
    serve.run(build_gpt_deployment(
        cfg=cfg, engine_cfg=EngineConfig(max_slots=2), params=params),
        use_actors=False, http=True)
    try:
        address = serve.proxy_address()[len("http://"):]
        host, port = address.split(":")
        plain = _post(host, port, {"prompt": [9, 2, 6], "max_tokens": 3})
        assert b"tokens" in plain
        assert tracing.get_finished_spans("front.account") == []
        sent = []
        for n in (40, 3):
            chunks = parse_stream_chunks(_post(host, port, {
                "prompt": [9, 2, 6], "max_tokens": n, "stream": True}))
            assert chunks[-1]["done"] is True and len(chunks) == n + 1
            sent.append(len(chunks))
        deadline = time.time() + 10
        while time.time() < deadline and len(
                tracing.get_finished_spans("front.request")) < 3:
            time.sleep(0.01)
    finally:
        serve.shutdown()
    chain = tracing.get_finished_spans("front.account")
    spans = tracing.get_finished_spans()
    tracing.clear()
    assert len(chain) == sum(sent) == 44 + 1     # one a chunk the client read
    gaps = [sum(s["attributes"]["write_gaps"].values()) for s in chain]
    for a, b in zip(chain, chain[1:]):
        assert b["t0_ns"] == a["t1_ns"] <= b["t1_ns"]
    # a response's first chunk is no gap: one gap fewer than its chunks
    assert gaps == [*range(sent[0]), *range(sent[0] - 1, sum(sent) - 1)]
    last = chain[-1]["attributes"]
    assert set(last) == {"proxy", "write_gaps", "profiling", "ring_dropped"}
    assert last["proxy"] == address and last["profiling"] is False
    assert chain[0]["attributes"]["write_gaps"] == {}    # a first chunk
    # the first token's two hops, of the long streamed request
    decodes = [s for s in spans if s["name"] == "request.decode"]
    assert "first_yield_ns" not in decodes[0]["attributes"]     # result()
    woke = decodes[1]["attributes"]["first_yield_ns"]
    front = next(s for s in spans if s["name"] == "front.request"
                 and s["trace_id"] == decodes[1]["trace_id"])
    assert decodes[1]["t0_ns"] <= woke \
        <= front["attributes"]["first_chunk_ns"] == chain[0]["t1_ns"]


def _fit(tmp_path, steps, report_every):
    import optax
    from ray_tpu.models import mlp
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    cfg = mlp.MLPConfig(in_dim=8, hidden=(16,), out_dim=4)
    rng = np.random.default_rng(0)
    batch = {"x": rng.normal(size=(8, 8)).astype(np.float32),
             "y": rng.integers(0, 4, 8).astype(np.int32)}

    def batches():
        while True:
            yield batch
    return JaxTrainer(
        loss_fn=lambda p, b: mlp.loss_fn(p, b, cfg),
        init_params=lambda r: mlp.init_params(cfg, r),
        optimizer=optax.sgd(1e-2), train_data=batches(), num_steps=steps,
        report_every=report_every,
        scaling_config=ScalingConfig(mesh={"dp": 1}, use_cpu_devices=True),
        run_config=RunConfig(name="spans", storage_path=str(tmp_path))
    ).fit()


def test_trainer_spans_one_step_span_a_step(tmp_path, traced):
    _fit(tmp_path, steps=6, report_every=2)
    spans = tracing.get_finished_spans()

    def steps_of(name):
        return [s["attributes"]["step"] for s in spans if s["name"] == name]
    for name in ("train.next_batch", "train.shard_batch", "train.step"):
        assert steps_of(name) == list(range(6))
    assert steps_of("train.report") == steps_of("train.fetch") == [1, 3, 5]
    assert steps_of("train.checkpoint") == [5]      # the last step's
    (ckpt,) = tracing.get_finished_spans("train.checkpoint")
    assert ckpt["attributes"]["bytes"] > 0
    by_id = {s["span_id"]: s for s in spans}
    for s in spans:
        if s["name"] in ("train.fetch", "train.checkpoint"):
            assert by_id[s["parent_id"]]["name"] == "train.report"
        elif s["name"].startswith("train."):
            assert s["parent_id"] is None


def test_profiler_session_switches_spans_on_and_carries_them(
        engine, tiny, tmp_path):
    """No flag: a jax.profiler session alone records pass-level spans,
    and the same spans lie on the xplane's host plane."""
    import jax
    from jax.profiler import ProfileData, ProfileOptions
    tracing.disable_tracing()
    tracing.clear()
    opts = ProfileOptions()
    opts.python_tracer_level = 0       # the spans, not every Python call
    jax.profiler.start_trace(str(tmp_path / "prof"), profiler_options=opts)
    try:
        assert tracing.active()
        _burst(engine, tiny[0], n=2, max_new=4, seed=3)
        _fit(tmp_path, steps=2, report_every=1)
    finally:
        jax.profiler.stop_trace()
    assert not tracing.active()
    ring = {s["name"] for s in tracing.get_finished_spans()}
    (path,) = (tmp_path / "prof").glob("plugins/profile/*/*.xplane.pb")
    host = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("engine.", "train.")):
                        host.setdefault(ev.name, dict(ev.stats))
    wanted = {"engine.pass", "engine.decode", "engine.fetch", "train.step",
              "train.next_batch"}
    assert wanted <= set(host) and wanted <= ring
    # every pass-level span is an annotation; the always-on account
    # (built from stamps, like a request's spans) is none
    assert set(host) == {n for n in ring
                         if n.startswith(("engine.", "train."))
                         and n != "engine.account"}
    assert host["train.step"]["step_num"] in (0, 1)
    assert host["engine.fetch"]["bytes"] > 0


def test_annotations_carry_their_stamp_onto_the_profilers_clock(tmp_path):
    """The clock map: in a session an annotated span's ``t0_ns`` rides
    as metadata beside the event's ``start_ns`` (which counts from the
    session's start), so ONE offset places ``time.monotonic_ns()``, and
    with it the spans that are no annotations, on the device's
    timeline."""
    import jax
    from jax.profiler import ProfileData, ProfileOptions
    tracing.disable_tracing()
    tracing.clear()
    acct = tracing.Account({"dispatch": "engine.dispatch"})
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "prof"), profiler_options=opts)
    try:
        for i in range(5):
            with tracing.span("engine.pass", active=i):
                with acct.phase("dispatch") as sp:
                    assert sp and sp.t0_ns == acct.t_ns
                    time.sleep(0.002)
            always = tracing.record_span("engine.account", sp.t0_ns,
                                         sp.t1_ns)
    finally:
        jax.profiler.stop_trace()
    assert acct.interval_profiled() and not acct.interval_profiled()
    ring = {s["t0_ns"]: s for s in tracing.get_finished_spans()
            if s["name"] != "engine.account"}
    tracing.clear()
    (path,) = (tmp_path / "prof").glob("plugins/profile/*/*.xplane.pb")
    offsets = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("engine."):
                        t0_ns = int(dict(ev.stats)["t0_ns"])
                        assert ring[t0_ns]["name"] == ev.name
                        offsets.append(t0_ns - ev.start_ns)
    assert len(offsets) == 10 == len(ring)
    # one offset, to the stamp-to-annotation distance (microseconds)
    assert max(offsets) - min(offsets) < 1_000_000
    # ... which places a span that is no annotation
    on_session_clock = always.t0_ns - offsets[-1]
    assert 0 < on_session_clock < 60e9
