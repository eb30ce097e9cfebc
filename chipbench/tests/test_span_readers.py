"""The span readers of PR 26 on a hand-made ring with known answers,
and both cells' rehearsals printing them.  CPU only; like the rest of
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

from chipbench import spans as span_lib       # noqa: E402
from chipbench.readers import load_reader     # noqa: E402

MS = 1_000_000
SERVE = ("queue_wait_p90_ms.serve", "prefill_p90_ms.serve",
         "front_overhead_p90_ms.serve", "decode_pass_ms.serve",
         "prefill_pass_share.serve", "engine_host_ms_per_pass.serve")
TRAIN = ("trainer_host_ms_per_step.train",)

_ids = iter(range(1, 10_000))


def span(name, t0_ms, t1_ms, parent=None, trace=None, **attributes):
    i = next(_ids)
    return {"name": name, "t0_ns": int(t0_ms * MS), "t1_ns": int(t1_ms * MS),
            "span_id": f"s{i}", "trace_id": trace or (
                parent["trace_id"] if parent else f"t{i}"),
            "parent_id": parent["span_id"] if parent else None,
            "attributes": attributes}


def request(submit_ms, queue_ms, prefill_ms, decode_ms=500.0, front_ms=None,
            first_chunk_extra_ms=0.0):
    """A request's spans; with ``front_ms`` it came through the serve
    front, which took that long before ``submit()``."""
    out, parent = [], None
    admitted = submit_ms + queue_ms
    first = admitted + prefill_ms
    if front_ms is not None:
        parent = span("front.request", submit_ms - front_ms,
                      first + decode_ms + 1.0,
                      first_chunk_ns=int((first + first_chunk_extra_ms)
                                         * MS))
        out.append(parent)
    trace = parent["trace_id"] if parent else f"r{next(_ids)}"
    out += [span("request.queue", submit_ms, admitted, parent, trace),
            span("request.prefill", admitted, first, parent, trace),
            span("request.decode", first, first + decode_ms, parent, trace)]
    return out


def engine_pass(t0_ms, chunk_ms, decode_ms, fetch_ms, host_ms=2.0):
    """pass = schedule (host_ms) + chunk (dispatch only, nothing
    fetched) + decode (its fetch lasting fetch_ms)."""
    end = t0_ms + host_ms + chunk_ms + decode_ms
    p = span("engine.pass", t0_ms, end)
    out = [p, span("engine.schedule", t0_ms, t0_ms + host_ms, p)]
    t = t0_ms + host_ms
    if chunk_ms:
        c = span("engine.prefill_chunk", t, t + chunk_ms, p)
        out += [c, span("engine.dispatch", t, t + chunk_ms, c)]
        t += chunk_ms
    d = span("engine.decode", t, t + decode_ms, p, speculative=False)
    out += [d, span("engine.fetch", t + 1.0, t + 1.0 + fetch_ms, d)]
    return out


def serve_obs(spans):
    # T_START 100 s, set-up 20 s, window 10 s: [120 s, 130 s]
    return {"spans": spans, "t_start": 100.0, "window_s": 10.0,
            "end_to_end": {"setup_s": 20.0}}


def read(name, obs):
    return load_reader(name).read(obs)


def test_request_readers_window_edges_and_missing_front():
    spans = []
    # ten requests submitted in the window: queue 10..100 ms, prefill
    # 1000..1900 ms; the front took 3 ms before submit() and 2 ms after
    # the first token for the first five, the other five came without
    for i in range(10):
        spans += request(120_000 + 900 * i, 10.0 * (i + 1),
                         1000.0 + 100 * i,
                         front_ms=3.0 if i < 5 else None,
                         first_chunk_extra_ms=2.0 + i)
    # the lead-in and the drain do not count, however slow
    spans += request(119_999, 5_000.0, 9_000.0, front_ms=50.0)
    spans += request(130_001, 7_000.0, 9_000.0, front_ms=50.0)
    # submitted in the window, finished before its first token
    spans += request(125_000, 1.0, 0.0)[:1]
    obs = serve_obs(spans)
    assert len(span_lib.window_requests(obs)) == 11
    # nearest rank over 11 queue waits (1, 10..100): index round(.9*10)
    assert read("queue_wait_p90_ms.serve", obs) == pytest.approx(90.0)
    assert read("prefill_p90_ms.serve", obs) == pytest.approx(1800.0)
    # fronted requests only: 3 + (2 + i) for i in 0..4 -> p90 = 3 + 6
    assert read("front_overhead_p90_ms.serve", obs) == pytest.approx(9.0)
    # no front.request at all: the two engine metrics stay, the front's
    # is left out
    bare = serve_obs([s for s in spans if s["name"] != "front.request"])
    assert read("queue_wait_p90_ms.serve", bare) == pytest.approx(90.0)
    assert read("front_overhead_p90_ms.serve", bare) is None
    # the window's edges belong to it
    edge = serve_obs(request(120_000, 4.0, 8.0) + request(130_000, 6.0, 8.0))
    assert read("queue_wait_p90_ms.serve", edge) == pytest.approx(6.0)


def test_pass_readers_leave_out_the_pass_the_session_may_have_cut():
    spans = []
    # four passes in the traced interval; the newest has lost its
    # decode (the profiler session ended inside it) and must not count
    spans += engine_pass(124_000, chunk_ms=100.0, decode_ms=200.0,
                         fetch_ms=190.0)
    spans += engine_pass(124_400, chunk_ms=0.0, decode_ms=200.0,
                         fetch_ms=195.0)
    spans += engine_pass(124_700, chunk_ms=100.0, decode_ms=204.0,
                         fetch_ms=200.0)
    spans += engine_pass(125_100, chunk_ms=100.0, decode_ms=0.5,
                         fetch_ms=0.0)[:4]
    # an orphan: opened while its pass had started before the session
    spans.append(span("engine.prefill_chunk", 123_900, 123_990))
    obs = serve_obs(spans)
    assert [p["t0_ns"] // MS for p, _ in span_lib.whole_passes(obs)] \
        == [124_000, 124_400, 124_700]
    assert read("decode_pass_ms.serve", obs) == pytest.approx(
        (200.0 + 200.0 + 204.0) / 3)
    # chunks 100 + 0 + 100 over passes 302 + 202 + 306
    assert read("prefill_pass_share.serve", obs) == pytest.approx(
        100.0 * 200.0 / 810.0)
    # pass minus its fetches: 112, 7, 106
    assert read("engine_host_ms_per_pass.serve", obs) == pytest.approx(
        (112.0 + 7.0 + 106.0) / 3)
    # passes outside the window (tracing on for a whole run) stay out
    early = serve_obs(spans + engine_pass(110_000, 0.0, 900.0, 1.0))
    assert read("decode_pass_ms.serve", early) == pytest.approx(
        (200.0 + 200.0 + 204.0) / 3)


def test_trainer_reader_takes_the_wait_out_and_only_traced_steps():
    spans = []
    for step in range(40, 44):          # traced steps
        t = 1000.0 * step
        if step > 40:                   # the profiler started inside
            spans.append(span("train.next_batch", t, t + 0.25, step=step))
        spans += [span("train.shard_batch", t + 0.25, t + 0.35, step=step),
                  span("train.step", t + 0.35, t + 0.75, step=step)]
    report = span("train.report", 43_001.0, 43_601.0, step=43)
    spans += [report, span("train.fetch", 43_001.0, 43_600.5, report,
                           step=43)]
    # the profiler's stop, inside next() of a step that is not traced
    spans.append(span("train.next_batch", 44_000.0, 47_000.0, step=44))
    # 3 x 0.25 + 4 x 0.1 + (600 - 599.5) over four steps
    assert read("trainer_host_ms_per_step.train", {"spans": spans}) \
        == pytest.approx((0.75 + 0.4 + 0.5) / 4)


@pytest.mark.parametrize("name,program", [
    ("decode_program_ms.serve", "jit_step"),
    ("chunk_program_ms.serve", "jit_chunk_fn")])
def test_program_readers_split_the_device_time_by_program(name, program):
    """The device side of the two host metrics, from the reduced trace
    (``trace_reduce.summarize``) as the traffic kind hands it on."""
    trace = {"module_seconds": {"jit_step": 1.8, "jit_chunk_fn": 2.1,
                                "jit__argmax": 0.0001},
             "module_counts": {"jit_step": 10.0, "jit_chunk_fn": 20.0,
                               "jit__argmax": 13.0}}
    assert read(name, {"trace": trace}) \
        == pytest.approx({"jit_step": 180.0, "jit_chunk_fn": 105.0}[program])
    # no trace (--trace 0), no device plane in it (a rehearsal), or a
    # run in which the program never ran: nothing to say
    for obs in ({}, {"trace": None},
                {"trace": {"module_seconds": {}, "module_counts": {}}},
                {"trace": {"module_seconds": {"jit__argmax": 0.1},
                           "module_counts": {"jit__argmax": 3.0}}}):
        assert read(name, obs) is None


@pytest.mark.parametrize("name", SERVE + TRAIN)
def test_no_spans_no_window_no_metric(name):
    assert read(name, serve_obs([])) is None
    # spans but no T_START to place the window: nothing to say
    spans = request(120_000, 1.0, 2.0, front_ms=1.0)
    if name in SERVE[:3]:
        assert read(name, {"spans": spans, "window_s": 10.0,
                           "end_to_end": {"setup_s": 20.0}}) is None
    # the program's own ring, empty in this process
    assert read(name, {"window_s": 10.0, "t_start": 0.0,
                       "end_to_end": {"setup_s": 0.0}}) is None


@pytest.mark.parametrize("cell,seconds,names", [
    ("train-124m-b16s1024", "1", TRAIN),
    ("serve-xl-chat-r80-v2", "2", SERVE)])
def test_rehearsal_prints_the_new_metrics(cell, seconds, names, tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    # one CPU device, tracing off, whatever the caller's shell says
    for name in ("RAY_TPU_TRACING", "RAY_TPU_TRACE_DIR", "XLA_FLAGS"):
        env.pop(name, None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", cell, "--seed", "3000000021", "--seconds", seconds,
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["metrics"] == {}
    # the sound path at the fixture size passes every check, and the
    # numbers compared end the line
    assert line["rehearsal_verdict_not_a_result"] is True, line["checks"]
    assert list(line)[-1] == "checks"
    assert set(line["notes"]["setup_stamps"]) >= {"import_s"}
    seen = line["rehearsal_metrics_not_device_numbers"]
    for name in names:
        assert seen[name]["value"] is not None, (name, seen)
        assert seen[name]["value"] >= 0 or name.startswith("front_")


def test_recorded_host_spans_lie_beside_the_device_gaps():
    """The excerpt of a chip trace (PR 26): host spans and device ops on
    one timeline, so that each idle gap of the device can be given the
    spans that cover it.  For the ``benchmark`` issue that will put them
    into ``trace_reduce.summarize``'s gap labels."""
    from chipbench import trace_reduce
    with open(os.path.join(ROOT, "chipbench", "tests",
                           "recorded_host_spans.json")) as f:
        rows = json.load(f)["rows"]
    assert all(len(r) == 5 for r in rows)
    host = [r for r in rows if r[0].startswith("/host:")]
    ops = [r for r in rows if r[0].startswith(trace_reduce.DEVICE_PREFIX)
           and r[1] == trace_reduce.OPS_LINE]
    assert [r[2] for r in host if r[2] == "engine.pass"] \
        == ["engine.pass"] * 2
    assert {r[2] for r in host} == {
        "engine.pass", "engine.schedule", "engine.prefill_chunk",
        "engine.decode", "engine.upload", "engine.dispatch",
        "engine.fetch", "engine.sample"}
    # every span lies inside one of the two passes
    passes = [(r[3], r[3] + r[4]) for r in host if r[2] == "engine.pass"]
    assert all(any(a <= r[3] and r[3] + r[4] <= b for a, b in passes)
               for r in host)
    _busy, merged = trace_reduce._union((r[3], r[3] + r[4]) for r in ops)
    gaps = [(e0, s1) for (_s0, e0), (s1, _e1) in zip(merged, merged[1:])
            if s1 - e0 > 1_000_000]

    def covering(g0, g1):
        return {r[2] for r in host
                if r[2] != "engine.pass" and r[3] < g1 and r[3] + r[4] > g0}
    assert len(gaps) == 3 and all(covering(*g) for g in gaps)
    by_end = {r[3] + r[4]: r[2] for r in ops}
    labelled = {by_end[g0].split()[0]: covering(g0, g1) for g0, g1 in gaps}
    # the decode program has ended, its logits go to the host and come
    # back for the argmax: the tail of the fetch, the head of sampling
    assert labelled["copy.27"] == {"engine.decode", "engine.fetch",
                                   "engine.sample"}
    # after the argmax: the rest of sampling, then the next pass's
    # scheduling and the upload for its first program
    assert {"engine.sample", "engine.schedule", "engine.upload"} \
        <= labelled["iota_reduce_fusion"]
