"""The engine loop keeps its own time account (ISSUE 40): every
``engine.*`` span site also feeds an always-on count of the loop
thread's wall time by phase and of the time it had no program in flight
(``tracing.Account``, ``engine._LOOP_PHASES``), exported by
``engine_stats()["loop_account"]``, ``/metrics`` and, about once a
second of the loop's time, one ``engine.account`` span in the ring.

Tiny CPU models at f32, both seams: the K/V-only family (with a cold
long prompt, which takes the full-width prefill) and the family that
also keeps a recurrent state."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.inference import (EngineConfig, InferenceEngine,
                               metrics_snapshot)
from ray_tpu.inference import engine as engine_mod
from ray_tpu.models import gpt, hybrid
from ray_tpu.util import tracing


@pytest.fixture(scope="module", params=["kv_only", "kv_and_state"])
def seam(request):
    """(cfg, params, engine config, a prompt the full-width prefill
    takes or None)."""
    if request.param == "kv_only":
        cfg = gpt.GPTConfig.tiny(dtype=jnp.float32, max_seq=64)
        return (cfg, gpt.init_params(cfg, jax.random.PRNGKey(0)),
                EngineConfig(max_slots=4, kv_block_size=8, prefill_chunk=8),
                40)
    cfg = hybrid.HybridConfig.tiny()
    return (cfg, hybrid.init_params(cfg, jax.random.PRNGKey(0)),
            EngineConfig(max_slots=3, max_seq=96, n_blocks=14,
                         kv_block_size=8, prefill_chunk=8), None)


def _run_mixed(seam, monkeypatch, traced: bool):
    """A greedy run of mixed prompts, some admitted while others decode,
    with an ``engine.account`` span where EVERY pass ends -> (requests,
    stats after the loop thread has ended, the ring)."""
    cfg, params, ec, long_prompt = seam
    monkeypatch.setattr(engine_mod, "ACCOUNT_EVERY_NS", 0)
    rng = np.random.default_rng(4)
    tracing.disable_tracing()
    tracing.clear()
    if traced:
        tracing.enable_tracing()
    eng = InferenceEngine(params, cfg, ec)
    try:
        reqs = []
        if long_prompt:
            # cold, alone on an idle engine, over half the cache
            first = eng.submit(rng.integers(0, cfg.vocab_size,
                                            long_prompt).tolist(), max_new=12)
            it = first.stream(timeout=300)
            next(it)
            reqs.append(first)
        reqs += [eng.submit(rng.integers(0, cfg.vocab_size, n).tolist(),
                            max_new=m)
                 for n, m in ((5, 9), (19, 4), (11, 1), (8, 7), (23, 6))]
        for r in reqs:
            r.result(timeout=300)
        snap = {name: series for name, _kind, _help, series
                in metrics_snapshot()}
    finally:
        tracing.disable_tracing()
        eng.shutdown()
    assert not eng._thread.is_alive()
    spans = tracing.get_finished_spans()
    tracing.clear()
    return eng, reqs, eng.stats(), snap, spans


def test_account_partitions_the_loops_time_and_counts_every_launch(
        seam, monkeypatch):
    eng, reqs, st, snap, spans = _run_mixed(seam, monkeypatch, traced=False)
    acct = st["loop_account"]
    assert set(acct["ns"]) == set(engine_mod._LOOP_PHASES)
    # phases + unaccounted = the thread's wall time, to the nanosecond
    assert sum(acct["ns"].values()) + acct["unaccounted_ns"] \
        == acct["t_ns"] - acct["t_made_ns"]
    # every program launched is one entry of `dispatch`
    full_width = sum(r.full_width_prefill for r in reqs)
    assert full_width == (seam[3] is not None)
    # (a chunk that rode a decode step, ISSUE 41, is no launch of its
    # own; the K/V-and-state seam has no such program)
    assert (st["chunks_in_step"] > 0) == (eng._step_chunk is not None)
    assert acct["count"]["dispatch"] == st["decode_iterations"] \
        + st["chunk_passes"] - st["chunks_in_step"] + full_width
    # ``admit`` counts the loop's turns that found work, ``passes`` the
    # account's units: a turn that only launched ahead of a device with
    # nothing queued (the first after a park; after a pass that owed
    # nothing) is no unit, its time is part of the unit that reads its
    # pass (ISSUE 55)
    kinds = acct["by_kind"]
    ahead_of_nothing = acct["count"]["admit"] - acct["passes"]
    assert 1 <= ahead_of_nothing < acct["passes"] // 2
    assert sum(k["count"] for name, k in kinds.items() if name != "idle") \
        == acct["passes"]
    assert acct["count"]["admit"] >= acct["count"]["grow"] \
        == st["decode_iterations"]
    # all but those turns dispatched their pass behind an unread one
    # (the cold long prompt found the engine idle: nothing to read
    # before its full-width prefill)
    assert st["passes_drained"] == 0
    assert st["passes_launched_ahead"] >= st["decode_iterations"] \
        - ahead_of_nothing - 1
    assert acct["count"]["pack"] == st["decode_iterations"] \
        + st["chunk_passes"]
    assert acct["count"]["prefill_host"] == st["chunk_passes"] + full_width
    # the loop never waits with nothing in flight; a phase is starved
    # but for the part of it that follows a launch not yet landed (the
    # step's packing behind a chunk, and since ISSUE 55 every phase of a
    # turn whose pass is dispatched behind the unread one: a wait lands
    # the programs it saw the end of, BY COUNT, and the newer pass's
    # stay in flight)
    assert acct["starved_ns"]["wait"] == 0 < acct["ns"]["wait"]
    assert eng._acct.in_flight == 0
    assert sum(acct["starved_ns"].values()) - acct["starved_ns"]["parked"] \
        < 0.5 * (sum(acct["ns"].values()) - acct["ns"]["parked"])
    assert acct["starved_ns"]["parked"] == acct["ns"]["parked"]
    assert all(0 <= acct["starved_ns"][p] <= acct["ns"][p]
               for p in acct["ns"])
    # (the block hunt comes behind a chunk's launch or beside the pass in
    # flight: it may never find the device with nothing queued)
    starved = {p: acct["starved_ns"][p] > 0
               for p in ("admit", "pack", "dispatch", "emit")}
    assert all(starved.values()), starved
    assert acct["unaccounted_starved_ns"] <= acct["unaccounted_ns"]
    # what no site covers is a small share of the time the loop worked
    worked = acct["t_ns"] - acct["t_made_ns"] - acct["ns"]["parked"]
    assert acct["unaccounted_ns"] < 0.2 * worked
    # tracing was off: the ring holds the requests' spans and the account
    assert {s["name"] for s in spans} == {
        "request.queue", "request.prefill", "request.decode",
        "engine.account"}
    # ... which /metrics carries by phase
    key = (("engine", eng.name),)
    loop_s = snap["ray_tpu_inference_loop_seconds_total"]
    starved_s = snap["ray_tpu_inference_loop_starved_seconds_total"]
    phases = {*engine_mod._LOOP_PHASES, tracing.UNACCOUNTED}
    assert {dict(k)["phase"] for k in loop_s if k[:1] == key} == phases
    assert starved_s[key + (("phase", "wait"),)] == 0.0
    assert 0.0 < loop_s[key + (("phase", "dispatch"),)] \
        <= acct["ns"]["dispatch"] / 1e9


def test_account_spans_chain_and_never_decrease(seam, monkeypatch):
    eng, _reqs, st, _snap, spans = _run_mixed(seam, monkeypatch,
                                              traced=False)
    chain = [s for s in spans if s["name"] == "engine.account"]
    # a span a turn of the loop that found work; a turn ends 0 units (it
    # only launched ahead), 1, or 2 (it read the pass in flight early,
    # then its own: a pass that follows a full-width prefill's wait)
    assert len(chain) == st["loop_account"]["count"]["admit"] >= 10
    assert chain[-1]["attributes"]["passes"] == st["loop_account"]["passes"]
    assert chain[0]["t0_ns"] == st["loop_account"]["t_made_ns"]
    for a, b in zip(chain, chain[1:]):
        assert b["t0_ns"] == a["t1_ns"] < b["t1_ns"]
        x, y = a["attributes"], b["attributes"]
        assert 0 <= y["passes"] - x["passes"] <= 2
        for k in ("decode_iterations", "chunk_passes", "chunks_in_step",
                  "ring_dropped", "unaccounted_ns",
                  "unaccounted_starved_ns"):
            assert y[k] >= x[k]
        for k in ("ns", "starved_ns", "count"):
            assert all(y[k][p] >= x[k][p] for p in engine_mod._LOOP_PHASES)
    for s in chain:
        at = s["attributes"]
        assert at["engine"] == eng.name and at["profiling"] is False
        assert s["parent_id"] is None
        # the counters are those at the span's end, exactly
        assert sum(at["ns"].values()) + at["unaccounted_ns"] \
            == s["t1_ns"] - chain[0]["t0_ns"]
    last = chain[-1]["attributes"]
    assert last["decode_iterations"] == st["decode_iterations"]
    assert last["chunk_passes"] == st["chunk_passes"]


def test_no_fetch_span_nests_in_another_and_sites_keep_their_spans(
        seam, monkeypatch):
    """Tracing on: each phase's entry IS its site's span (same stamps,
    same count), and a first token read inside a decode step's fetch is
    that span's attribute, not a span of its own."""
    _eng, _reqs, st, _snap, spans = _run_mixed(seam, monkeypatch, traced=True)
    by_id = {s["span_id"]: s for s in spans}

    def ancestors(s):
        while s.get("parent_id") in by_id:
            s = by_id[s["parent_id"]]
            yield s["name"]
    fetches = [s for s in spans if s["name"] == "engine.fetch"]
    assert fetches and not [s for s in fetches
                            if "engine.fetch" in ancestors(s)]
    # a step's fetch comes a pass later: inside the span of the step
    # dispatched meanwhile, or the pass's own where nothing was left to
    # dispatch; it says itself that it read a step
    steps = [s for s in fetches if s["attributes"].get("stepped")]
    assert {by_id[s["parent_id"]]["name"] for s in steps} \
        <= {"engine.decode", "engine.pass", "engine.prefill_chunk"}
    assert len(steps) == st["decode_iterations"]
    # some prompt ended while other rows decoded
    assert sum(s["attributes"]["first_tokens"] for s in steps) >= 1
    assert sum(s["attributes"]["bytes"] for s in fetches) == st["fetch_bytes"]
    count = st["loop_account"]["count"]
    named = {}
    for s in spans:
        named[s["name"]] = named.get(s["name"], 0) + 1
    assert named["engine.pass"] == count["admit"]
    assert named["engine.schedule"] == count["admit"] + count["grow"]
    assert named["engine.upload"] == count["pack"]
    assert named["engine.dispatch"] == count["dispatch"]
    assert named["engine.fetch"] == count["wait"]
    assert named["engine.sample"] == count["emit"]
    assert named["engine.prefill_chunk"] == count["prefill_host"]


# ------------------------------------------ the account by KIND of pass
# (ISSUE 54: ``tracing.Account.pass_done``, ``engine._PASS_KIND``)

def _two_program_layout():
    """A hybrid layout whose delta-rule sublayer keeps the pass of two
    programs (``recurrent.has_step_chunk`` False)."""
    cfg = hybrid.HybridConfig.tiny(
        layer_types=(hybrid.LINEAR, hybrid.ATTENTION), lin_heads=2,
        lin_key_dim=8, lin_value_dim=16, dense_layers=2, dense_width=32)
    return (cfg, hybrid.init_params(cfg, jax.random.PRNGKey(0)),
            EngineConfig(max_slots=4, max_seq=96, n_blocks=24,
                         kv_block_size=8, prefill_chunk=8), None)


def _gpt_layout():
    cfg = gpt.GPTConfig.tiny(dtype=jnp.float32, max_seq=64)
    return (cfg, gpt.init_params(cfg, jax.random.PRNGKey(0)),
            EngineConfig(max_slots=4, kv_block_size=8, prefill_chunk=8), 40)


KINDS = {
    "gpt": (_gpt_layout, {"chunk", "step", "chunk+step_chunk",
                          "step_chunk", "prefill"}),
    "two_programs": (_two_program_layout, {"chunk", "step", "chunk+step"}),
}


@pytest.fixture(scope="module", params=list(KINDS))
def kinds_run(request):
    """One prompt alone (chunk passes, then steps); two more admitted in
    ONE pass while it decodes (at low occupancy a pass runs a chunk of
    each: the first alone, the last with the step where one program
    does both); once all is quiet a cold long prompt (the full-width
    prefill, where the family has one).  An ``engine.account`` span
    where EVERY pass ends -> (expected kinds, engine, requests, stats
    after the loop thread has ended, the account's chain)."""
    make, expected = KINDS[request.param]
    cfg, params, ec, long_prompt = make()
    rng = np.random.default_rng(54)

    def toks(n):
        return rng.integers(0, cfg.vocab_size, n).tolist()
    every, engine_mod.ACCOUNT_EVERY_NS = engine_mod.ACCOUNT_EVERY_NS, 0
    tracing.disable_tracing()
    tracing.clear()
    eng = InferenceEngine(params, cfg, ec)
    try:
        first = eng.submit(toks(12), max_new=30)
        it = first.stream(timeout=300)
        next(it)
        with eng._cond:                 # both reach the same admission
            reqs = [first, eng.submit(toks(20), max_new=3),
                    eng.submit(toks(20), max_new=3)]
        for r in reqs:
            r.result(timeout=300)
        if long_prompt:
            reqs.append(eng.submit(toks(long_prompt), max_new=2))
            reqs[-1].result(timeout=300)
            assert reqs[-1].full_width_prefill
    finally:
        eng.shutdown()
        engine_mod.ACCOUNT_EVERY_NS = every
    assert not eng._thread.is_alive()
    spans = tracing.get_finished_spans()
    tracing.clear()
    return (expected, eng, reqs, eng.stats(), spans)


def _partition(acct, t_ns):
    """The identities of the account by kind, as of a pass's end: the
    kinds' time, ``idle`` (no work: the parks and what led up to them)
    among them, is the account's; the others' count is the passes'."""
    kinds = acct["by_kind"]
    # (the rows by kind cover the time to the last unit's end: a turn
    # that launched ahead and read nothing ends none)
    assert acct["unit_t_ns"] <= t_ns
    assert sum(k["ns"] for k in kinds.values()) \
        == acct["unit_t_ns"] - acct["t_made_ns"]
    assert sum(k["count"] for kind, k in kinds.items() if kind != "idle") \
        == acct["passes"]
    assert all(k["host_ns"] + k["wait_ns"] == k["ns"] for k in kinds.values())
    assert kinds.get("idle", {"wait_ns": 0})["wait_ns"] \
        == acct["ns"]["parked"]


def test_every_kind_of_pass_has_its_row_and_they_partition_the_time(
        kinds_run):
    expected, eng, reqs, st, spans = kinds_run
    acct = st["loop_account"]
    kinds = acct["by_kind"]
    assert expected | {"idle"} <= set(kinds) \
        <= {*engine_mod._PASS_KIND, "idle"}
    _partition(acct, acct["t_ns"])
    # ... as of EVERY pass's end: the chain carries the account there
    chain = [s["attributes"] for s in spans if s["name"] == "engine.account"]
    ends = [s["t1_ns"] for s in spans if s["name"] == "engine.account"]
    assert len(chain) == acct["count"]["admit"]     # one a turn
    for a, t1_ns in zip(chain, ends):
        _partition({**a, "t_made_ns": acct["t_made_ns"]}, t1_ns)
    assert sum(k["wait_ns"] for k in kinds.values()) \
        == acct["ns"]["wait"] + acct["ns"]["parked"]
    # a pass is of exactly one kind, and of the kind of what it ran.
    # What a turn DISPATCHED shows in the counters' growth over that
    # turn; its unit is booked where its tokens are read, a turn later
    # (the same turn where nothing was in flight behind it), under the
    # same kind: the two sequences are one
    launched, booked = [], []
    for a, b in zip(chain, chain[1:]):
        for k, row in b["by_kind"].items():
            grew = row["count"] - a["by_kind"].get(k, {"count": 0})["count"]
            booked += [k] * grew if k not in ("idle", "host") else []
        chunks, rode, steps, prompt = (
            b["counters"][k] - a["counters"][k] for k in (
                "chunk_passes", "chunks_in_step", "decode_iterations",
                "prefill_tokens"))
        assert steps <= 1 and rode <= steps
        if prompt >= 40:                    # the full-width prefill
            assert prompt >= int(reqs[-1].prompt_tokens)
            launched.append("prefill")
            continue
        step = ("step_chunk" if rode else "step") if steps else ""
        kind = "+".join(filter(None, ["chunk" * (chunks > rode), step]))
        if kind == "chunk+step_chunk":      # lone chunks AND the one inside
            assert chunks >= 2
        launched += [kind] if kind else []
    # (the chain's first span ends the engine's first pass: its growth
    # is not between two spans)
    assert launched == booked[len(booked) - len(launched):]
    assert len(booked) - len(launched) <= 1
    seen = set(booked)
    assert expected - seen <= {"chunk"}     # (the engine's first pass)
    if "prefill" in expected:
        assert kinds["prefill"]["count"] == 1


def test_kinds_count_every_token_and_gaps_every_token_but_the_first(
        kinds_run):
    _expected, _eng, reqs, st, spans = kinds_run
    acct = st["loop_account"]
    assert sum(k.get("tokens", 0) for k in acct["by_kind"].values()) \
        == st["tokens_greedy_on_device"] + st["tokens_sampled"] \
        == sum(len(r.tokens) for r in reqs)
    # a request's tokens, as its ``request.decode`` span has them
    decoded = [s["attributes"]["output_tokens"] for s in spans
               if s["name"] == "request.decode"]
    assert sorted(decoded) == sorted(len(r.tokens) for r in reqs)
    assert sum(acct["gaps"].values()) == sum(n - 1 for n in decoded)
    # a pass of chunk programs alone ends no prompt (the row whose
    # prompt ends turns active and the pass steps it): it emits nothing
    assert acct["by_kind"]["chunk"]["tokens"] == 0
    # the histogram's buckets hold passes' times: none beyond the
    # longest time between two ends of the chain
    # (a unit may span the turn that launched ahead and the one that
    # read)
    chain = [s for s in spans if s["name"] == "engine.account"]
    longest = max(b["t1_ns"] - a["t0_ns"] for a, b in zip(chain, chain[1:]))
    top = max(acct["gaps"])
    assert tracing.Histogram.edge_ns(top) <= longest


def test_account_span_carries_the_engines_counter_table(kinds_run):
    _expected, eng, _reqs, st, spans = kinds_run
    last = [s for s in spans if s["name"] == "engine.account"][-1]
    counters = last["attributes"]["counters"]
    from ray_tpu.serve import engine_stats
    assert set(counters) == set(engine_stats.COUNTED)
    assert {k: st[k] for k in counters if k in st} \
        == {k: v for k, v in counters.items() if k in st}
    assert counters["chunk_passes"] == last["attributes"]["chunk_passes"] > 0
    assert counters["chunks_in_step"] == last["attributes"]["chunks_in_step"]
    assert counters["prefill_tokens"] == st["prefill_tokens"] > 0
    assert last["attributes"]["engine"] == eng.name


def test_a_failed_step_leaves_the_partition_exact(monkeypatch):
    """``_fail_all``: the failed pass ends like any other (its kind is
    what it had launched), and the engine keeps serving and counting."""
    cfg, params, ec, _ = _gpt_layout()
    monkeypatch.setattr(engine_mod, "ACCOUNT_EVERY_NS", 0)
    tracing.disable_tracing()
    tracing.clear()
    eng = InferenceEngine(params, cfg, ec)
    try:
        real_step, boom = eng._step, {"armed": True}

        def failing_step(*a):
            if boom.pop("armed", False):
                raise RuntimeError("injected step failure")
            return real_step(*a)
        eng._step = failing_step
        bad = eng.submit([1, 2], max_new=8)
        with pytest.raises(RuntimeError, match="injected"):
            bad.result(timeout=120)
        good = eng.submit([3, 4, 5], max_new=4)
        assert len(good.result(timeout=120)) == 4
    finally:
        eng.shutdown()
    st = eng.stats()
    spans = tracing.get_finished_spans()
    tracing.clear()
    acct = st["loop_account"]
    _partition(acct, acct["t_ns"])
    for s in spans:
        if s["name"] == "engine.account":
            _partition({**s["attributes"], "t_made_ns": acct["t_made_ns"]},
                       s["t1_ns"])
    # each prompt is one chunk and the step behind it in the same pass:
    # the first one's step failed, and the chunk's first token, already
    # owed, still reached the stream
    assert {k: row["count"] for k, row in acct["by_kind"].items()
            if k != "idle"} == {"chunk+step": 2, "step": 2}
    assert acct["by_kind"]["chunk+step"]["tokens"] == 1 + 2
    assert sum(k.get("tokens", 0) for k in acct["by_kind"].values()) \
        == st["tokens_greedy_on_device"] + st["tokens_sampled"] \
        == len(bad.tokens) + len(good.tokens) == 1 + 4
    assert sum(acct["gaps"].values()) == 3


def test_first_yield_is_stamped_by_a_stream_and_not_by_result():
    """``request.decode`` says when the consumer's ``stream()`` had the
    first token in hand; a request read by ``result()`` has no such
    stamp."""
    cfg, params, ec, _ = _gpt_layout()
    tracing.disable_tracing()
    tracing.clear()
    eng = InferenceEngine(params, cfg, ec)
    try:
        streamed = eng.submit([5, 6, 7], max_new=30)
        it = streamed.stream(timeout=300)
        next(it)
        stamp = streamed.first_yield_s
        assert streamed.first_token_s <= stamp <= time.monotonic()
        assert len(list(it)) == 29 and streamed.first_yield_s == stamp
        waited = eng.submit([5, 6, 7], max_new=6)
        waited.result(timeout=300)
        assert waited.first_yield_s is None
    finally:
        eng.shutdown()
    spans = {s["attributes"]["req"]: s
             for s in tracing.get_finished_spans("request.decode")}
    tracing.clear()
    got = spans[streamed.id]
    assert got["t0_ns"] <= got["attributes"]["first_yield_ns"] \
        == int(stamp * 1e9) <= got["t1_ns"]
    assert got["attributes"]["output_tokens"] == 30
    assert "first_yield_ns" not in spans[waited.id]["attributes"]
    assert spans[waited.id]["attributes"]["output_tokens"] == 6
