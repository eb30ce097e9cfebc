"""The engine loop keeps its own time account (ISSUE 40): every
``engine.*`` span site also feeds an always-on count of the loop
thread's wall time by phase and of the time it had no program in flight
(``tracing.Account``, ``engine._LOOP_PHASES``), exported by
``engine_stats()["loop_account"]``, ``/metrics`` and, about once a
second of the loop's time, one ``engine.account`` span in the ring.

Tiny CPU models at f32, both seams: the K/V-only family (with a cold
long prompt, which takes the full-width prefill) and the family that
also keeps a recurrent state."""

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
    assert acct["count"]["admit"] == acct["passes"] >= acct["count"]["grow"] \
        == st["decode_iterations"]
    assert acct["count"]["pack"] == st["decode_iterations"] \
        + st["chunk_passes"]
    assert acct["count"]["prefill_host"] == st["chunk_passes"] + full_width
    # the loop never waits with nothing in flight; a phase is starved
    # but for the part of it that follows a launch not yet landed (the
    # step's packing behind a chunk, the pass after an unfinished one)
    assert acct["starved_ns"]["wait"] == 0 < acct["ns"]["wait"]
    assert acct["starved_ns"]["parked"] == acct["ns"]["parked"]
    assert all(0 <= acct["starved_ns"][p] <= acct["ns"][p]
               for p in acct["ns"])
    assert all(acct["starved_ns"][p] > 0
               for p in ("admit", "grow", "pack", "dispatch", "emit"))
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
    assert len(chain) == st["loop_account"]["passes"] >= 10
    assert chain[0]["t0_ns"] == st["loop_account"]["t_made_ns"]
    for a, b in zip(chain, chain[1:]):
        assert b["t0_ns"] == a["t1_ns"] < b["t1_ns"]
        x, y = a["attributes"], b["attributes"]
        assert y["passes"] == x["passes"] + 1
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
    steps = [s for s in fetches
             if by_id[s["parent_id"]]["name"] == "engine.decode"]
    assert len(steps) == st["decode_iterations"]
    # some prompt ended while other rows decoded
    assert sum(s["attributes"]["first_tokens"] for s in steps) >= 1
    assert sum(s["attributes"]["bytes"] for s in fetches) == st["fetch_bytes"]
    count = st["loop_account"]["count"]
    named = {}
    for s in spans:
        named[s["name"]] = named.get(s["name"], 0) + 1
    assert named["engine.pass"] == st["loop_account"]["passes"]
    assert named["engine.schedule"] == count["admit"] + count["grow"]
    assert named["engine.upload"] == count["pack"]
    assert named["engine.dispatch"] == count["dispatch"]
    assert named["engine.fetch"] == count["wait"]
    assert named["engine.sample"] == count["emit"]
    assert named["engine.prefill_chunk"] == count["prefill_host"]
