"""Speculative decoding tests: greedy token parity vs the
full-recompute oracle for BOTH drafters (n-gram prompt-lookup and
truncated-layer self-draft) under prefix reuse, chunked prefill,
block-pressure preemption mid-speculation, and verify-step failure
recovery; block-refcount audits proving reject rollback leaks zero
blocks; the typed SpeculationUnsupported boundary and the documented
temperature fallback; the infer_speculate chaos point (forced full
rejection and injected verify failure); and the accept-rate /
tokens-per-step metric surface.

Everything runs on CPU with GPTConfig.tiny at f32 (greedy argmax
parity must not hinge on bf16 ties)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.inference import (EngineConfig, InferenceEngine,
                               SpeculationUnsupported, metrics_snapshot,
                               ngram_propose)
from ray_tpu.models import gpt
from ray_tpu.util import tracing


@pytest.fixture(scope="module")
def cfg():
    return gpt.GPTConfig.tiny(dtype=jnp.float32, max_seq=64)


@pytest.fixture(scope="module")
def params(cfg):
    return gpt.init_params(cfg, jax.random.PRNGKey(0))


def _ref_tokens(params, cfg, prompt, max_new):
    out = gpt.generate(params, cfg, jnp.asarray([prompt], jnp.int32),
                       max_new=max_new, temperature=0.0)
    return np.asarray(out)[0, len(prompt):].tolist()


def _spec_cfg(mode, **kw):
    base = dict(max_slots=4, kv_block_size=8, prefill_chunk=16,
                speculate=mode, speculate_k=4)
    if mode == "self":
        base["draft_layers"] = 1
    base.update(kw)
    return EngineConfig(**base)


def _assert_no_block_leak(st):
    assert st["blocks_free"] + st["prefix_cached_blocks"] \
        == st["blocks_total"], f"block leak: {st}"


# ------------------------------------------------------ n-gram drafter


def test_ngram_propose_matches_repeated_pattern():
    ctx = np.array([7, 1, 2, 3, 9, 1, 2, 3], np.int32)
    # suffix [1,2,3] last matched at s=1 -> continuation [9, 1, 2, ...]
    prop = ngram_propose(ctx, 3)
    assert prop.tolist() == [9, 1, 2]


def test_ngram_propose_prefers_longest_match_and_latest_site():
    ctx = np.array([1, 2, 5, 3, 2, 6, 3, 2], np.int32)
    # 2-gram [3,2] matches at s=3 -> continuation starts with 6; the
    # 1-gram [2] would have matched later but the longer match wins
    assert ngram_propose(ctx, 2).tolist() == [6, 3]


def test_ngram_propose_no_match_is_empty():
    ctx = np.array([1, 2, 3, 4, 5], np.int32)
    assert ngram_propose(ctx, 4).size == 0
    assert ngram_propose(np.array([1], np.int32), 4).size == 0
    assert ngram_propose(np.array([], np.int32), 4).size == 0


def test_ngram_propose_caps_at_k_and_history_end():
    ctx = np.array([1, 2, 1, 2, 1, 2], np.int32)
    assert ngram_propose(ctx, 2).size <= 2
    # match near the end: continuation shorter than k is fine
    prop = ngram_propose(np.array([5, 6, 7, 5, 6], np.int32), 8)
    assert prop.tolist() == [7, 5, 6]


# --------------------------------------------- parity: the tentpole


@pytest.mark.parametrize("mode", ["ngram", "self"])
def test_spec_parity_prefix_reuse_and_chunked_prefill(params, cfg, mode):
    """THE speculation invariant (tier-1): greedy decode with
    draft-then-verify — under paging, radix prefix reuse, and chunked
    prefill — is token-identical to the full-recompute oracle, while
    actually speculating (accepted tokens > 0)."""
    eng = InferenceEngine(params, cfg, _spec_cfg(mode))
    try:
        rng = np.random.default_rng(7)
        head = rng.integers(0, cfg.vocab_size, 24).tolist()   # 3 blocks
        prompts = ([head + rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(2, 10))).tolist()
                    for _ in range(3)]
                   + [[1, 2, 3, 4] * 6]                # n-gram gold
                   + [rng.integers(0, cfg.vocab_size, 40).tolist()])
        for wave in ("cold", "warm"):
            reqs = [eng.submit(p, max_new=8) for p in prompts]
            for p, r in zip(prompts, reqs):
                assert r.result(timeout=300) == \
                    _ref_tokens(params, cfg, p, 8), (mode, wave, p)
        st = eng.stats()
        assert st["speculate"] == mode
        assert st["spec_passes"] > 0
        assert st["spec_drafted_tokens"] > 0
        assert st["spec_accepted_tokens"] > 0
        assert st["prefix_hit_tokens"] > 0        # warm wave adopted heads
        # per-row throughput: > 1 token per (row, compiled call) pair is
        # the whole point; the plain engine reports exactly 1.0 here
        assert st["tokens_per_step"] > 1.0
        _assert_no_block_leak(st)
    finally:
        eng.shutdown()


def test_spec_parity_under_preemption_refunds_charge(params, cfg):
    """Block pressure preempts a row that holds a speculative charge:
    the charged blocks joined the row's chain at grant time, so the
    preemption refund covers them automatically — streams stay
    oracle-exact and the pool audits clean."""
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=4, max_seq=32, kv_block_size=8, n_blocks=6,
        prefill_chunk=16, speculate="self", draft_layers=1,
        speculate_k=4))
    try:
        rng = np.random.default_rng(1)
        jobs = []
        for _ in range(6):
            p = rng.integers(0, cfg.vocab_size,
                             int(rng.integers(6, 20))).tolist()
            jobs.append((p, eng.submit(p, max_new=12)))
        for p, h in jobs:
            assert h.result(timeout=300) == _ref_tokens(params, cfg, p, 12)
        st = eng.stats()
        assert st["preemptions"] > 0, \
            "pool of 6 blocks under 6 concurrent requests never preempted"
        assert st["spec_drafted_tokens"] > 0, "never speculated"
        _assert_no_block_leak(st)
    finally:
        eng.shutdown()


def test_spec_verify_failure_recovers_pool_and_prefix(params, cfg):
    """A verify-step failure takes the same recovery path as a plain
    step failure: in-flight requests fail typed, the donated pool is
    reallocated, the prefix index is cleared, and the engine keeps
    serving with oracle parity."""
    eng = InferenceEngine(params, cfg, _spec_cfg("ngram"))
    try:
        rep = [1, 2, 3, 4] * 6                   # n-gram drafts for sure
        assert eng.generate(rep, max_new=4, timeout=300) \
            == _ref_tokens(params, cfg, rep, 4)

        real_verify = eng._verify
        boom = {"armed": True}

        def failing_verify(*a):
            if boom.pop("armed", False):
                raise RuntimeError("injected verify failure")
            return real_verify(*a)

        eng._verify = failing_verify
        bad = eng.submit(rep, max_new=8)
        with pytest.raises(RuntimeError, match="injected verify"):
            bad.result(timeout=60)
        st = eng.stats()
        assert st["prefix_cached_blocks"] == 0       # index cleared
        assert st["blocks_free"] == st["blocks_total"]
        assert eng.generate(rep, max_new=4, timeout=300) \
            == _ref_tokens(params, cfg, rep, 4)
    finally:
        eng.shutdown()


# --------------------------------------------------- chaos: infer_speculate


def test_chaos_forced_rejection_keeps_parity_and_blocks(params, cfg):
    """The registered infer_speculate gate: scripted FULL rejection of
    every draft still verifies, emits the plain step's token (parity),
    and rolls the speculative block charge back without leaking."""
    from ray_tpu.core import fault_injection as fi

    eng = InferenceEngine(params, cfg, _spec_cfg("ngram"))
    plan = fi.FaultPlan()
    plan.add(fi.Rule("infer_speculate", "script",
                     fn=lambda ctx: ctx.__setitem__("reject_all", True)))
    fi.install(plan)
    try:
        rep = [1, 2, 3, 4] * 6
        assert eng.generate(rep, max_new=8, timeout=300) \
            == _ref_tokens(params, cfg, rep, 8)
        assert any(p == "infer_speculate" for p, _, _ in plan.log)
        st = eng.stats()
        assert st["spec_drafted_tokens"] > 0         # drafts were offered
        assert st["spec_accepted_tokens"] == 0       # ... all rejected
        assert st["spec_accept_rate"] == 0.0
        _assert_no_block_leak(st)
    finally:
        fi.uninstall()
        eng.shutdown()


def test_chaos_speculate_raise_takes_recovery_path(params, cfg):
    """Raising from the infer_speculate hook injects a failure at the
    draft/verify choke point; the engine fails in-flight work typed and
    keeps serving."""
    from ray_tpu.core import fault_injection as fi

    eng = InferenceEngine(params, cfg, _spec_cfg("ngram"))
    plan = fi.FaultPlan()

    def raiser(ctx):
        raise RuntimeError("injected speculation failure")

    plan.add(fi.Rule("infer_speculate", "script", fn=raiser, nth=1))
    fi.install(plan)
    try:
        rep = [1, 2, 3, 4] * 6
        bad = eng.submit(rep, max_new=8)
        with pytest.raises(RuntimeError, match="injected speculation"):
            bad.result(timeout=60)
    finally:
        fi.uninstall()
    try:
        rep = [1, 2, 3, 4] * 6
        assert eng.generate(rep, max_new=4, timeout=300) \
            == _ref_tokens(params, cfg, rep, 4)
    finally:
        eng.shutdown()


# ------------------------------------- typed boundary + temperature


def test_speculation_unsupported_is_typed_and_construction_time(params,
                                                                cfg):
    """The capability boundary raises at engine CONSTRUCTION — never
    mid-decode with slots held."""
    # bad draft_layers: 0 and >= n_layers have no truncated model
    with pytest.raises(SpeculationUnsupported):
        InferenceEngine(params, cfg, _spec_cfg("self", draft_layers=0))
    with pytest.raises(SpeculationUnsupported):
        InferenceEngine(params, cfg, _spec_cfg(
            "self", draft_layers=cfg.n_layers))
    with pytest.raises(ValueError):
        InferenceEngine(params, cfg, EngineConfig(
            max_slots=2, speculate="medusa"))
    with pytest.raises(ValueError):
        InferenceEngine(params, cfg, _spec_cfg("ngram", speculate_k=0))


def test_temperature_rows_fall_back_transparently(params, cfg):
    """The decided temperature policy (documented on submit()): sampled
    rows ride the verify pass one token at a time — they never draft —
    while greedy neighbors in the SAME batch keep full parity.  No
    error, no silent parity break."""
    eng = InferenceEngine(params, cfg, _spec_cfg("ngram"))
    try:
        rep = [1, 2, 3, 4] * 6
        plain = [9, 8, 7, 6, 5]
        greedy1 = eng.submit(rep, max_new=8)
        sampled = eng.submit(plain, max_new=8, temperature=0.9, seed=3)
        greedy2 = eng.submit(list(reversed(rep)), max_new=8)
        assert greedy1.result(timeout=300) \
            == _ref_tokens(params, cfg, rep, 8)
        assert greedy2.result(timeout=300) \
            == _ref_tokens(params, cfg, list(reversed(rep)), 8)
        out = sampled.result(timeout=300)
        assert len(out) == 8
        assert sampled.spec_drafted == 0     # sampled rows never draft
        _assert_no_block_leak(eng.stats())
    finally:
        eng.shutdown()


# ------------------------------------------------- metrics + timeline


def test_spec_metrics_and_per_request_accounting(params, cfg):
    """stats()/metrics_snapshot expose accept-rate and per-row
    tokens-per-step; each request carries its own accept accounting."""
    tracing.clear()
    eng = InferenceEngine(params, cfg, _spec_cfg("ngram"))
    try:
        rep = [1, 2, 3, 4] * 6
        req = eng.submit(rep, max_new=8)
        assert req.result(timeout=300) == _ref_tokens(params, cfg, rep, 8)
        assert req.spec_drafted > 0
        assert req.spec_accepted > 0
        st = eng.stats()
        assert st["spec_accept_rate"] > 0.0
        assert st["tokens_per_step"] > 1.0
        series = {name: values for name, _, _, values in
                  metrics_snapshot()}
        assert "ray_tpu_inference_spec_accept_rate" in series
        assert "ray_tpu_inference_spec_accepted_tokens_total" in series
        assert "ray_tpu_inference_tokens_per_step" in series
        key = (("engine", eng.name),)
        assert series["ray_tpu_inference_spec_accept_rate"][key] > 0.0
        assert series["ray_tpu_inference_tokens_per_step"][key] > 1.0
    finally:
        eng.shutdown()
    # the request's tokens, as its lifecycle span has them
    (decode,) = tracing.get_finished_spans("request.decode")
    tracing.clear()
    assert decode["attributes"]["output_tokens"] == 8


def test_a_draft_and_verify_pass_is_of_kind_spec(params, cfg):
    """The account by kind of pass (``tracing.Account.pass_done``): a
    pass that drafted and verified is ``spec`` whatever else it ran, and
    weighs EVERY token it emitted to a row that already had one at the
    pass's whole time; a draft-dry pass is the plain step's kind."""
    eng = InferenceEngine(params, cfg, _spec_cfg("ngram"))
    try:
        rep = [1, 2, 3, 4] * 6
        out = eng.generate(rep, max_new=12, timeout=300)
        sampled = eng.generate([5, 6, 7], max_new=5, temperature=0.8,
                               seed=1, timeout=300)
    finally:
        eng.shutdown()
    st = eng.stats()
    acct = st["loop_account"]
    kinds = acct["by_kind"]
    assert kinds["spec"]["count"] == st["spec_passes"] > 0
    # several tokens a row and pass: fewer passes than tokens
    assert kinds["spec"]["tokens"] > kinds["spec"]["count"]
    assert sum(k["count"] for kind, k in kinds.items() if kind != "idle") \
        == acct["passes"]
    assert sum(k.get("tokens", 0) for k in kinds.values()) \
        == st["tokens_greedy_on_device"] + st["tokens_sampled"] \
        == len(out) + len(sampled) == 17
    assert sum(acct["gaps"].values()) == 17 - 2
    # the sampled request is never drafted: its passes are plain steps
    assert kinds["step"]["tokens"] + kinds["chunk+step"]["tokens"] >= 5
    assert sum(k["ns"] for k in kinds.values()) \
        == acct["t_ns"] - acct["t_made_ns"]


def test_timeline_renders_engine_request_slices():
    """engine_request flight-recorder events (engine._fr_note) become X
    slices on the engine's track with accept/reject counts in args."""
    from ray_tpu.util.timeline import build_trace
    trace = build_trace(ingress=[
        {"t": 20.5, "kind": "engine_request", "engine": "engine-0",
         "req": 3, "start_t": 20.0, "tokens": 8,
         "spec_accepted": 5, "spec_rejected": 2},
    ])
    sl = [e for e in trace["traceEvents"] if e.get("cat") == "engine"]
    assert len(sl) == 1 and sl[0]["ph"] == "X"
    assert sl[0]["pid"] == "engine" and sl[0]["tid"] == "engine-0"
    assert sl[0]["dur"] == pytest.approx(0.5e6)
    assert sl[0]["args"]["spec_accepted"] == 5
    assert sl[0]["args"]["spec_rejected"] == 2


def test_engine_emits_request_slice_to_flight_recorder(params, cfg):
    """With the flight recorder armed, every completed request lands an
    engine_request event carrying its speculation counts."""
    from ray_tpu.core import flight_recorder as fr

    rec = fr.enable()
    eng = InferenceEngine(params, cfg, _spec_cfg("ngram"))
    try:
        rep = [1, 2, 3, 4] * 6
        eng.generate(rep, max_new=6, timeout=300)
        evs = [e for e in rec.export_ingress()
               if e.get("kind") == "engine_request"]
        assert evs, "no engine_request event recorded"
        ev = evs[-1]
        assert ev["engine"] == eng.name
        assert ev["tokens"] == 6
        assert ev["spec_accepted"] >= 0 and ev["spec_rejected"] >= 0
        assert ev["spec_accepted"] + ev["spec_rejected"] > 0
        assert ev["t"] >= ev["start_t"]
    finally:
        eng.shutdown()
        fr.disable()
