"""Inference-engine tests: KV-cache decode parity against the
full-recompute oracle, continuous-batching admission/eviction semantics,
and metrics well-formedness (the block pool's own bounds:
tests/test_paged_cache.py).

Everything runs on CPU with GPTConfig.tiny (f32 activations so greedy
argmax parity is not at the mercy of bf16 ties)."""

import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.inference import EngineConfig, InferenceEngine
from ray_tpu.models import gpt


@pytest.fixture(scope="module")
def cfg():
    return gpt.GPTConfig.tiny(dtype=jnp.float32, max_seq=64)


@pytest.fixture(scope="module")
def params(cfg):
    return gpt.init_params(cfg, jax.random.PRNGKey(0))


def _ref_tokens(params, cfg, prompt, max_new):
    """Greedy full-recompute oracle (models/gpt.generate)."""
    out = gpt.generate(params, cfg, jnp.asarray([prompt], jnp.int32),
                       max_new=max_new, temperature=0.0)
    return np.asarray(out)[0, len(prompt):].tolist()


@pytest.fixture
def engine(params, cfg):
    eng = InferenceEngine(params, cfg, EngineConfig(max_slots=2))
    yield eng
    eng.shutdown()


# ------------------------------------------------------------------ parity

def test_greedy_kv_cache_parity_vs_full_recompute(engine, params, cfg):
    """The tentpole invariant: greedy KV-cache decode is token-identical
    to the full-recompute generate() oracle."""
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6, 5, 3, 5, 8, 9, 7], [42]]
    for prompt in prompts:
        got = engine.generate(prompt, max_new=10, timeout=120)
        assert got == _ref_tokens(params, cfg, prompt, 10)


def test_prefill_logits_match_forward(params, cfg):
    """Right-padded prefill must produce the same next-token logits as
    an unpadded forward (causality makes the padding invisible)."""
    from ray_tpu.inference.decode import make_prefill_fn
    prefill = make_prefill_fn(cfg)
    prompt = np.array([3, 1, 4, 1, 5, 9, 2, 6], np.int32)
    n, S = len(prompt), 32
    padded = np.zeros((1, S), np.int32)
    padded[0, :n] = prompt
    logits, k, v = prefill(params, padded)
    ref = gpt.forward(params, jnp.asarray(prompt)[None], cfg)
    np.testing.assert_allclose(np.asarray(logits)[0, n - 1],
                               np.asarray(ref)[0, -1], atol=1e-4)
    assert k.shape == (cfg.n_layers, 1, cfg.n_heads, S, cfg.head_dim)


def test_attention_kv_lengths_masks_per_row():
    """ops/attention kv_lengths == explicit per-row mask."""
    from ray_tpu.ops.attention import mha_reference
    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(rng, (3, 2, 1, 8))
    k = jax.random.normal(jax.random.PRNGKey(1), (3, 2, 6, 8))
    v = jax.random.normal(jax.random.PRNGKey(2), (3, 2, 6, 8))
    lengths = jnp.array([1, 3, 6])
    got = mha_reference(q, k, v, causal=False, kv_lengths=lengths)
    mask = (jnp.arange(6)[None, :] < lengths[:, None])[:, None, None, :]
    ref = mha_reference(q, k, v, causal=False,
                        mask=jnp.broadcast_to(mask, (3, 2, 1, 6)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-6)


def test_sample_token_shared_head():
    logits = jnp.asarray([[0.1, 3.0, -1.0], [2.0, 0.0, 1.0]])
    assert gpt.sample_token(logits, temperature=0.0).tolist() == [1, 0]
    tok = gpt.sample_token(logits[0], temperature=1.0,
                           rng=jax.random.PRNGKey(0))
    assert 0 <= int(tok) < 3
    with pytest.raises(ValueError):
        gpt.sample_token(logits, temperature=0.5)   # rng required


# --------------------------------------------------- continuous batching

def test_admission_mid_decode_isolated(engine, params, cfg):
    """Request B joins while A decodes; both finish with oracle-exact
    tokens — B's admission must not perturb A's cache rows and vice
    versa (slot masking)."""
    pa, pb = [3, 1, 4, 1, 5], [9, 2, 6, 5, 3, 5, 8]
    ra = engine.submit(pa, max_new=24)
    stream = ra.stream(timeout=120)
    first = [next(stream) for _ in range(4)]      # A is mid-decode...
    rb = engine.submit(pb, max_new=6)             # ...when B is admitted
    assert ra.result(timeout=120) == _ref_tokens(params, cfg, pa, 24)
    assert rb.result(timeout=120) == _ref_tokens(params, cfg, pb, 6)
    assert first == _ref_tokens(params, cfg, pa, 24)[:4]


def test_slot_exhaustion_queues(params, cfg):
    """With one slot, a second request parks in the admission queue (no
    memory growth) and runs after the first evicts."""
    eng = InferenceEngine(params, cfg, EngineConfig(max_slots=1))
    try:
        ra = engine_a = eng.submit([1, 2, 3], max_new=40)
        rb = eng.submit([4, 5, 6], max_new=5)
        saw_waiting = False
        deadline = time.time() + 60
        while time.time() < deadline:
            st = eng.stats()
            if st["waiting_requests"] >= 1 and st["active_slots"] == 1:
                saw_waiting = True
                break
            if rb.done:
                break
            time.sleep(0.002)
        assert saw_waiting, "second request never observed queued"
        assert ra.result(timeout=120) == _ref_tokens(params, cfg,
                                                     [1, 2, 3], 40)
        assert rb.result(timeout=120) == _ref_tokens(params, cfg,
                                                     [4, 5, 6], 5)
        assert eng.stats()["free_slots"] == 1
    finally:
        eng.shutdown()


def test_eos_eviction_frees_slot(params, cfg):
    ref = _ref_tokens(params, cfg, [7, 8, 9], 8)
    eng = InferenceEngine(params, cfg,
                          EngineConfig(max_slots=2, eos_token=ref[0]))
    try:
        out = eng.generate([7, 8, 9], max_new=8, timeout=120)
        assert out == [ref[0]]            # stopped at EOS, not max_new
        st = eng.stats()
        assert st["active_slots"] == 0 and st["free_slots"] == 2
        # the freed slot is immediately reusable
        out2 = eng.generate([7, 8, 9], max_new=8, timeout=120)
        assert out2 == [ref[0]]
    finally:
        eng.shutdown()


def test_max_tokens_eviction_and_slot_reuse(engine, params, cfg):
    """More requests than slots, all complete (slots recycle)."""
    prompts = [[i + 1, i + 2] for i in range(5)]
    reqs = [engine.submit(p, max_new=4) for p in prompts]
    for p, r in zip(prompts, reqs):
        assert r.result(timeout=120) == _ref_tokens(params, cfg, p, 4)
    st = engine.stats()
    assert st["requests_completed"] >= 5
    assert st["free_slots"] == st["max_slots"]


def test_temperature_sampling_in_range(engine, cfg):
    out = engine.generate([1, 2, 3], max_new=12, temperature=1.0, seed=7,
                          timeout=120)
    assert len(out) == 12
    assert all(0 <= t < cfg.vocab_size for t in out)


def test_submit_validation(engine, cfg):
    with pytest.raises(ValueError):
        engine.submit([], max_new=4)
    with pytest.raises(ValueError):
        engine.submit([1, 2], max_new=0)
    with pytest.raises(ValueError):
        engine.submit([cfg.vocab_size + 5], max_new=4)
    with pytest.raises(ValueError):                 # overflows the cache
        engine.submit([1] * 60, max_new=60)


def test_shutdown_fails_pending(params, cfg):
    eng = InferenceEngine(params, cfg, EngineConfig(max_slots=1))
    r = eng.submit([1, 2, 3], max_new=50)
    eng.shutdown()
    with pytest.raises(RuntimeError):
        r.result(timeout=10)            # failed, not silently dropped
    with pytest.raises(RuntimeError):
        eng.submit([4], max_new=2)


def test_cancel_waiting_and_active_frees_slots(params, cfg):
    """cancel() drops a queued request before admission and evicts an
    active one at the next iteration — abandoned work never holds a slot
    against live requests."""
    eng = InferenceEngine(params, cfg, EngineConfig(max_slots=1))
    try:
        ra = eng.submit([1, 2, 3], max_new=40)
        rb = eng.submit([4, 5, 6], max_new=40)   # parked: no free slot
        rb.cancel()
        ra.cancel()
        ra.result(timeout=60)
        rb.result(timeout=60)
        assert ra.done and rb.done
        deadline = time.time() + 30
        while eng.stats()["free_slots"] != 1 and time.time() < deadline:
            time.sleep(0.005)
        assert eng.stats()["free_slots"] == 1
        # live work proceeds on the freed slot
        out = eng.generate([7, 8], max_new=3, timeout=120)
        assert out == _ref_tokens(params, cfg, [7, 8], 3)
    finally:
        eng.shutdown()


def test_admit_failure_isolated_no_slot_leak(params, cfg):
    """A failure of the full-width prefill (a cold prompt longer than
    half the cache width, on an idle engine) fails the request with the
    error it raised, hands every row and block back, and the engine
    keeps serving."""
    eng = InferenceEngine(params, cfg, EngineConfig(max_slots=2))
    try:
        real_prefill = eng._prefill
        boom = {"armed": True}

        def failing_prefill(params_, tokens):
            if boom.pop("armed", False):
                raise RuntimeError("injected prefill failure")
            return real_prefill(params_, tokens)

        eng._prefill = failing_prefill
        cold = list(range(1, cfg.max_seq // 2 + 9))
        bad = eng.submit(cold, max_new=4)
        with pytest.raises(RuntimeError, match="injected"):
            bad.result(timeout=60)
        assert bad.full_width_prefill and not boom
        st = eng.stats()
        assert st["free_slots"] == 2                # the row came back
        assert st["blocks_free"] == st["blocks_total"]
        out = eng.generate([3, 4], max_new=4, timeout=120)
        assert out == _ref_tokens(params, cfg, [3, 4], 4)
        # and the full-width path itself still works
        out = eng.generate(cold, max_new=4, timeout=120)
        assert out == _ref_tokens(params, cfg, cold, 4)
    finally:
        eng.shutdown()


def test_cancelled_waiters_reaped_while_pool_full(params, cfg):
    """Cancelled queued requests are reaped even when no slot is free —
    zombies must not consume max_waiting backpressure."""
    eng = InferenceEngine(params, cfg, EngineConfig(max_slots=1))
    try:
        ra = eng.submit([1, 2, 3], max_new=50)     # holds the only slot
        zombies = [eng.submit([4, 5], max_new=50) for _ in range(3)]
        for z in zombies:
            z.cancel()
        deadline = time.time() + 30
        while time.time() < deadline:
            st = eng.stats()
            if st["waiting_requests"] == 0 and st["active_slots"] == 1:
                break
            time.sleep(0.005)
        st = eng.stats()
        assert st["waiting_requests"] == 0 and st["active_slots"] == 1
        for z in zombies:
            z.result(timeout=30)                   # finished, not hung
        ra.cancel()
    finally:
        eng.shutdown()


def test_step_failure_fails_inflight_and_recovers(params, cfg):
    """A decode-step failure fails the in-flight requests AND reallocates
    the (donated) cache arrays so the engine keeps serving."""
    eng = InferenceEngine(params, cfg, EngineConfig(max_slots=2))
    try:
        real_step = eng._step
        boom = {"armed": True}

        def failing_step(*a):
            if boom.pop("armed", False):
                raise RuntimeError("injected step failure")
            return real_step(*a)

        eng._step = failing_step
        bad = eng.submit([1, 2], max_new=8)
        with pytest.raises(RuntimeError, match="injected"):
            bad.result(timeout=60)
        out = eng.generate([3, 4], max_new=4, timeout=120)
        assert out == _ref_tokens(params, cfg, [3, 4], 4)
        assert eng.stats()["free_slots"] == 2
    finally:
        eng.shutdown()


def test_result_timeout_zero_raises(engine):
    r = engine.submit([1, 2, 3], max_new=30)
    with pytest.raises(TimeoutError):
        r.result(timeout=0)
    r.cancel()


def test_abandoned_engine_is_collectable(params, cfg):
    """Dropping every reference without shutdown() must let the engine
    (KV pool + loop thread) die: the loop thread only holds it weakly
    between passes."""
    import gc
    import weakref as _weakref
    eng = InferenceEngine(params, cfg, EngineConfig(max_slots=1))
    eng.generate([1, 2], max_new=2, timeout=120)
    thread = eng._thread
    ref = _weakref.ref(eng)
    del eng
    deadline = time.time() + 30
    while ref() is not None and time.time() < deadline:
        gc.collect()
        time.sleep(0.01)
    assert ref() is None, "engine leaked after last reference dropped"
    thread.join(timeout=30)
    assert not thread.is_alive()


def test_replica_teardown_stops_engine(params, cfg):
    """Scaling a serve replica away must shut its engine down (thread +
    KV pool released), via the _InProcReplica.close → teardown hook."""
    from ray_tpu import serve as serve_mod
    from ray_tpu.inference import build_gpt_deployment

    dep = build_gpt_deployment(cfg=cfg, engine_cfg=EngineConfig(max_slots=2),
                               seed=0, params=params)
    try:
        h = serve_mod.run(dep, use_actors=False)
        from ray_tpu.inference.engine import _ENGINES
        names = [n for n, e in _ENGINES.items() if not e._stopped]
        assert names, "replica engine not registered"
        serve_mod.status()   # deployment is live
        serve_mod.get_handle("v1")._state.scale_to(0)
        assert all(_ENGINES[n]._stopped for n in names
                   if n in _ENGINES)
    finally:
        serve_mod.shutdown()


# ----------------------------------------------------------------- metrics

# one Prometheus exposition sample: name{labels} value
_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})? '
    r'[-+]?((\d+(\.\d+)?([eE][-+]?\d+)?)|Inf|NaN)$')


def test_engine_metrics_wellformed(engine):
    """Per-engine gauges render as valid Prometheus exposition — the
    inference-side companion of the flight-recorder histogram test."""
    from ray_tpu import inference
    from ray_tpu.metrics import render_prometheus
    engine.generate([1, 2, 3], max_new=6, timeout=120)
    snap = inference.metrics_snapshot()
    names = {t[0] for t in snap}
    assert {"ray_tpu_inference_active_slots",
            "ray_tpu_inference_waiting_requests",
            "ray_tpu_inference_batch_occupancy_ratio",
            "ray_tpu_inference_generated_tokens_total",
            "ray_tpu_inference_requests_completed_total"} <= names
    text = render_prometheus(snap)
    help_seen, type_seen, samples = set(), set(), 0
    for line in text.strip().splitlines():
        if line.startswith("# HELP "):
            help_seen.add(line.split()[2])
        elif line.startswith("# TYPE "):
            name, kind = line.split()[2:4]
            type_seen.add(name)
            assert kind in ("gauge", "counter", "histogram")
        else:
            assert _SAMPLE_RE.match(line), f"malformed sample: {line!r}"
            samples += 1
    assert help_seen == type_seen == names
    assert samples >= len(names)
    # this engine's series carries its label and real counts
    assert f'engine="{engine.name}"' in text
    st = engine.stats()
    assert st["generated_tokens"] >= 6
    assert 0.0 <= st["batch_occupancy"] <= 1.0
