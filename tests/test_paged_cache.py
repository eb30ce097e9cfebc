"""Paged KV cache tests: BlockPool/RadixIndex edge cases, greedy token
parity under paging + prefix reuse + chunked prefill (the tentpole
acceptance oracle), copy-on-write on shared tails, LRU prefix eviction
under pressure, preemption, and donated-pool reallocation after a step
failure (the r10 recovery rule generalized to blocks).

Everything runs on CPU with GPTConfig.tiny at f32 (greedy argmax parity
must not hinge on bf16 ties)."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.inference import (BlockPool, EngineConfig, InferenceEngine,
                               RadixIndex)
from ray_tpu.models import gpt


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


# ------------------------------------------------------------- block pool

def test_block_pool_alloc_free_churn(cfg):
    """Alloc/free churn in adversarial orders never loses or double-
    hands a block (blocks are uniform — 'fragmentation' would show up
    as a pool that cannot re-reach full capacity)."""
    pool = BlockPool(cfg, n_blocks=8, block_size=8)
    rng = np.random.default_rng(3)
    held: list = []
    for _ in range(300):
        if held and (len(held) == 8 or rng.random() < 0.45):
            bid = held.pop(int(rng.integers(len(held))))
            pool.decref(bid)
        else:
            bid = pool.alloc()
            assert bid is not None and bid != 0      # never the scratch
            assert bid not in held                   # never double-handed
            held.append(bid)
        assert pool.n_free + len(held) == 8
    for bid in held:
        pool.decref(bid)
    assert pool.n_free == 8
    assert sorted(pool.alloc() for _ in range(8)) == list(range(1, 9))
    assert pool.alloc() is None                      # exhausted, not grown


def test_block_pool_refcount_and_cow_copy(cfg):
    pool = BlockPool(cfg, n_blocks=8, block_size=8)
    a = pool.alloc()
    pool.incref(a)
    assert pool.refcount(a) == 2
    assert pool.decref(a) == 1
    assert pool.decref(a) == 0
    with pytest.raises(ValueError):                  # double free
        pool.decref(a)
    with pytest.raises(ValueError):                  # never allocated
        pool.incref(5)
    # copy_block duplicates content (the CoW primitive)
    src, dst = pool.alloc(), pool.alloc()
    k, v = pool.read_blocks([src])
    pool.write_blocks_at([src], k + 1.5, v - 2.5)
    pool.copy_block(src, dst)
    k, v = pool.read_blocks([src, dst])
    np.testing.assert_array_equal(k, np.full_like(k, 1.5))
    np.testing.assert_array_equal(v, np.full_like(v, -2.5))


def test_block_pool_bounds(cfg):
    with pytest.raises(ValueError):                  # can't hold one seq
        BlockPool(cfg, n_blocks=2, block_size=8, max_seq=64)
    with pytest.raises(ValueError):                  # wider than wpe
        BlockPool(cfg, n_blocks=64, block_size=8, max_seq=cfg.max_seq + 1)


# ------------------------------------------------------------ radix index

def test_radix_match_insert_cap_and_eviction(cfg):
    pool = BlockPool(cfg, n_blocks=16, block_size=4)
    trie = RadixIndex(pool)
    seq = np.arange(10, 24, dtype=np.int32)          # 14 tokens: 3 full + 2
    blocks = [pool.alloc() for _ in range(4)]
    trie.insert(seq, blocks)
    assert trie.cached_blocks == 4
    for bid in blocks:                               # request releases; the
        pool.decref(bid)                             # trie keeps its refs
    assert pool.n_free == 12

    # the identical prompt adopts full blocks but NOT the tail leaf
    # (its whole content would leave no token to prefill)
    ids, n = trie.match(seq)
    assert n == 12 and len(ids) == 3
    for bid in ids:
        pool.decref(bid)
    # a prompt extending past the cached chain adopts everything
    longer = np.concatenate([seq, np.asarray([99, 98], np.int32)])
    ids, n = trie.match(longer)
    assert n == 14 and len(ids) == 4
    for bid in ids:
        pool.decref(bid)
    # diverging first block: no hit
    ids, n = trie.match(np.asarray([1, 2, 3, 4, 5, 6], np.int32))
    assert (ids, n) == ([], 0)

    # eviction frees unreferenced leaves first, LRU order, and never a
    # block some request still holds
    held_ids, _ = trie.match(longer)                 # reference the chain
    assert trie.evict(10) == 0                       # everything referenced
    for bid in held_ids:
        pool.decref(bid)
    assert trie.evict(2) == 2                        # leaves-up now
    assert trie.cached_blocks == 2
    assert trie.evict(10) == 2
    assert trie.cached_blocks == 0
    assert pool.n_free == 16


def test_radix_match_cap_exact_multiple(cfg):
    """A prompt that is exactly N cached full blocks must NOT adopt the
    last block whole — at least one token always prefills (its logits
    drive the first sampled token)."""
    pool = BlockPool(cfg, n_blocks=8, block_size=4, max_seq=32)
    trie = RadixIndex(pool)
    seq = np.arange(8, dtype=np.int32)               # exactly 2 full blocks
    blocks = [pool.alloc(), pool.alloc()]
    trie.insert(seq, blocks)
    ids, n = trie.match(seq)
    assert n == 4 and len(ids) == 1                  # only the first block
    for bid in ids:
        pool.decref(bid)


# ------------------------------------------------- engine: parity oracle

def test_paged_parity_prefix_reuse_and_chunked_prefill(params, cfg):
    """THE tentpole invariant (tier-1): greedy decode under paging,
    radix prefix reuse, and chunked prefill is token-identical to the
    full-recompute oracle — cold, warm (prefix hit), and with prompts
    long enough to prefill in multiple chunks across block boundaries."""
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=4, kv_block_size=8, prefill_chunk=16))
    try:
        rng = np.random.default_rng(7)
        head = rng.integers(0, cfg.vocab_size, 24).tolist()   # 3 blocks
        prompts = ([head + rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(2, 10))).tolist()
                    for _ in range(4)]
                   + [rng.integers(0, cfg.vocab_size, 40).tolist()])
        # wave 1: cold — multi-chunk prefill (40 > 16), block crossings
        for wave in ("cold", "warm"):
            reqs = [eng.submit(p, max_new=8) for p in prompts]
            for p, r in zip(prompts, reqs):
                assert r.result(timeout=300) == \
                    _ref_tokens(params, cfg, p, 8), (wave, p)
        st = eng.stats()
        # warm wave must have adopted shared heads from the radix index
        assert st["prefix_hit_tokens"] > 0
        assert st["prefix_hit_rate"] > 0.0
        assert st["prefix_cached_blocks"] > 0
    finally:
        eng.shutdown()


def test_paged_parity_under_preemption(params, cfg):
    """Block pressure preempts the youngest request (blocks donated to
    the prefix index, request requeued with emitted tokens folded into
    its prompt) — and every stream still matches the oracle exactly."""
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=4, max_seq=32, kv_block_size=8, n_blocks=6,
        prefill_chunk=16))
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
        assert st["blocks_free"] + st["prefix_cached_blocks"] \
            == st["blocks_total"]
    finally:
        eng.shutdown()


def test_cow_on_shared_tail_block(params, cfg):
    """A later request adopting a cached PARTIAL tail block must
    copy-on-write before extending it: its own continuation diverges,
    and the original cached prefix must stay intact for a third request
    re-matching the original prompt."""
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=2, kv_block_size=8, prefill_chunk=16))
    try:
        a = [5, 9, 13, 2, 7, 11, 3, 8, 1, 6]        # 10 tokens: 1 full + 2
        ra = eng.generate(a, max_new=4, timeout=300)
        assert ra == _ref_tokens(params, cfg, a, 4)
        st0 = eng.stats()
        assert st0["prefix_cached_blocks"] >= 2      # full + partial tail
        # B shares the whole of A's prompt, then diverges: it adopts the
        # partial tail and EXTENDS it (CoW) — token-exact regardless
        b = a + [17, 23, 29, 31]
        rb = eng.generate(b, max_new=4, timeout=300)
        assert rb == _ref_tokens(params, cfg, b, 4)
        st1 = eng.stats()
        assert st1["prefix_hit_tokens"] > st0["prefix_hit_tokens"]
        # C re-runs A's prompt: the ORIGINAL cached tail must be
        # uncorrupted by B's extension (the CoW guarantee)
        rc = eng.generate(a, max_new=4, timeout=300)
        assert rc == ra
    finally:
        eng.shutdown()


def test_prefix_eviction_under_pressure(params, cfg):
    """Filling the trie with distinct prompts forces LRU eviction of
    unreferenced cached prefixes when new admissions need blocks — the
    pool never wedges and parity holds for the evicting request."""
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=2, kv_block_size=8, n_blocks=8, prefill_chunk=16))
    try:
        rng = np.random.default_rng(5)
        for i in range(5):                 # each run caches ~2-3 blocks
            p = rng.integers(0, cfg.vocab_size, 18).tolist()
            assert eng.generate(p, max_new=4, timeout=300) \
                == _ref_tokens(params, cfg, p, 4)
        st = eng.stats()
        assert eng.trie.evicted_blocks > 0, \
            "5 x 22-token sequences through 8 blocks never evicted"
        assert st["prefix_cached_blocks"] <= st["blocks_total"]
    finally:
        eng.shutdown()


def test_cancellation_releases_block_refcounts(params, cfg):
    """Cancelling a request (queued or mid-decode) drops every block
    reference it held; shared blocks survive exactly while the prefix
    index or a sibling request still references them."""
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=2, kv_block_size=8, prefix_cache=False))
    try:
        ra = eng.submit(list(range(1, 11)), max_new=40)
        rb = eng.submit(list(range(2, 12)), max_new=40)   # may queue
        deadline = time.time() + 60
        while time.time() < deadline and eng.stats()["active_slots"] < 1:
            time.sleep(0.005)
        ra.cancel()
        rb.cancel()
        ra.result(timeout=60)
        rb.result(timeout=60)
        deadline = time.time() + 30
        while time.time() < deadline:
            st = eng.stats()
            if st["blocks_free"] == st["blocks_total"] \
                    and st["active_slots"] == 0:
                break
            time.sleep(0.005)
        st = eng.stats()
        # prefix_cache=False: cancellation must return EVERY block
        assert st["blocks_free"] == st["blocks_total"]
        assert st["active_slots"] == 0
        # pool is fully reusable afterwards
        out = eng.generate([7, 8, 9], max_new=4, timeout=300)
        assert out == _ref_tokens(params, cfg, [7, 8, 9], 4)
    finally:
        eng.shutdown()


def test_step_failure_recovers_donated_pool_and_clears_prefix(params, cfg):
    """The r10 donated-cache recovery rule generalized to blocks: a
    decode-step failure fails the in-flight requests, REALLOCATES the
    donated pool, and CLEARS the prefix index (cached prefixes would
    otherwise point at zeroed blocks — silently wrong KV on the next
    hit).  The engine keeps serving with oracle parity, including for
    the previously-cached prompt."""
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=2, kv_block_size=8, prefill_chunk=16))
    try:
        warm = [4, 8, 15, 16, 23, 42, 10, 11, 12]
        assert eng.generate(warm, max_new=4, timeout=300) \
            == _ref_tokens(params, cfg, warm, 4)
        assert eng.stats()["prefix_cached_blocks"] > 0

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
        st = eng.stats()
        assert st["prefix_cached_blocks"] == 0       # index cleared
        assert st["blocks_free"] == st["blocks_total"]
        # the previously-cached prompt must be RE-COMPUTED correctly (a
        # stale trie would have served zeroed KV here)
        assert eng.generate(warm, max_new=4, timeout=300) \
            == _ref_tokens(params, cfg, warm, 4)
        assert eng.generate([3, 4], max_new=4, timeout=300) \
            == _ref_tokens(params, cfg, [3, 4], 4)
    finally:
        eng.shutdown()


def test_chaos_block_alloc_failure_recovers(params, cfg):
    """The registered _fi gate (infer_block_alloc): a scripted pool
    failure at decode-time block growth takes the recovery path and the
    engine keeps serving."""
    from ray_tpu.core import fault_injection as fi

    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=2, kv_block_size=4, prefill_chunk=16))
    plan = fi.FaultPlan()

    def raiser(ctx):
        raise RuntimeError("injected block-alloc failure")

    plan.add(fi.Rule("infer_block_alloc", "script", fn=raiser, nth=2))
    fi.install(plan)
    try:
        bad = eng.submit([1, 2, 3, 4, 5], max_new=12)   # crosses blocks
        with pytest.raises(RuntimeError, match="injected block-alloc"):
            bad.result(timeout=60)
        assert any(p == "infer_block_alloc" for p, _, _ in plan.log)
    finally:
        fi.uninstall()
    try:
        out = eng.generate([6, 7, 8], max_new=4, timeout=300)
        assert out == _ref_tokens(params, cfg, [6, 7, 8], 4)
    finally:
        eng.shutdown()


# ----------------------------------------------- block-budget admission

def test_block_budget_concurrency_beats_slot_count(params, cfg):
    """The memory-sharing win: at EQUAL pool tokens, block-granular
    admission runs more concurrent short requests than one worst-case
    ``max_seq`` stripe a request would allow (the mixed-length
    acceptance claim in miniature)."""
    # pool = 2 x max_seq(64) tokens: 2 whole stripes, but 4 rows of
    # short requests over the same 128 tokens (16 blocks of 8)
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=4, kv_block_size=8, n_blocks=16, prefill_chunk=16))
    try:
        reqs = [eng.submit([i + 1, i + 2, i + 3], max_new=24)
                for i in range(4)]
        for i, r in enumerate(reqs):
            assert r.result(timeout=300) == _ref_tokens(
                params, cfg, [i + 1, i + 2, i + 3], 24)
        assert eng.stats()["peak_active_requests"] > 2
    finally:
        eng.shutdown()


# -------------------------------------------------------------- MoE decode

def test_moe_paged_decode_parity():
    """The MoE wall is down: a paged engine over an MoE config
    constructs and its greedy tokens match the training-forward oracle
    (gpt.generate runs the same expert dispatch).  capacity_factor=4.0
    = n_experts/top_k·2, so expert capacity never binds — the regime
    where incremental windows and the full-sequence oracle route
    identically (see gpt._transformer_layer)."""
    moe_cfg = gpt.GPTConfig.tiny_moe(capacity_factor=4.0)
    moe_params = gpt.init_params(moe_cfg, jax.random.PRNGKey(0))
    eng = InferenceEngine(moe_params, moe_cfg, EngineConfig(
        max_slots=2, kv_block_size=8, prefill_chunk=16))
    try:
        prompt = [3, 1, 4, 1, 5, 9, 2, 6]
        got = eng.generate(prompt, max_new=8, timeout=300)
        assert got == _ref_tokens(moe_params, moe_cfg, prompt, 8)
    finally:
        eng.shutdown()


# -------------------------------------------------------------- metrics

def test_paged_metrics_series(params, cfg):
    """The new capacity gauges render and carry real values."""
    from ray_tpu import inference
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=2, kv_block_size=8))
    try:
        p = [9, 8, 7, 6, 5, 4, 3, 2, 1]
        eng.generate(p, max_new=4, timeout=300)
        eng.generate(p, max_new=4, timeout=300)      # prefix hit
        snap = inference.metrics_snapshot()
        names = {t[0] for t in snap}
        assert {"ray_tpu_inference_block_utilization_ratio",
                "ray_tpu_inference_prefix_hit_rate",
                "ray_tpu_inference_prefix_cached_blocks",
                "ray_tpu_inference_preemptions_total"} <= names
        by_name = {t[0]: t[3] for t in snap}
        key = ((("engine", eng.name),)
               + tuple(sorted(eng.labels.items())))
        assert by_name["ray_tpu_inference_prefix_hit_rate"][key] > 0.0
        assert by_name["ray_tpu_inference_prefix_cached_blocks"][key] > 0
    finally:
        eng.shutdown()
