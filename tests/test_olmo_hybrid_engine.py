"""The ``olmo_hybrid`` layout through the serving path at a tiny size on
the CPU: the two paged programs (chunked prefill, then one-token steps,
through ``BlockPool`` + ``StatePool``) and ``InferenceEngine`` held to
the plain reference's full forward pass at every emitted position; the
head-wise window attention against ``mha_reference``; what the engine
derives from a recurrent state."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import olmo_hybrid as ref
from ray_tpu.inference import EngineConfig, InferenceEngine
from ray_tpu.inference import decode, recurrent
from ray_tpu.inference.cache import BlockPool, PoolLayout
from ray_tpu.inference.decode import (SpeculationUnsupported, pack_chunk,
                                      pack_step, window_by_head)
from ray_tpu.models import hybrid
from ray_tpu.ops.attention import (KEY_BLOCK, head_window_attention,
                                   mha_reference)
from tests.test_olmo_hybrid_model import F32, PUB

# float32 against float32 (the tolerance of tests/test_olmo_hybrid_
# model.py, for the same reason: the order of the sums)
ATOL = 5e-5


@pytest.fixture(scope="module")
def cfg():
    return hybrid.HybridConfig.from_published(PUB, **F32)


@pytest.fixture(scope="module")
def params(cfg):
    return hybrid.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _ref(params, toks):
    return np.asarray(ref.logits(params, np.asarray(toks), PUB))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n)


# ------------------------------------------------------- the two programs

def test_programs_chunks_then_decode_equal_reference_logits(cfg, params):
    """Two rows of unequal length: each prefilled in chunks (the last
    one partial), then decoded TOGETHER in one step a token; a third row
    sits every pass out and its state stays zero."""
    bs, C, n_rows = 8, 16, 3
    pool = BlockPool(cfg, n_blocks=16, block_size=bs, max_seq=96,
                     state_rows=n_rows)
    T = pool.blocks_per_seq
    step = recurrent.make_recurrent_decode_step(cfg, block_size=bs,
                                                n_table=T)
    chunk = recurrent.make_recurrent_chunk_fn(cfg, chunk=C, block_size=bs,
                                              n_table=T)
    seqs = {0: _tokens(50, 8), 2: _tokens(44, 9)}
    prompts = {0: 37, 2: 21}
    want = {r: _ref(params, s) for r, s in seqs.items()}
    tables = np.zeros((n_rows, T), np.int32)
    tables[0, :7] = [3, 7, 2, 9, 11, 4, 13]
    tables[2, :6] = [5, 1, 8, 6, 10, 12]
    pools, state = pool.pools, (pool.state.conv, pool.state.ssm)
    feed = jnp.zeros(n_rows, jnp.int32)     # the rows' next tokens
    for row, n_prompt in prompts.items():
        for pos in range(0, n_prompt, C):
            n_q = min(C, n_prompt - pos)
            toks = np.zeros(C, np.int32)
            toks[:n_q] = seqs[row][pos:pos + n_q]
            logits, load, pools, state, feed = chunk(
                params, pools, state, feed,
                pack_chunk(tables[row], toks, pos, row, n_q))
            np.testing.assert_allclose(np.asarray(logits)[:n_q],
                                       want[row][pos:pos + n_q], atol=ATOL)
            assert load.tolist()[:hybrid.N_LOAD] == [0] * hybrid.N_LOAD
            assert int(load[hybrid.N_LOAD]) == int(
                np.asarray(logits)[n_q - 1].argmax())
    active = np.array([True, False, True])
    for t in range(7):
        tokens = np.zeros(n_rows, np.int32)
        positions = np.zeros(n_rows, np.int32)
        for row, n_prompt in prompts.items():
            tokens[row], positions[row] = (seqs[row][n_prompt + t],
                                           n_prompt + t)
        logits, load, pools, state, feed = step(
            params, pools, state, feed,
            pack_step(tables, tokens, positions, active))
        for row, n_prompt in prompts.items():
            np.testing.assert_allclose(np.asarray(logits)[row],
                                       want[row][n_prompt + t], atol=ATOL)
    conv, matrix = state
    assert matrix.shape == (3, n_rows, 8, 48)
    assert float(jnp.abs(matrix[:, 1]).max()) == 0.0
    assert float(jnp.abs(conv[:, 1]).max()) == 0.0
    assert float(jnp.abs(matrix[:, 0]).max()) > 0.0


# ------------------------------------- the head-wise window attention

@pytest.mark.parametrize("heads, kv_heads", [(4, 4), (6, 2), (3, 1)])
@pytest.mark.parametrize("start, w", [(0, 16), (40, 16), (23, 9)])
def test_head_window_attention_equals_mha_reference(heads, kv_heads, start,
                                                    w):
    """Queries at positions start .. start + w over a paged row, grouped
    and ungrouped, against the plain form on the gathered keys; what
    lies past the window's last query is NaN and must not be seen."""
    hd, bs, n_keys = 128, 8, 64
    ks = jax.random.split(jax.random.PRNGKey(start + heads), 3)
    q = jax.random.normal(ks[0], (heads, w, hd))
    k = jax.random.normal(ks[1], (n_keys, kv_heads * hd))
    v = jax.random.normal(ks[2], (n_keys, kv_heads * hd))
    pos = start + jnp.arange(w, dtype=jnp.int32)
    seen = jnp.arange(n_keys)[:, None] <= pos[-1]
    k_nan, v_nan = (jnp.where(seen, x, jnp.nan) for x in (k, v))

    def read_keys(j, n):
        return tuple(jax.lax.dynamic_slice_in_dim(x, j * n, n)
                     for x in (k_nan, v_nan))

    got = head_window_attention(q, read_keys, pos, n_kv_heads=kv_heads,
                                scale=hd ** -0.5, key_block=2 * bs)
    rep = heads // kv_heads

    def split(x):       # [keys, kv * hd] -> [1, heads, keys, hd]
        x = x.reshape(n_keys, kv_heads, hd).transpose(1, 0, 2)
        return jnp.repeat(x, rep, axis=0)[None]
    mask = (jnp.arange(n_keys)[None, :] <= pos[:, None])[None, None]
    want = mha_reference(q[None], split(k), split(v), causal=False,
                         mask=mask)[0]
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("heads, hd", [(30, 128), (8, 128), (2, 128),
                                       (25, 64), (8, 64)])
def test_which_window_form_a_layout_gets(heads, hd):
    """A table of more than one key block is walked head by head,
    whatever the heads (olmo's, granite's, nemotron's, XL's, lfm2's); a
    table of one key block is attended packed, and so is a sharded
    pool's."""
    def lay(shards=1, bs=16):
        return PoolLayout(1, 4, bs, heads, hd, shards)
    one = KEY_BLOCK // 16
    assert not window_by_head(lay(), one)
    assert window_by_head(lay(), one + 1)
    assert not window_by_head(lay(shards=2), 4 * one)
    # a cache block that does not divide KEY_BLOCK is a key block itself
    assert window_by_head(lay(bs=24), 2) and not window_by_head(lay(bs=24), 1)


def test_chunk_program_by_head_equals_the_packed_form(cfg, params,
                                                      monkeypatch):
    """The tiny model's 4 heads of 16 lanes take the packed form; forced
    onto the head-wise one (interpret mode takes any width) the chunk
    program gives the same logits."""
    bs, C = 8, 16
    pool = BlockPool(cfg, n_blocks=16, block_size=bs, max_seq=96,
                     state_rows=1)
    T = pool.blocks_per_seq
    seq = _tokens(40, 3)
    table = np.zeros(T, np.int32)
    table[:5] = [3, 7, 2, 9, 11]

    def run():
        chunk = recurrent.make_recurrent_chunk_fn(
            cfg, chunk=C, block_size=bs, n_table=T)
        pools = tuple(jnp.zeros_like(p) for p in pool.pools)
        state = (jnp.zeros_like(pool.state.conv),
                 jnp.zeros_like(pool.state.ssm))
        feed = jnp.zeros(pool.state.conv.shape[1], jnp.int32)
        outs = []
        for p in range(0, 40, C):
            n_q = min(C, 40 - p)
            toks = np.zeros(C, np.int32)
            toks[:n_q] = seq[p:p + n_q]
            logits, _, pools, state, feed = chunk(
                params, pools, state, feed,
                pack_chunk(table, toks, p, 0, n_q))
            outs.append(np.asarray(logits)[:n_q])
        return np.concatenate(outs)

    # (a built program is cached by its shapes: build both anew)
    monkeypatch.setattr(recurrent, "_cached",
                        lambda key, cfg, mesh, rules, build: build())
    packed = run()
    monkeypatch.setattr(decode, "window_by_head", lambda lay, n_table: True)
    np.testing.assert_allclose(run(), packed, atol=ATOL)
    np.testing.assert_allclose(packed, _ref(params, seq), atol=ATOL)


# ------------------------------------------------------------- the engine

def _margins(params, prompt, emitted):
    seq = np.asarray(list(prompt) + list(emitted))
    step = _ref(params, seq)[len(prompt) - 1:len(seq) - 1]
    return step.max(-1) - step[np.arange(len(emitted)), emitted]


def _engine(cfg, params, **kw):
    ec = dict(max_slots=3, max_seq=96, n_blocks=30, kv_block_size=8,
              prefill_chunk=16)
    return InferenceEngine(params, cfg, EngineConfig(**{**ec, **kw}))


def test_engine_derives_what_a_recurrent_state_forbids(cfg, params):
    eng = _engine(cfg, params)
    try:
        assert eng.trie is None                 # no radix index
        assert eng.pool.state is not None and eng.pool.v is not None
        # since ISSUE 58 the delta rule takes a window in two parts
        assert eng._step_chunk is not None and eng._prefill is None
        st = eng.stats()
        assert st["state_bytes"] == eng.pool.state.bytes_total() > 0
    finally:
        eng.shutdown()
    with pytest.raises(SpeculationUnsupported, match="recurrent state"):
        _engine(cfg, params, speculate="ngram")
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("tp",))
    with pytest.raises(ValueError, match="recurrent layers"):
        InferenceEngine(params, cfg, EngineConfig(max_slots=2, max_seq=96),
                        mesh=mesh)


def test_engine_rows_admitted_at_different_times(cfg, params):
    eng = _engine(cfg, params)
    rng = np.random.default_rng(0)
    plan = [(5, 6), (19, 10), (33, 3), (8, 12), (27, 7)]
    prompts = [rng.integers(0, 256, n).tolist() for n, _ in plan]
    reqs = []
    for p, (_, m) in zip(prompts, plan):
        reqs.append(eng.submit(p, max_new=m))
        time.sleep(0.05)
    outs = [r.result(timeout=300) for r in reqs]
    st = eng.stats()
    eng.shutdown()
    for p, o, (_, m) in zip(prompts, outs, plan):
        assert len(o) == m
        assert _margins(params, p, o).max() <= ATOL
    assert st["tokens_greedy_on_device"] == st["generated_tokens"]
    # every prompt token went through the window form, every decode
    # token but a request's first advanced one row's matrix state
    assert st["linear_chunk_tokens"] == st["prefill_tokens"] == sum(
        n for n, _ in plan)
    assert st["linear_state_rows_advanced"] == sum(m - 1 for _, m in plan)
    assert st["prefix_hit_tokens"] == 0 and st["state_rows_in_use"] == 0


@pytest.mark.parametrize("fused", [True, False])
def test_engine_chunks_ride_the_steps_of_a_long_answer(cfg, params, fused):
    """One long answer decodes while prompts of one to three chunks
    (partial last ones among them) wait for its rows, admitted as rows
    come free: the last chunk of every pass rides the step as ONE
    program, the rows' matrix state advanced in the pool and the chunk
    row's behind it, and every stream is still the reference's argmax;
    without the program (``_step_chunk = None``, the only switch) the
    same streams."""
    eng = _engine(cfg, params, max_slots=4, n_blocks=40)
    if not fused:
        eng._step_chunk = None
    rng = np.random.default_rng(5)
    plan = [(19, 6), (33, 4), (16, 5), (3, 7), (41, 3)]
    prompts = [rng.integers(0, 256, n).tolist() for n, _ in plan]
    long_ = rng.integers(0, 256, 6).tolist()
    try:
        first = eng.submit(long_, max_new=80)
        it = first.stream(timeout=300)
        head = [next(it) for _ in range(2)]           # it is decoding now
        reqs = [eng.submit(p, max_new=m) for p, (_, m) in zip(prompts, plan)]
        outs = [r.result(timeout=300) for r in reqs]
        assert not first.done                         # ... and still is
        whole = head + list(it)
        st = eng.stats()
    finally:
        eng.shutdown()
    assert len(whole) == 80
    assert _margins(params, long_, whole).max() <= ATOL
    for p, o, (_, m) in zip(prompts, outs, plan):
        assert len(o) == m
        assert _margins(params, p, o).max() <= ATOL
    chunks = sum(-(-n // 16) for n, _ in plan) + 1
    assert st["chunk_passes"] == chunks
    # every prompt but the first met a decoding row: its last chunk rode
    assert (st["chunks_in_step"] >= len(plan)) if fused \
        else st["chunks_in_step"] == 0
    assert st["linear_chunk_tokens"] == st["prefill_tokens"] == len(
        long_) + sum(n for n, _ in plan)
    assert st["linear_state_rows_advanced"] == 79 + sum(
        m - 1 for _, m in plan)
    assert st["tokens_greedy_on_device"] == st["generated_tokens"]


def test_engine_preempted_row_reprefills_to_the_same_logits(cfg, params):
    """A pool too small for three rows' growth: a row is preempted, its
    state is dropped, it is re-admitted and re-prefills prompt + what it
    had emitted from zero state; every answer is still the reference's
    argmax, token for token."""
    eng = _engine(cfg, params, n_blocks=12)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, n).tolist() for n in (20, 22, 18)]
    try:
        reqs = [eng.submit(p, max_new=24) for p in prompts]
        outs = [r.result(timeout=600) for r in reqs]
        st = eng.stats()
    finally:
        eng.shutdown()
    assert st["preemptions"] >= 1
    assert st["prefill_tokens"] > sum(map(len, prompts))   # re-prefilled
    for p, o in zip(prompts, outs):
        assert len(o) == 24
        assert _margins(params, p, o).max() <= ATOL


def test_other_families_count_no_linear_work():
    tiny = hybrid.HybridConfig.tiny()
    eng = InferenceEngine(hybrid.init_params(tiny, jax.random.PRNGKey(0)),
                          tiny, EngineConfig(max_slots=2, max_seq=64,
                                             kv_block_size=8,
                                             prefill_chunk=8))
    try:
        eng.submit(_tokens(12).tolist(), max_new=4).result(timeout=300)
        st = eng.stats()
    finally:
        eng.shutdown()
    assert st["linear_state_rows_advanced"] == 0
    assert st["linear_chunk_tokens"] == 0 and st["prefill_tokens"] == 12
