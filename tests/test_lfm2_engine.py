"""The ``lfm2_moe`` layout through the serving path at a tiny size on the
CPU: the paged programs (chunked prefill, one-token steps, the two as
one) through the K/V pools and the convolution state, the state
SNAPSHOT each full block carries, and ``InferenceEngine`` adopting
cached prefixes ACROSS the recurrent state — held to the plain
reference's full forward pass (no cache, no state) — with what the
engine derives for the layouts whose state has no snapshot form."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import lfm2 as ref
from ray_tpu.inference import (EngineConfig, InferenceEngine, decode,
                               recurrent)
from ray_tpu.inference.cache import BlockPool, RadixIndex
from ray_tpu.inference.decode import pack_chunk, pack_step, pack_step_chunk
from ray_tpu.models import hybrid
from tests.test_lfm2_model import F32, HELD, PUB, seeded

# float32 against float32 (tests/test_lfm2_model.py's tolerance, for the
# same reason: the order of the sums; bfloat16 products read 3e-2)
ATOL = 5e-5
BS, C = 8, 16


@pytest.fixture(scope="module")
def cfg():
    return hybrid.HybridConfig.from_published(PUB, **F32)


@pytest.fixture(scope="module")
def params(cfg):
    return seeded(cfg)


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(params=["one_key_block", "walked"])
def walked(request, monkeypatch):
    """The two forms a chunk's queries are attended in
    (``decode.window_by_head``): packed over the gathered table where it
    is one key block, as these tables of 96-160 keys are — or, with the
    key block shrunk to 32 keys, the walk over the key blocks the chunk
    can see.  (The programs are cached by their shapes: built anew.)"""
    if request.param == "walked":
        monkeypatch.setattr(decode, "KEY_BLOCK", 32)
        monkeypatch.setattr(importlib.import_module("ray_tpu.ops.attention"),
                            "HEAD_TILE", (16, 8))
    decode.clear_fn_cache()
    yield request.param == "walked"
    decode.clear_fn_cache()


def _ref(params, toks):
    return np.asarray(ref.logits(params, np.asarray(toks), PUB, HELD))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n)


def _margins(params, prompt, emitted):
    seq = np.asarray(list(prompt) + list(emitted))
    step = _ref(params, seq)[len(prompt) - 1:len(seq) - 1]
    return step.max(-1) - step[np.arange(len(emitted)), emitted]


def _engine(cfg, params, **kw):
    ec = dict(max_slots=3, max_seq=160, kv_block_size=BS, prefill_chunk=C,
              n_blocks=60)
    return InferenceEngine(params, cfg, EngineConfig(**{**ec, **kw}))


def _serve(eng, prompt, n=10):
    req = eng.submit(list(prompt), max_new=n)
    return req, req.result(timeout=600)


# --------------------------------------------------------- the programs

def _state_after(cfg, params, toks):
    """Every conv layer's state after ``toks``, by the mixer's own
    full-sequence form: what a snapshot at that boundary must hold."""
    out = []
    hybrid.run_layers(
        cfg, params, hybrid.embed(cfg, params, jnp.asarray(toks)[None]),
        jnp.array([len(toks)]), state_in=lambda mi: hybrid.zero_state(cfg, 1),
        state_out=lambda mi, s: out.append(s[0][0]),
        attend_for=lambda ai: hybrid.causal_attend(cfg),
        positions=jnp.arange(len(toks))[None])
    return jnp.stack(out)                       # [L, K-1, d]


@pytest.mark.parametrize("fused", [False, True])
def test_programs_chunks_then_decode_and_their_snapshots(cfg, params,
                                                         fused, walked):
    """Two rows prefilled in chunks (the last partial, one prompt ending
    ON a block boundary), then decoded together while a third sits out:
    logits are the reference's, and every block a program CLOSED carries
    the state after its last token — the chunk's from its own window,
    the step's when a row's token is its block's last.  ``fused``: the
    second row's last chunk rides the first row's decode step."""
    n_rows = 3
    pool = BlockPool(cfg, n_blocks=24, block_size=BS, max_seq=96,
                     state_rows=n_rows)
    assert decode.window_by_head(pool.layout, pool.blocks_per_seq) == walked
    assert pool.snapshots and pool.state.ssm is None
    assert pool.state.snap.shape == (25, 5 * 2 * 64)
    assert [a.shape for a in pool.state.arrays] == [(5, 3, 2, 64),
                                                    (25, 640)]
    T = pool.blocks_per_seq
    kw = dict(block_size=BS, n_table=T)
    step = recurrent.make_recurrent_decode_step(cfg, **kw)
    chunk = recurrent.make_recurrent_chunk_fn(cfg, chunk=C, **kw)
    both = recurrent.make_recurrent_step_chunk(cfg, chunk=C, **kw)
    assert recurrent.has_step_chunk(cfg)
    seqs = {0: _tokens(60, 8), 2: _tokens(52, 9)}
    prompts = {0: 40, 2: 37}
    want = {r: _ref(params, s) for r, s in seqs.items()}
    tables = np.zeros((n_rows, T), np.int32)
    tables[0, :8] = [3, 7, 2, 9, 11, 4, 13, 15]
    tables[2, :7] = [5, 1, 8, 6, 10, 12, 14]
    pools, state = pool.pools, pool.state.arrays
    feed = jnp.zeros(n_rows, jnp.int32)     # the rows' next tokens
    tokens = np.zeros(n_rows, np.int32)
    positions = np.zeros(n_rows, np.int32)
    active = np.zeros(n_rows, bool)

    def run_chunk(row, pos, ride=False):
        nonlocal pools, state, feed
        n_q = min(C, prompts[row] - pos)
        toks = np.zeros(C, np.int32)
        toks[:n_q] = seqs[row][pos:pos + n_q]
        packed = pack_chunk(tables[row], toks, pos, row, n_q)
        if not ride:
            logits, _, pools, state, feed = chunk(params, pools, state,
                                                  feed, packed)
            np.testing.assert_allclose(np.asarray(logits)[:n_q],
                                       want[row][pos:pos + n_q], atol=ATOL)
            return
        logits, _, pools, state, feed = both(
            params, pools, state, feed, pack_step_chunk(
                pack_step(tables, tokens, positions, active), packed))
        np.testing.assert_allclose(np.asarray(logits)[n_rows],
                                   want[row][pos + n_q - 1], atol=ATOL)
        return np.asarray(logits)

    for pos in range(0, 40, C):
        run_chunk(0, pos)
    tokens[0], positions[0], active[0] = seqs[0][40], 40, True
    for pos in range(0, 37, C):
        last = pos + C >= 37
        logits = run_chunk(2, pos, ride=fused and last)
        if fused and last:              # row 0 stepped inside that pass
            np.testing.assert_allclose(logits[0], want[0][40], atol=ATOL)
            tokens[0], positions[0] = seqs[0][41], 41
    tokens[2], positions[2], active[2] = seqs[2][37], 37, True
    for _ in range(12):
        logits, _, pools, state, feed = step(
            params, pools, state, feed,
            pack_step(tables, tokens, positions, active))
        for r in (0, 2):
            np.testing.assert_allclose(np.asarray(logits)[r],
                                       want[r][positions[r]], atol=ATOL)
            positions[r] += 1
            tokens[r] = seqs[r][positions[r]]
    conv, snap = state
    # row 0 wrote 40 + 12 (+ 1 fused) tokens, row 2 37 + 12: every FULL
    # block's snapshot is the state after its last token
    for r, done in ((0, int(positions[0])), (2, int(positions[2]))):
        assert done // BS >= 6
        for b in range(done // BS):
            np.testing.assert_allclose(
                snap[tables[r, b]].reshape(5, 2, 64),
                _state_after(cfg, params, seqs[r][:(b + 1) * BS]),
                atol=1e-5)
        np.testing.assert_allclose(
            conv[:, r], _state_after(cfg, params, seqs[r][:done]), atol=1e-5)
    # a block nobody closed has none; the idle row's state is untouched
    assert float(jnp.abs(snap[20]).max()) == 0.0
    assert float(jnp.abs(conv[:, 1]).max()) == 0.0


def test_boundaries_of_a_chunk_window():
    table = jnp.arange(100, 112)
    marks, ids, real = recurrent._boundaries(table, jnp.int32(16),
                                             jnp.int32(16), C, BS)
    assert marks.tolist() == [8, 16] and ids.tolist() == [102, 103]
    assert real.tolist() == [True, True]
    # a partial last chunk: the boundary past the real tokens is no mark
    marks, ids, real = recurrent._boundaries(table, jnp.int32(32),
                                             jnp.int32(13), C, BS)
    assert marks.tolist() == [8, 0] and real.tolist() == [True, False]
    assert ids.tolist()[0] == 104
    # a window that starts inside a block (no engine does; the form is
    # general): the first boundary is the block's own end
    marks, ids, real = recurrent._boundaries(table, jnp.int32(21),
                                             jnp.int32(16), C, BS)
    assert marks.tolist() == [3, 11] and ids.tolist() == [102, 103]
    assert real.all()


# ------------------------------------------------------------ adoption

@pytest.mark.parametrize("head,own", [
    (24, 13),       # a block boundary INSIDE a chunk
    (32, 21),       # a chunk boundary
    (40, 9),        # a head longer than one chunk
    (64, 1),        # ... and one token of its own behind four chunks
])
def test_adopted_prompt_is_the_cold_one_and_the_reference(cfg, params,
                                                          head, own):
    shared = _tokens(head, seed=head)
    first = np.concatenate([shared, _tokens(11, seed=1)])
    prompt = np.concatenate([shared, _tokens(own, seed=2)])
    eng, cold_eng = _engine(cfg, params), _engine(cfg, params)
    try:
        assert eng.trie is not None and not eng.trie.tails
        _serve(eng, first)
        req, out = _serve(eng, prompt)
        cold_req, cold = _serve(cold_eng, prompt)
        st = eng.stats()
    finally:
        eng.shutdown()
        cold_eng.shutdown()
    assert req.prefix_hit_tokens == head and cold_req.prefix_hit_tokens == 0
    assert out == cold
    assert _margins(params, prompt, out).max() <= ATOL
    assert st["prefix_hit_tokens"] == head
    assert st["state_snapshots_restored"] == 1 and st["admissions"] == 2
    assert st["prefix_blocks_adopted"] == head // BS
    # what the adopting row prefilled is its own part alone
    assert st["prefill_tokens"] == len(first) + own
    assert st["state_snapshot_bytes"] == 5 * 61 * 128 * 4
    assert st["state_bytes"] == 5 * 3 * 128 * 4 + st["state_snapshot_bytes"]


def test_partial_tail_has_no_snapshot_and_is_not_adopted(cfg, params):
    """The rule: only a FULL block carries a state, so a match ends on a
    block boundary: of a 21-token shared head 16 tokens are adopted, and
    the index of such a pool holds no tail leaf."""
    shared = _tokens(21, seed=5)
    eng = _engine(cfg, params)
    try:
        _serve(eng, np.concatenate([shared, _tokens(6, seed=1)]), n=3)
        cached = eng.trie.cached_blocks
        prompt = np.concatenate([shared, _tokens(9, seed=2)])
        req, out = _serve(eng, prompt)
    finally:
        eng.shutdown()
    # 27 prompt + 2 fed tokens = 29 written: 3 full blocks, no leaf for 5
    assert cached == 3
    assert req.prefix_hit_tokens == 16
    assert _margins(params, prompt, out).max() <= ATOL


def test_index_over_snapshots_keeps_full_blocks_only(cfg):
    pool = BlockPool(cfg, n_blocks=12, block_size=BS, max_seq=96,
                     state_rows=1)
    trie = RadixIndex(pool)
    ids = [pool.alloc() for _ in range(3)]
    toks = _tokens(21)
    trie.insert(toks, ids)
    assert trie.cached_blocks == 2 and pool.refcount(ids[2]) == 1
    got, n = trie.match(np.concatenate([toks, _tokens(4, 1)]))
    assert (got, n) == (ids[:2], 16)
    # a K/V-only pool keeps the tail, as ever
    from ray_tpu.models.gpt import GPTConfig
    kv = BlockPool(GPTConfig.tiny(), n_blocks=12, block_size=BS, max_seq=64)
    assert not kv.snapshots and RadixIndex(kv).tails


def test_decode_closed_blocks_are_offered_to_the_index(cfg, params):
    """The choice: the decode step writes a snapshot when a row's token
    closes a block, so a follow-up turn (prompt + answer + more) adopts
    past the first prompt's end."""
    prompt = _tokens(30, seed=6)
    eng = _engine(cfg, params)
    try:
        _, out = _serve(eng, prompt, n=20)
        follow = np.concatenate([prompt, out, _tokens(7, seed=7)])
        req, out2 = _serve(eng, follow)
        st = eng.stats()
    finally:
        eng.shutdown()
    # 30 + 19 fed = 49 written: 6 full blocks, three closed by a step
    assert req.prefix_hit_tokens == 48
    assert _margins(params, follow, out2).max() <= ATOL
    assert st["state_snapshots_written"] == 6 + (57 + 9) // BS - 6


def test_concurrent_siblings_adopt_at_their_first_chunk(cfg, params, walked):
    """Three requests of one head admitted together: the re-match before
    a chunk adopts what the first one published and restores the state
    with it; fused and two-program passes give the same streams,
    whichever form attends the chunks' queries — ``jit_step_chunk`` hands
    its chunk part to the walk as ``jit_chunk_fn`` does — and the key
    blocks walked are counted a chunk pass and walking layer."""
    shared = _tokens(40, seed=8)
    prompts = [np.concatenate([shared, _tokens(5 + 3 * i, seed=i)])
               for i in range(3)]
    streams = {}
    for fused in (True, False):
        eng = _engine(cfg, params)
        try:
            if not fused:
                eng._step_chunk = None
            reqs = [eng.submit(p.tolist(), max_new=14) for p in prompts]
            streams[fused] = [r.result(timeout=600) for r in reqs]
            st = eng.stats()
        finally:
            eng.shutdown()
        assert [r.prefix_hit_tokens for r in reqs] == [0, 40, 40]
        assert st["state_snapshots_restored"] == 2
        assert (st["chunks_in_step"] > 0) == fused
        # the first prompt's chunks reach 16, 32 and 45 keys, the two
        # adopters' one chunk each 40 + 8 and 40 + 11: 1, 1, 2, 2, 2
        # key blocks of 32, an attention layer each
        assert st["chunk_passes"] == 5
        assert st["chunk_key_blocks_walked"] == (
            8 * cfg.n_attention if walked else 0)
    assert streams[True] == streams[False]
    for p, o in zip(prompts, streams[True]):
        assert _margins(params, p, o).max() <= ATOL
