"""Window attention in the two forms the serving programs use
(ops/attention.py), in interpret mode against ``mha_reference`` under a
window mask: the one-token kernel that walks a row's table from the
block that holds the first key of its window, and the head-wise window
form whose walk over key blocks starts there too.  Windows smaller
than, equal to and larger than the context; what lies behind a window
is NEVER read (NaN there changes nothing) and the walk's length is
bounded by the window, not by the context (counted).  Mosaic's view of
the same bodies at the cell's widths is tests/test_chip_compile.py's."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.inference.cache import PoolLayout

attention_mod = importlib.import_module("ray_tpu.ops.attention")

BS, TABLE, LAYERS, LAYER = 8, 8, 2, 1      # 64 keys a row at most
HEADS, KV, HD = 4, 2, 128


def _pools(lens, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    b = len(lens)
    lay = PoolLayout(LAYERS, b * TABLE + 1, BS, KV, HD)
    k_pool, v_pool = (lay.pack(jnp.asarray(rng.standard_normal(
        (*lay.shape[:2], KV, HD)), dtype)) for _ in range(2))
    ids = rng.permutation(np.arange(1, lay.n_rows))
    tables = np.zeros((b, TABLE), np.int32)
    for r, n in enumerate(lens):
        held = -(-n // BS)
        tables[r, :held] = ids[r * TABLE:r * TABLE + held]
    q = jnp.asarray(rng.standard_normal((b, HEADS, 1, HD)), dtype)
    return lay, k_pool, v_pool, tables, q


def _head_major(lay, pool, tables):
    """This layer's keys of every row, [b, heads, T * bs, hd]."""
    x = lay.unpack(lay.read(pool, LAYER, jnp.asarray(tables)))
    return jnp.repeat(x.transpose(0, 2, 1, 3), HEADS // KV, axis=1)


def _decode(lay, q, k_pool, v_pool, tables, lens, window):
    return attention_mod.paged_decode_attention(
        q, k_pool, v_pool, lay.rows(LAYER, 0), jnp.asarray(tables),
        jnp.asarray(lens, jnp.int32), q_per_kv=HEADS // KV, window=window)


# window < context, == context, > context; one wave and several
@pytest.mark.parametrize("wave", [1, 2, TABLE])
@pytest.mark.parametrize("window", [5, 8, 13, 24, 41, 64, 200])
def test_decode_kernel_attends_the_last_window_keys(window, wave,
                                                    monkeypatch):
    lens = [41, 0, 64, 7, 24, 1]
    lay, k_pool, v_pool, tables, q = _pools(lens)
    monkeypatch.setattr(attention_mod, "WAVE_BYTES",
                        4 * wave * BS * lay.width * 4)
    out = _decode(lay, q, k_pool, v_pool, tables, lens, window)
    n = np.asarray(lens)[:, None]
    key = np.arange(TABLE * BS)[None, :]
    mask = (key < np.maximum(n, 1)) & (key >= np.maximum(n, 1) - window)
    want = attention_mod.mha_reference(
        q, _head_major(lay, k_pool, tables), _head_major(lay, v_pool, tables),
        causal=False, mask=jnp.asarray(mask)[:, None, None, :])
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(want)[live],
                               atol=2e-5, rtol=2e-5)
    assert not np.asarray(out)[~live].any()


def test_decode_kernel_reads_no_block_behind_the_window(monkeypatch):
    """Every block that holds no key of a row's window — the blocks
    behind it, which a cache has given back — is NaN and its table entry
    names the scratch block (NaN too): not one bit of the output
    changes.  So a window layer's bytes are the window's."""
    lens, window = [61, 0, 33, 64, 9], 16
    lay, k_pool, v_pool, tables, q = _pools(lens, seed=1)
    monkeypatch.setattr(attention_mod, "WAVE_BYTES",
                        4 * 2 * BS * lay.width * 4)
    clean = _decode(lay, q, k_pool, v_pool, tables, lens, window)
    assert np.isfinite(np.asarray(clean)).all()
    keep = np.zeros(lay.shape[:2], bool)
    freed = tables.copy()
    for r, n in enumerate(lens):
        for j in range(-(-n // BS)):
            if (j + 1) * BS <= n - window:          # wholly behind
                freed[r, j] = 0
                continue
            lo = max(n - window - j * BS, 0)
            keep[lay.rows(LAYER, int(tables[r, j])),
                 lo:min(BS, n - j * BS)] = True
    assert keep.sum() == sum(min(n, window) for n in lens)
    poisoned = [jnp.where(keep[:, :, None], p, jnp.nan)
                for p in (k_pool, v_pool)]
    out = _decode(lay, q, *poisoned, freed, lens, window)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))


def _window_form(q, k, v, pos, window, key_block, n_blocks=None, count=None):
    def read_keys(j, n):
        if count is not None:
            count.append(int(j))
        return tuple(jax.lax.dynamic_slice_in_dim(x, j * n, n)
                     for x in (k, v))
    return attention_mod.head_window_attention(
        q, read_keys, pos, n_kv_heads=KV, scale=HD ** -0.5,
        key_block=key_block, n_blocks=n_blocks, window=window)


@pytest.mark.parametrize("window", [3, 16, 24, 40, 64, 500])
@pytest.mark.parametrize("start, w", [(0, 16), (40, 16), (23, 9), (55, 9)])
def test_head_window_attention_within_a_window(window, start, w):
    """Queries at positions start .. start + w over a row's keys, each
    over its last ``window`` keys, against the plain form under the same
    mask; what lies past the last query or behind the FIRST query's
    window is NaN and must not be seen."""
    n_keys = 64
    ks = jax.random.split(jax.random.PRNGKey(start + window), 3)
    q = jax.random.normal(ks[0], (HEADS, w, HD))
    k = jax.random.normal(ks[1], (n_keys, KV * HD))
    v = jax.random.normal(ks[2], (n_keys, KV * HD))
    pos = start + jnp.arange(w, dtype=jnp.int32)
    key = jnp.arange(n_keys)
    seen = (key <= pos[-1]) & (key > pos[0] - window)
    k_nan, v_nan = (jnp.where(seen[:, None], x, jnp.nan) for x in (k, v))
    got = _window_form(q, k_nan, v_nan, pos, window, 16)

    def split(x):       # [keys, kv * hd] -> [1, heads, keys, hd]
        x = x.reshape(n_keys, KV, HD).transpose(1, 0, 2)
        return jnp.repeat(x, HEADS // KV, axis=0)[None]
    mask = ((key[None, :] <= pos[:, None])
            & (key[None, :] > pos[:, None] - window))[None, None]
    want = attention_mod.mha_reference(q[None], split(k), split(v),
                                       causal=False, mask=mask)[0]
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("context", [64, 256, 1024, 4096])
def test_a_window_layer_walks_the_window_not_the_context(context):
    """COUNTED: the key blocks the window form reads for the last chunk
    of a context.  With a window it is the window's and the chunk's
    (here 3 blocks of 16 keys for a window of 24 and 16 queries)
    whatever the context; without, it grows with the context (walked up
    to 256 keys: un-jitted a block takes a second, and the 256 blocks of
    4,096 outlast the tests' own time limit)."""
    window, w, kb = 24, 16, 16
    start = context - w
    q = jnp.ones((HEADS, w, HD))
    k = v = jnp.ones((context, KV * HD))
    pos = start + jnp.arange(w, dtype=jnp.int32)
    walked = {}
    with jax.disable_jit():
        for name, win in (("window", window), ("full", 0)):
            if win or context <= 256:
                walked[name] = []
                _window_form(q, k, v, pos, win, kb, count=walked[name])
    assert len(walked.get("full", range(context // kb))) == context // kb
    assert len(walked["window"]) == min(context // kb, 3)
    assert walked["window"][-1] == context // kb - 1


# ------------------------------------------------ the walk a chunk takes
#
# ``paged_attend``'s ``q_pos`` form on a table of more than one key
# block (PR 53): one row's chunk of queries walks its table head by
# head where it was attended packed over the whole gathered table.  The
# key block and the score tile are shrunk so that a few hundred keys are
# several of each; the heads and their lanes are the cells' own (lfm2's
# 8 K/V heads of 64 lanes take the re-laid key block, granite's 8 and
# nemotron's 2 of 128 the stored one).

decode = importlib.import_module("ray_tpu.inference.decode")

WALK_KB, WALK_BS, WALK_W = 32, 8, 32
WALK_TABLE = 18                        # 144 keys: 4.5 key blocks
# (start, real queries): the keys in reach are 1, 3 and 4.5 key blocks
WALKS = {"first_block": (0, 32), "adopted_head_padded": (64, 16),
         "table_s_end": (112, 32), "one_real_query": (64, 1)}
WALK_HEADS = {"lfm2": (32, 8, 64), "granite": (32, 8, 128),
              "nemotron": (32, 2, 128)}


@pytest.fixture
def small_key_blocks(monkeypatch):
    monkeypatch.setattr(decode, "KEY_BLOCK", WALK_KB)
    monkeypatch.setattr(attention_mod, "HEAD_TILE", (16, 8))


def _chunk_case(heads, start, n_valid, rows=0, nan=True, seed=0):
    """One row's past of ``start`` keys in a pool that is NaN wherever
    no real query of the chunk may look (every other block, the scratch
    block, the row's own blocks from ``start`` on — and, committed by
    the call, the padding lanes' own keys), the chunk's queries and new
    K/V, and ``rows`` one-token rows with pasts of their own."""
    h, kv, hd = heads
    rng = np.random.default_rng(seed)
    n_rows = 1 + WALK_TABLE * (rows + 1)
    lay = PoolLayout(LAYERS, n_rows, WALK_BS, kv, hd)
    ids = rng.permutation(np.arange(1, n_rows))
    tables = ids.reshape(rows + 1, WALK_TABLE).astype(np.int32)
    lens = [int(n) for n in rng.integers(1, WALK_TABLE * WALK_BS, rows)]
    fill = np.nan if nan else 0.0
    pools = []
    for _ in range(2):
        pool = np.full((*lay.shape[:2], kv, hd), fill, np.float32)
        for r, n in enumerate(lens + [start]):
            for p in range(n):
                pool[lay.rows(LAYER, int(tables[r, p // WALK_BS])),
                     p % WALK_BS] = rng.standard_normal((kv, hd))
        pools.append(lay.pack(jnp.asarray(pool)))
    w = rows + WALK_W
    q = jnp.asarray(rng.standard_normal((1, h, w, hd)), jnp.float32)
    real = np.arange(w) < rows + n_valid
    new = [jnp.asarray(np.where(real[:, None, None], rng.standard_normal(
        (w, kv, hd)), fill), jnp.float32)[None] for _ in range(2)]
    return lay, tuple(pools), tables, lens, q, new


def _attend_chunk(lay, pools, tables, lens, q, new, start, n_valid, **form):
    """``paged_attend`` as the chunk programs call it: the one-token
    rows (if any) and the chunk committed together, then attended."""
    from ray_tpu.inference.recurrent import _chunk_window
    rows = len(lens)
    pos, c_blocks, c_off = _chunk_window(jnp.asarray(tables[-1]), start,
                                         WALK_W, WALK_BS)
    how = dict(q_pos=pos, n_valid=jnp.int32(n_valid))
    if rows:
        at = jnp.asarray(lens, jnp.int32)         # each row's new token
        r_blocks, r_off, kv_len = decode._step_indices(
            jnp.asarray(tables[:-1]), at, jnp.ones(rows, bool), WALK_BS)
        c_blocks = jnp.concatenate([r_blocks, c_blocks])
        c_off = jnp.concatenate([r_off, c_off])
        how.update(kv_lengths=kv_len, mask_tables=jnp.asarray(tables[-1:]))
    how.update(form)
    attend_for, held = decode.paged_attend(
        lay, pools, c_blocks[None], c_off[None],
        jnp.asarray(tables[:-1] if rows else tables[-1:]),
        q_per_kv=q.shape[1] // lay.n_heads, **how)
    return attend_for(LAYER)(q, *new), held["pools"]


@pytest.mark.parametrize("walk", sorted(WALKS))
@pytest.mark.parametrize("heads", sorted(WALK_HEADS))
def test_a_chunk_walks_the_key_blocks_it_can_see(heads, walk,
                                                 small_key_blocks):
    """The walk gives ``packed_attention``'s and ``mha_reference``'s
    sums on every real query, wherever the chunk starts and however few
    of its lanes are real, and it reads nothing past the last real key:
    everything there is NaN — the table's later blocks, the scratch
    block that pads the last key block, the padding lanes' own keys —
    and the real queries' output is what the clean pool gives, the
    padding lanes' finite."""
    start, n_valid = WALKS[walk]
    case = _chunk_case(WALK_HEADS[heads], start, n_valid)
    lay, _, tables, _, q, _ = case
    assert decode.window_by_head(lay, WALK_TABLE)
    got, pools = _attend_chunk(*case, start, n_valid)
    assert np.isfinite(np.asarray(got)).all()
    # the same chunk over a pool with zeros where the NaN were, packed
    # over the whole gathered table (a table of one key block's form)
    clean = _chunk_case(WALK_HEADS[heads], start, n_valid, nan=False)
    held = decode.KEY_BLOCK
    decode.KEY_BLOCK = WALK_TABLE * WALK_BS
    try:
        assert not decode.window_by_head(lay, WALK_TABLE)
        packed, clean_pools = _attend_chunk(*clean, start, n_valid)
    finally:
        decode.KEY_BLOCK = held
    np.testing.assert_allclose(np.asarray(got)[:, :, :n_valid],
                               np.asarray(packed)[:, :, :n_valid],
                               atol=2e-5, rtol=2e-5)
    # and the plain form over the row's keys, head-major
    n_keys = start + n_valid
    k, v = (jnp.repeat(lay.unpack(lay.read(p, LAYER, jnp.asarray(
        tables[-1:])))[:, :n_keys].transpose(0, 2, 1, 3),
        q.shape[1] // lay.n_heads, axis=1) for p in clean_pools)
    want = attention_mod.mha_reference(q[:, :, :n_valid], k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got)[:, :, :n_valid], want,
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("walk", ["adopted_head_padded", "table_s_end"])
@pytest.mark.parametrize("heads", sorted(WALK_HEADS))
def test_the_two_part_window_is_its_two_programs_joined(heads, walk,
                                                        small_key_blocks):
    """``n`` one-token rows and a chunk as ONE window (the fused
    step+chunk program's attention): the rows through the one-token
    kernel, the chunk through the walk over ITS row's table — bit for
    bit what the two forms give apart over the same committed pools."""
    start, n_valid = WALKS[walk]
    rows = 3
    case = _chunk_case(WALK_HEADS[heads], start, n_valid, rows=rows,
                       nan=False, seed=1)
    lay, pools, tables, lens, q, new = case
    both, left = _attend_chunk(*case, start, n_valid)
    assert both.shape == q.shape
    # apart, over the pools the one window left (commits are idempotent)
    chunk, _ = _attend_chunk(lay, left, tables[-1:], [], q[:, :, rows:],
                             [x[:, rows:] for x in new], start, n_valid)
    kv_len = jnp.asarray(lens, jnp.int32) + 1
    step = attention_mod.paged_decode_attention(
        q[0, :, :rows].transpose(1, 0, 2)[:, :, None], *left,
        lay.rows(LAYER, 0), jnp.asarray(tables[:-1]), kv_len,
        q_per_kv=q.shape[1] // lay.n_heads)
    np.testing.assert_array_equal(np.asarray(both)[0, :, :rows],
                                  np.asarray(step)[:, :, 0].transpose(1, 0, 2))
    np.testing.assert_array_equal(np.asarray(both)[:, :, rows:],
                                  np.asarray(chunk))


@pytest.mark.parametrize("start, n_valid, blocks", [(0, 32, 1), (64, 16, 3),
                                                    (64, 0, 0), (112, 32, 5)])
def test_the_walk_stops_at_the_last_real_query_s_key_block(start, n_valid,
                                                           blocks):
    """COUNTED: the key blocks read are those up to the block of the
    last REAL query's key, not the padding lanes' and not the table's
    (a chunk of no real query, which ``warm_up`` runs, reads none and
    gives zeros)."""
    h, kv, hd = 4, 2, 64
    pos = start + jnp.arange(WALK_W, dtype=jnp.int32)
    q = jnp.ones((h, WALK_W, hd))
    k = v = jnp.ones((WALK_TABLE * WALK_BS + WALK_KB, kv * hd))
    walked = []

    def read_keys(j, n):
        walked.append(int(j))
        return tuple(jax.lax.dynamic_slice_in_dim(x, j * n, n)
                     for x in (k, v))
    with jax.disable_jit():
        out = attention_mod.head_window_attention(
            q, read_keys, pos, n_kv_heads=kv, scale=hd ** -0.5,
            key_block=WALK_KB, n_valid=jnp.int32(n_valid))
    assert walked == list(range(blocks))
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out)[:, :n_valid], 1.0, atol=1e-6)
