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
