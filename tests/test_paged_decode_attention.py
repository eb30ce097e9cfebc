"""The one-token paged attention kernel (ops/attention.
paged_decode_attention) in interpret mode against the oracle
(ops/attention.paged_attention, head-major, float32 logits): it attends
what the gather formulation attends, and READS what a row holds and
nothing else.  Mosaic's view of the same body at the cells' widths is
tests/test_chip_compile.py's."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.inference.cache import PoolLayout

attention_mod = importlib.import_module("ray_tpu.ops.attention")

BS, TABLE, LAYERS, LAYER = 8, 6, 3, 1      # 48 keys a row at most

# heads, head_dim, q_per_kv, scale, dtype
XL_LIKE = (5, 64, 1, None, jnp.bfloat16)        # odd heads, 320 -> 384 lanes
SMALL = (4, 64, 1, None, jnp.float32)           # 256 lanes, no padding
GROUPED = (8, 128, 4, 1 / 128, jnp.float32)     # 2 K/V heads of 128

CASES = {
    # geometry, kv_len a row (0 = the row sits out), blocks a wave
    "xl-like-odd-heads-padded-width": (XL_LIKE, [17, 0, 48, 30], 2),
    "124m-like-unpadded-width": (SMALL, [17, 0, 48, 30], 2),
    "grouped-queries-with-scale": (GROUPED, [17, 0, 48, 30], 2),
    "one-key": (SMALL, [1, 0, 0, 1], 2),
    "whole-table": (SMALL, [48, 48, 48, 48], 4),
    "not-a-multiple-of-the-block": (SMALL, [13, 29, 3, 41], 2),
    "idle-rows-between-live-ones": (SMALL, [7, 0, 48, 0, 0, 13], 1),
    "no-row-live": (SMALL, [0, 0, 0, 0], 2),
    "one-wave-holds-the-table": (SMALL, [13, 0, 48, 30], TABLE),
    "grouped-queries-idle-first-and-last": (GROUPED, [0, 41, 8, 0], 4),
}


def _setup(geometry, lens, seed=0):
    """Pools of three layers as stored, distinct blocks for every row in
    a shuffled order, unused table entries at the scratch block."""
    heads, hd, q_per_kv, scale, dtype = geometry
    rng = np.random.default_rng(seed)
    b = len(lens)
    lay = PoolLayout(LAYERS, b * TABLE + 1, BS, heads // q_per_kv, hd)
    k_pool, v_pool = (lay.pack(jnp.asarray(rng.standard_normal(
        (*lay.shape[:2], lay.n_heads, hd)), dtype)) for _ in range(2))
    ids = rng.permutation(np.arange(1, lay.n_rows))
    tables = np.zeros((b, TABLE), np.int32)
    for r, n in enumerate(lens):
        held = -(-n // BS)
        tables[r, :held] = ids[r * TABLE:r * TABLE + held]
    q = jnp.asarray(rng.standard_normal((b, heads, 1, hd)), dtype)
    return lay, k_pool, v_pool, jnp.asarray(tables), q


def _walk(geometry, lay, q, k_pool, v_pool, tables, lens):
    return attention_mod.paged_decode_attention(
        q, k_pool, v_pool, lay.rows(LAYER, 0), tables,
        jnp.asarray(lens, jnp.int32), q_per_kv=geometry[2],
        scale=geometry[3])


def _wave_of(monkeypatch, lay, dtype, blocks):
    monkeypatch.setattr(
        attention_mod, "WAVE_BYTES",
        4 * blocks * BS * lay.width * jnp.dtype(dtype).itemsize)


@pytest.mark.parametrize("case", CASES)
def test_kernel_attends_what_the_oracle_attends(case, monkeypatch):
    geometry, lens, wave = CASES[case]
    q_per_kv, scale, dtype = geometry[2:]
    lay, k_pool, v_pool, tables, q = _setup(geometry, lens)
    _wave_of(monkeypatch, lay, dtype, wave)
    out = _walk(geometry, lay, q, k_pool, v_pool, tables, lens)
    assert out.shape == q.shape and out.dtype == dtype

    def head_major(pool):           # this layer, [blocks, h, bs, hd]
        x = lay.unpack(pool[lay.rows(LAYER, 0):lay.rows(LAYER + 1, 0)])
        return jnp.repeat(x.transpose(0, 2, 1, 3), q_per_kv, axis=1)

    live = np.asarray(lens) > 0
    ref = attention_mod.paged_attention(
        q, head_major(k_pool), head_major(v_pool), tables,
        kv_lengths=jnp.maximum(jnp.asarray(lens), 1), scale=scale)
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(out[live], ref[live], atol=tol, rtol=tol)
    assert not out[~live].any()


def test_kernel_reads_what_a_row_holds_and_nothing_else(monkeypatch):
    """NaN in every block of the pools that no live row's table names
    within its length (the other layers, the scratch block, every block
    of an idle row) and in the keys past ``kv_len`` of a row's last
    block: not one bit of the output changes."""
    geometry, lens = GROUPED, [0, 41, 8, 0, 48]
    lay, k_pool, v_pool, tables, q = _setup(geometry, lens, seed=1)
    _wave_of(monkeypatch, lay, geometry[4], 2)
    clean = _walk(geometry, lay, q, k_pool, v_pool, tables, lens)
    assert np.isfinite(np.asarray(clean)).all()

    keep = np.zeros(lay.shape[:2], bool)
    for r, n in enumerate(lens):
        for j in range(-(-n // BS)):
            keep[lay.rows(LAYER, int(tables[r, j])),
                 :min(BS, n - j * BS)] = True
    assert keep.sum() == sum(lens)
    poisoned = [jnp.where(keep[:, :, None], p, jnp.nan)
                for p in (k_pool, v_pool)]
    assert np.isnan(np.asarray(lay.read(poisoned[0], LAYER, tables))).any()
    out = _walk(geometry, lay, q, *poisoned, tables, lens)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))
