"""The paged pool's stored layout (cache.PoolLayout) and its two
operations, ``read`` and ``commit``, against a numpy model of the pool
— a plain ``[L, N+1, bs, h, hd]`` array written with index loops — and
``packed_attention`` against ``mha_reference`` and the head-major
``paged_attention`` on the same keys.

Widths: 25 heads of 64 (GPT-2 XL: 1600 lanes, padded to 1664), 12 of 64
(124M: 768, unpadded), and 4 of 16 split over a tp=2 mesh of virtual
CPU devices (each shard's 32 lanes padded to 128)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.inference.cache import BlockPool
from ray_tpu.models import gpt
from ray_tpu.ops.attention import (mha_reference, packed_attention,
                                   paged_attention)
from ray_tpu.parallel import create_mesh

L, N, BS = 3, 9, 4                     # layers, usable blocks, block size
WIDTHS = {"xl-padded": (25, 64, 1), "124m-unpadded": (12, 64, 1),
          "tp2": (4, 16, 2)}


class NumpyPool:
    """The oracle: block (layer, id) holds ``[bs, h, hd]``."""

    def __init__(self, h, hd):
        self.a = np.zeros((L, N + 1, BS, h, hd), np.float32)

    def commit(self, layer, blocks, offsets, new):
        for i in np.ndindex(blocks.shape):
            self.a[layer, blocks[i], offsets[i]] = new[i]

    def read(self, layer, tables):                  # -> [..., T*bs, h, hd]
        g = self.a[layer][tables]                   # [..., T, bs, h, hd]
        return g.reshape(*tables.shape[:-1], -1, *g.shape[-2:])


@pytest.fixture(params=list(WIDTHS))
def pool(request):
    h, hd, shards = WIDTHS[request.param]
    mesh = None
    if shards > 1:
        if jax.device_count() < shards:
            pytest.skip(f"need {shards} CPU devices for the tp mesh")
        mesh = create_mesh({"tp": shards}, devices=jax.devices()[:shards])
    cfg = gpt.GPTConfig.tiny(dtype=jnp.float32, n_layers=L, n_heads=h,
                             d_model=h * hd, max_seq=BS * 4)
    return BlockPool(cfg, n_blocks=N, block_size=BS, mesh=mesh)


def _commit(pool, layer, blocks, offsets, new):
    """``commit`` as the programs run it: jitted, pool donated."""
    fn = jax.jit(pool.layout.commit, donate_argnums=(0,))
    pool.k = fn(pool.k, jnp.int32(layer), jnp.asarray(blocks),
                jnp.asarray(offsets), jnp.asarray(new))


def _read(pool, layer, tables):
    lay = pool.layout
    fn = jax.jit(lambda p, li, t: lay.unpack(lay.read(p, li, t)))
    return np.asarray(fn(pool.k, jnp.int32(layer), jnp.asarray(tables)))


def _tokens(rng, pool, *lead):
    lay = pool.layout
    return rng.standard_normal((*lead, lay.n_heads, lay.head_dim)) \
        .astype(np.float32)


def _whole(pool, layer):
    """Every usable block of a layer (the scratch block's content is
    whatever collided there last)."""
    return _read(pool, layer, np.arange(1, N + 1, dtype=np.int32)[None])


def test_shape_is_tiled_without_padding(pool):
    lay = pool.layout
    h, hd, shards = lay.n_heads, lay.head_dim, lay.shards
    assert pool.k.shape == lay.shape == (L * (N + 1), BS, lay.width)
    assert lay.width % (128 * shards) == 0
    assert lay.width - h * hd < 128 * shards        # never a tile too many
    if (h * hd // shards) % 128 == 0:
        assert lay.width == h * hd                  # 768 stays 768
    assert pool.bytes_total() == 2 * 4 * int(np.prod(lay.shape))
    assert pool.stats()["bytes_per_device"] == pool.bytes_total() // shards
    if pool.mesh is not None:
        assert "tp" in str(pool.k.sharding.spec[-1])


def test_commit_on_a_block_boundary(pool):
    """Two rows, one at the last slot of its block and one at the first
    of the next: each token lands in its own block and nowhere else."""
    rng = np.random.default_rng(0)
    ref = NumpyPool(pool.layout.n_heads, pool.layout.head_dim)
    tables = np.array([[2, 5, 0, 0], [7, 3, 0, 0]], np.int32)
    positions = np.array([BS - 1, BS], np.int32)
    blocks = tables[np.arange(2), positions // BS]
    new = _tokens(rng, pool, 2)
    for layer in (0, L - 1):
        _commit(pool, layer, blocks, positions % BS, new)
        ref.commit(layer, blocks, positions % BS, new)
    for layer in range(L):
        np.testing.assert_array_equal(_read(pool, layer, tables),
                                      ref.read(layer, tables))
        np.testing.assert_array_equal(
            _whole(pool, layer), ref.read(layer, np.arange(1, N + 1)[None]))


def test_inactive_rows_go_to_the_scratch_block(pool):
    """Rows redirected to (block 0, offset 0) collide there and touch no
    usable block of any layer."""
    rng = np.random.default_rng(1)
    ref = NumpyPool(pool.layout.n_heads, pool.layout.head_dim)
    active = np.array([True, False, False, True])
    blocks = np.where(active, [4, 6, 6, 1], 0).astype(np.int32)
    offsets = np.where(active, [1, 2, 2, 3], 0).astype(np.int32)
    new = _tokens(rng, pool, 4)
    _commit(pool, 1, blocks, offsets, new)
    ref.commit(1, blocks[active], offsets[active], new[active])
    for layer in range(L):
        np.testing.assert_array_equal(
            _whole(pool, layer), ref.read(layer, np.arange(1, N + 1)[None]))
    scratch = _read(pool, 1, np.zeros((1, 1), np.int32))[0, 0]
    assert any(np.array_equal(scratch, new[i]) for i in (1, 2))


def test_chunk_spanning_three_blocks(pool):
    """A window of 2*bs tokens starting mid-block, as the chunk program
    writes it: (table[pos // bs], pos % bs) per token, then read back
    through the table in position order."""
    rng = np.random.default_rng(2)
    ref = NumpyPool(pool.layout.n_heads, pool.layout.head_dim)
    table = np.array([8, 2, 6, 0], np.int32)
    pos = BS // 2 + np.arange(2 * BS, dtype=np.int32)
    assert len(set(pos // BS)) == 3
    new = _tokens(rng, pool, len(pos))
    _commit(pool, 2, table[pos // BS], pos % BS, new)
    ref.commit(2, table[pos // BS], pos % BS, new)
    got = _read(pool, 2, table[None])
    np.testing.assert_array_equal(got, ref.read(2, table[None]))
    np.testing.assert_array_equal(got[0, pos], new)


def test_interchange_round_trip(pool):
    """read_blocks -> write_blocks_at through ``[L, T, h, bs, hd]``, and
    write_prefill / copy_block through the same programs."""
    rng = np.random.default_rng(3)
    lay = pool.layout
    ids = [3, 9, 1]
    k = rng.standard_normal((L, len(ids), lay.n_heads, BS, lay.head_dim)) \
        .astype(np.float32)
    pool.write_blocks_at(ids, k, -k)
    k2, v2 = pool.read_blocks(ids)
    assert k2.shape == k.shape
    np.testing.assert_array_equal(k2, k)
    np.testing.assert_array_equal(v2, -k)
    # what the programs read is what the interchange wrote
    np.testing.assert_array_equal(
        _read(pool, 1, np.asarray([ids], np.int32))[0],
        k[1].transpose(0, 2, 1, 3).reshape(-1, lay.n_heads, lay.head_dim))
    # a second pool adopts the chain under other ids
    other = BlockPool(pool.cfg, n_blocks=N, block_size=BS, mesh=pool.mesh)
    other.write_blocks_at([5, 6, 7], *pool.read_blocks(ids))
    np.testing.assert_array_equal(other.read_blocks([5, 6, 7])[0], k)
    # full-width prefill K/V [L, h, S, hd] through a table
    span = pool.blocks_per_seq * BS
    full = rng.standard_normal((L, lay.n_heads, span - 3, lay.head_dim)) \
        .astype(np.float32)
    table = [2, 4, 8, 6]
    pool.write_prefill(table, jnp.asarray(full), jnp.asarray(2 * full))
    got_k, got_v = pool.read_blocks(table)        # [L, T, h, bs, hd]
    flat = got_k.transpose(0, 2, 1, 3, 4).reshape(L, lay.n_heads, span, -1)
    np.testing.assert_array_equal(flat[:, :, :span - 3], full)
    np.testing.assert_array_equal(flat[:, :, span - 3:], 0)
    np.testing.assert_array_equal(got_v, 2 * got_k)
    pool.copy_block(4, 3)
    np.testing.assert_array_equal(pool.read_blocks([3])[0],
                                  pool.read_blocks([4])[0])
    np.testing.assert_array_equal(pool.read_blocks([9])[0], k[:, 1:2])


@pytest.mark.parametrize("q_len", [1, 5])
def test_packed_attention_is_the_reference(pool, q_len):
    """Keys read from the pool as stored, attended packed, against
    mha_reference over the same keys with the heads split out."""
    rng = np.random.default_rng(4)
    lay = pool.layout
    k = rng.standard_normal((L, N, lay.n_heads, BS, lay.head_dim)) \
        .astype(np.float32)
    pool.write_blocks_at(list(range(1, N + 1)), k, k[::-1])
    tables = jnp.asarray([[3, 1, 4, 0], [5, 9, 2, 6]], jnp.int32)
    q = jnp.asarray(rng.standard_normal(
        (2, lay.n_heads, q_len, lay.head_dim)).astype(np.float32))
    kv_len = jnp.asarray([7, 14], jnp.int32)
    hor = kv_len[:, None] - q_len + jnp.arange(q_len)[None, :]
    mask = (jnp.arange(4 * BS)[None, None, :] <= hor[:, :, None])[:, None]
    for kw in ({"kv_lengths": kv_len}, {"mask": mask}):
        @jax.jit
        def both(pk, pv, q):
            ck, cv = lay.read(pk, 1, tables), lay.read(pv, 1, tables)
            split = [lay.unpack(c).transpose(0, 2, 1, 3) for c in (ck, cv)]
            return (packed_attention(q, ck, cv, groups=lay.shards, **kw),
                    mha_reference(q, *split, causal=False, **kw))
        got, want = both(pool.k, pool.v, q)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-6, atol=2e-6)
        # and the head-major oracle over one layer's [N+1, h, bs, hd]
        scratch = np.zeros_like(k[1, :1])
        oracle = paged_attention(
            q, jnp.asarray(np.concatenate([scratch, k[1]])),
            jnp.asarray(np.concatenate([scratch, k[::-1][1]])), tables, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(oracle),
                                   rtol=2e-6, atol=2e-6)
