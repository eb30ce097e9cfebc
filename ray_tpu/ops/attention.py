"""Multi-head attention entry point with hardware dispatch.

``attention(q, k, v)`` picks the best implementation for the current
backend: the pallas flash kernel on TPU (block-wise, online softmax, no
O(s²) materialization — HBM-bandwidth friendly), a pure-jax reference
everywhere else (XLA still fuses it into a few kernels on CPU).  Both are
differentiable and numerically interchangeable (tests assert allclose).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.sharding import Mesh, PartitionSpec


def _scale_for(q, scale):
    return (q.shape[-1] ** -0.5) if scale is None else scale


def mha_reference(q, k, v, *, causal: bool = True,
                  scale: Optional[float] = None,
                  mask: Optional[jax.Array] = None,
                  kv_lengths: Optional[jax.Array] = None) -> jax.Array:
    """Plain softmax attention.  [b, h, s, d] layout.

    Kept in float32 logits regardless of input dtype — matches the flash
    kernel's accumulator precision so the two paths agree in bf16.

    ``kv_lengths`` [b] int32 masks each batch row to its own valid kv
    prefix (key position < kv_lengths[b]).  This is the slot-batched
    decode shape (ray_tpu.inference): one fixed-width kv cache per slot,
    every slot at a DIFFERENT sequence length, so the single global
    (k_len - q_len) causal offset cannot express the mask.  Rows must
    have at least one valid key (length >= 1) or the softmax is NaN.
    """
    s = _scale_for(q, scale)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * s
    if causal:
        q_len, k_len = logits.shape[-2], logits.shape[-1]
        # offset supports cross-length (e.g. decode with kv cache)
        idx_q = jnp.arange(q_len)[:, None] + (k_len - q_len)
        idx_k = jnp.arange(k_len)[None, :]
        causal_mask = idx_q >= idx_k
        logits = jnp.where(causal_mask, logits, -jnp.inf)
    probs = _masked_softmax(logits, kv_lengths, mask)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


def _masked_softmax(logits, kv_lengths, mask):
    """softmax over keys of f32 ``logits`` [b, h, q, k], each batch row
    limited to its ``kv_lengths`` [b] and to ``mask``."""
    if kv_lengths is not None:
        valid = (jnp.arange(logits.shape[-1])[None, :]
                 < kv_lengths[:, None])                   # [b, k]
        logits = jnp.where(valid[:, None, None, :], logits, -jnp.inf)
    if mask is not None:
        logits = jnp.where(mask, logits, -jnp.inf)
    return jax.nn.softmax(logits, axis=-1)


def packed_attention(q, k, v, *, groups: int = 1, q_per_kv: int = 1,
                     scale: Optional[float] = None,
                     mask: Optional[jax.Array] = None,
                     kv_lengths: Optional[jax.Array] = None) -> jax.Array:
    """``mha_reference`` over keys and values stored TOKEN-MAJOR, a
    token's heads side by side in the minor dim — the paged KV pool's
    stored form (inference/cache.PoolLayout), attended as it is
    gathered.

    q    [b, h, q_len, hd]
    k, v [b, S, W]: W is ``groups`` equal runs of lanes (one per heads
         shard); a run holds its ``h / groups`` heads of ``hd`` lanes
         each, then padding lanes that are ignored.
    -> [b, h, q_len, hd]

    Heads of 64 lanes fill half a TPU tile, so splitting them out
    ([b, h, S, hd]) re-tiles the whole context before it can be
    multiplied.  Here every head's query is instead placed in its own
    lanes of a [W, heads] matrix that is zero elsewhere: ONE matmul over
    the full width gives all heads' logits, and one matmul of the
    probabilities with V gives every (head, lane) pair, of which each
    lane keeps its own head's.  The extra products are exact zeros, so
    the sums are mha_reference's (same float32 logits, same rounding of
    the probabilities to ``v.dtype``); the MXU does ``h / groups`` times
    the arithmetic and the context is read once, where it lies.

    Grouped queries (``q_per_kv`` > 1): k and v hold ``h / q_per_kv``
    heads, each attended by ``q_per_kv`` consecutive query heads, whose
    queries are all placed in that K/V head's lanes.
    """
    b, h, nq, hd = q.shape
    hg, wg = h // groups, k.shape[-1] // groups
    s = _scale_for(q, scale)
    heads = jnp.arange(hg)[None, :]
    own = (jnp.arange(wg)[:, None] // hd
           == (heads if q_per_kv == 1 else heads // q_per_kv))  # [wg, hg]
    if q_per_kv == 1:
        ql = q.transpose(0, 2, 1, 3).reshape(b, nq, groups, hg * hd)
        ql = jnp.pad(ql, [(0, 0)] * 3 + [(0, wg - hg * hd)])[..., None]
    else:
        # every K/V head's lanes hold a copy of each query; ``own`` keeps
        # the copies that lie in the query's own K/V head
        ql = q.reshape(b, groups, hg, nq, hd).transpose(0, 3, 1, 4, 2)
        ql = jnp.tile(ql, (1, 1, 1, wg // hd, 1))         # [b,q,g,~wg,hg]
        ql = jnp.pad(ql, [(0, 0)] * 3 + [(0, wg - ql.shape[3]), (0, 0)])
    qx = jnp.where(own, ql, 0).astype(q.dtype)            # [b,q,g,wg,hg]
    kg = k.reshape(b, -1, groups, wg)
    vg = v.reshape(b, -1, groups, wg)
    logits = jnp.einsum("bkgw,bqgwh->bghqk", kg, qx,
                        preferred_element_type=jnp.float32) * s
    probs = _masked_softmax(logits.reshape(b, h, nq, -1), kv_lengths, mask)
    probs = probs.astype(v.dtype).reshape(b, groups, hg, nq, -1)
    full = jnp.einsum("bghqk,bkgw->bqgwh", probs, vg,
                      preferred_element_type=jnp.float32)
    if q_per_kv == 1:
        o = jnp.where(own, full, 0).sum(-1).astype(v.dtype)  # [b,q,g,wg]
        o = o[..., :hg * hd].reshape(b, nq, h, hd)
        return o.transpose(0, 2, 1, 3)
    # head j's output lies in its K/V head's lanes of column j
    kv = hg // q_per_kv
    o = full[..., :kv * hd, :].reshape(b, nq, groups, kv, hd, kv, q_per_kv)
    o = jnp.diagonal(o, axis1=3, axis2=5)                 # [b,q,g,hd,r,kv]
    o = o.transpose(0, 2, 5, 4, 1, 3).reshape(b, h, nq, hd)
    return o.astype(v.dtype)


def paged_attention(q, k_pool, v_pool, block_tables, *,
                    kv_lengths: Optional[jax.Array] = None,
                    mask: Optional[jax.Array] = None,
                    scale: Optional[float] = None) -> jax.Array:
    """Block-table-indexed attention over a paged KV pool (one layer).

    q            [b, h, q_len, hd]
    k_pool/v_pool [n_blocks, h, block_size, hd] — ONE layer's pool slice
    block_tables [b, n_table] int32 — per-row block ids, in sequence
                 order; unused entries point at the scratch block (id 0)
                 whose garbage the masks hide.

    Gathers each row's blocks into a contiguous virtual sequence
    ``[b, h, n_table * block_size, hd]`` (position p lands at gather
    index p — tables are position-ordered) and runs the reference
    masked attention: ``kv_lengths`` [b] masks each row to its own
    valid prefix (the paged decode shape), ``mask`` is the explicit
    [b, 1|h, q_len, S] variant (chunked prefill, where each query row
    has its OWN causal horizon).  This is the gather-per-step cost the
    slot-granular design deferred; block granularity buys pool sharing
    across mixed-length sequences in exchange.

    This is the REFERENCE formulation, the oracle of the tests, kept
    in the head-major ``[n_blocks, h, block_size, hd]`` form.  The
    compiled step bodies in inference/decode.py store the pool as
    inference/cache.PoolLayout says (token-major, all layers in one
    array), write the current window's K/V into it and then gather the
    same tables, attending the gathered rows as stored
    (``packed_attention``): same keys at the same positions.
    """
    b = q.shape[0]
    n_tab = block_tables.shape[1]
    bs = k_pool.shape[2]
    h, hd = k_pool.shape[1], k_pool.shape[3]

    def gather(pool):
        g = pool[block_tables]                       # [b, T, h, bs, hd]
        return g.transpose(0, 2, 1, 3, 4).reshape(b, h, n_tab * bs, hd)

    return mha_reference(q, gather(k_pool), gather(v_pool), causal=False,
                         scale=scale, mask=mask, kv_lengths=kv_lengths)


# VMEM the decode kernel's wave buffers take together (K and V, each
# double-buffered): a wave is the largest power of two of blocks that
# fits, 16 blocks of 16 x 1664 bf16 (GPT-2 XL), 32 of 16 x 1024 (the
# hybrid's one K/V layer)
WAVE_BYTES = 4 << 20


def _list_live_rows(len_ref, rows_ref):
    """``rows_ref`` <- the rows with ``len`` > 0, in order; -> their
    count (a row that sits out costs one scalar comparison)."""
    def list_live(row, n):
        live = len_ref[row] > 0

        @pl.when(live)
        def _():
            rows_ref[n] = row
        return n + live.astype(jnp.int32)

    return lax.fori_loop(0, rows_ref.shape[0], list_live, 0)


def _decode_kernel(base_ref, len_ref, tab_ref, q_ref, k_hbm, v_hbm, o_ref,
                   rows_ref, k_buf, v_buf, sem, qx_ref, acc_ref, *,
                   wave: int, n_table: int, n_kv: int, head_dim: int,
                   scale: float, window: int = 0):
    """Every live row's one query over the blocks its table names, a
    wave of blocks at a time: wave w + 1 (or the next live row's first)
    is on its way into one half of the buffers while wave w is attended
    in the other.  The live rows (``len`` > 0) are listed first, so a
    row that sits out costs one scalar comparison.

    A row's R = ``q_per_kv`` queries a K/V head arrive as R vectors of
    the pool's width, query r of K/V head g in g's lanes of vector r.
    ``qx`` [R * gp, W] is ``packed_attention``'s query matrix, one row a
    query head (row r * gp + g, the K/V heads padded to gp rows): its
    own lanes hold the query, the rest are zero, so one product over
    the full width gives every head's logits [heads, tokens] and one
    product with V every (head, lane) pair, of which each lane keeps
    its own head's.

    ``window`` > 0: a row attends its LAST ``window`` keys only (keys
    ``len - window .. len``), and the walk starts at the wave, and in
    it at the block, that holds the first of them: what lies before is
    neither copied nor multiplied (its table entries may name blocks
    the row gave back), so a row's time is bounded by the window."""
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    bs, width = k_buf.shape[1] // wave, k_buf.shape[2]
    tokens = wave * bs
    reps, gp = q_ref.shape[1], qx_ref.shape[0] // q_ref.shape[1]
    base = base_ref[0]

    count = _list_live_rows(len_ref, rows_ref)

    def first_key(row):
        """The first key the row attends."""
        return jnp.maximum(len_ref[row] - window, 0) if window else 0

    def first_wave(row):
        return first_key(row) // tokens if window else 0

    def blocks_of(row, w):
        """Blocks of the row's wave w that hold a key: 0 .. wave."""
        return jnp.clip(pl.cdiv(len_ref[row], bs) - w * wave, 0, wave)

    def first_block(row, w):
        """... and the first of them that holds a key the row attends."""
        if not window:
            return 0
        return jnp.clip(first_key(row) // bs - w * wave, 0, wave)

    def copies(row, w, half, i):
        at = pl.ds(pl.multiple_of(i * bs, bs), bs)
        src = base + tab_ref[row * n_table + w * wave + i]
        return (pltpu.make_async_copy(k_hbm.at[src], k_buf.at[half, at],
                                      sem.at[0, half]),
                pltpu.make_async_copy(v_hbm.at[src], v_buf.at[half, at],
                                      sem.at[1, half]))

    def each_copy(row, w, half, do):
        def one(i, _):
            for c in copies(row, w, half, i):
                do(c)
        lax.fori_loop(first_block(row, w), blocks_of(row, w), one, None)

    def start(row, w, half):
        each_copy(row, w, half, lambda c: c.start())

    def wait(row, w, half):
        each_copy(row, w, half, lambda c: c.wait())

    def own():
        """[gp, W]: lane belongs to the K/V head of this sublane."""
        head = lax.broadcasted_iota(jnp.int32, (gp, width), 0)
        lane = lax.broadcasted_iota(jnp.int32, (gp, width), 1)
        return ((lane >= head * head_dim) & (lane < (head + 1) * head_dim)
                & (head < n_kv))

    @pl.when(count > 0)
    def _():
        start(rows_ref[0], first_wave(rows_ref[0]), 0)

    def row_body(slot, done):
        """``done``: waves attended so far, whose parity says which
        half of the buffers this row's first wave is arriving in."""
        row = rows_ref[slot]
        kv_len = len_ref[row]
        lo, w0 = first_key(row), first_wave(row)
        n_waves = pl.cdiv(kv_len, tokens)
        q = q_ref[row]                                          # [R, W]
        for r in range(reps):
            qx_ref[r * gp:(r + 1) * gp, :] = jnp.where(
                own(), q[r:r + 1, :], 0.0).astype(qx_ref.dtype)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def wave_body(w, carry):
            m_prev, l_prev = carry
            half = (done + w - w0) % 2
            more = w + 1 < n_waves

            @pl.when(more | (slot + 1 < count))
            def _():
                # (with a window the next row's length is read too: past
                # the last live row the list holds nothing, so stay on it)
                nxt = rows_ref[jnp.minimum(
                    slot + 1, count - 1 if window else rows_ref.shape[0] - 1)]
                start(jnp.where(more, row, nxt),
                      jnp.where(more, w + 1, first_wave(nxt)), 1 - half)

            wait(row, w, half)
            s = lax.dot_general(
                qx_ref[...], k_buf[half], (((1,), (1,)), ((), ())),
                preferred_element_type=f32) * scale     # [heads, tokens]
            key = w * tokens + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            seen = key < kv_len
            if window:
                seen &= key >= lo
            s = jnp.where(seen, s, -jnp.inf)
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(s - m_next)

            # a key past kv_len (or before the window) weighs exactly
            # 0, and what lies there (a block not copied, a block's
            # unwritten tail) may be anything: 0 x NaN is NaN
            ragged = (w + 1) * tokens > kv_len
            if window:
                ragged |= w * tokens < lo

            @pl.when(ragged)
            def _():
                pos = w * tokens + lax.broadcasted_iota(
                    jnp.int32, (tokens, width), 0)
                v = v_buf[half]
                keep = pos < kv_len
                if window:
                    keep &= pos >= lo
                v_buf[half] = jnp.where(keep, v, jnp.zeros_like(v))

            acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
                p.astype(v_buf.dtype), v_buf[half],
                preferred_element_type=f32)
            return m_next, l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)

        heads = qx_ref.shape[0]
        _, l = lax.fori_loop(
            w0, n_waves, wave_body,
            (jnp.full((heads, 1), -jnp.inf, f32), jnp.zeros((heads, 1), f32)))
        out = acc_ref[...] / l                                  # [heads, W]
        for r in range(reps):
            o_ref[row, r:r + 1, :] = jnp.sum(
                jnp.where(own(), out[r * gp:(r + 1) * gp], 0.0),
                axis=0, keepdims=True)
        return done + n_waves - w0

    lax.fori_loop(0, count, row_body, 0)


def paged_decode_attention(q, k_pool, v_pool, base, tables, kv_lengths, *,
                           q_per_kv: int = 1,
                           scale: Optional[float] = None,
                           window: int = 0) -> jax.Array:
    """One query a row over the paged pools AS STORED, reading only the
    blocks a row holds.

    q            [b, h, 1, hd]
    k/v_pool     [rows, bs, W] (inference/cache.PoolLayout, one shard:
                 ``h / q_per_kv`` heads of hd lanes, then padding); the
                 kernel takes them where they lie in HBM
    base         int32 scalar: the pools' row of this layer's block 0
    tables       [b, T] int32 block ids in position order
    kv_lengths   [b] int32: keys the row attends; 0 = the row sits the
                 pass out
    window       > 0: of those keys only the last ``window`` (the row's
                 query is the last of them); the blocks before them are
                 not read, and their table entries may name anything
    -> [b, h, 1, hd]; a row that sits out gets zeros.

    ONE Pallas kernel walks the live rows.  For each it copies the
    ``ceil(kv_len / bs)`` blocks its table names, each one contiguous
    [bs, W] run, into VMEM a wave at a time, and does there
    ``packed_attention``'s arithmetic with a running softmax across
    waves: float32 logits over the full width for all heads at once,
    keys past ``kv_len`` masked, the (unnormalised) probabilities
    rounded to the pool's dtype before the product with V, float32
    sums.  A row that sits out is not visited and no other block is
    read."""
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.ops.flash_attention import _interpret_mode

    b, h, _, hd = q.shape
    n_table = tables.shape[1]
    _, bs, width = k_pool.shape
    n_kv = h // q_per_kv
    gp = -(-n_kv // 8) * 8                  # a float32 tile's sublanes
    wave = max(1, WAVE_BYTES // (4 * bs * width * k_pool.dtype.itemsize))
    wave = min(1 << (wave.bit_length() - 1), n_table)

    # query r of K/V head g: head g * q_per_kv + r -> vector r, g's lanes
    qr = q.reshape(b, n_kv, q_per_kv, hd).transpose(0, 2, 1, 3)
    qr = qr.reshape(b, q_per_kv, n_kv * hd).astype(jnp.float32)
    qr = jnp.pad(qr, [(0, 0), (0, 0), (0, width - n_kv * hd)])

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, wave=wave, n_table=n_table,
                          n_kv=n_kv, head_dim=hd,
                          scale=_scale_for(q, scale), window=int(window)),
        in_specs=[smem] * 3 + [vmem, hbm, hbm],
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct(qr.shape, jnp.float32),
        scratch_shapes=[
            pltpu.SMEM((b,), jnp.int32),
            pltpu.VMEM((2, wave * bs, width), k_pool.dtype),
            pltpu.VMEM((2, wave * bs, width), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((q_per_kv * gp, width), k_pool.dtype),
            pltpu.VMEM((q_per_kv * gp, width), jnp.float32)],
        interpret=_interpret_mode(),
        name="paged_decode_attention",
    )(jnp.asarray(base, jnp.int32)[None], kv_lengths.astype(jnp.int32),
      tables.reshape(-1), qr, k_pool, v_pool)
    out = jnp.where((kv_lengths > 0)[:, None, None], out[..., :n_kv * hd], 0.0)
    out = out.reshape(b, q_per_kv, n_kv, hd).transpose(0, 2, 1, 3)
    return out.reshape(b, h, 1, hd).astype(v_pool.dtype)


# ---------------------------------------------------------------------------
# latent attention: ONE cached vector a token, [c_kv | k_rope], shared by
# every head; a head's key is [c_kv W_uk | k_rope] and its value c_kv
# W_uv.  Two forms of the same sums:
# a WINDOW of queries decompresses the keys a block at a time (the work
# is the keys' and is shared by the window's queries); ONE token absorbs
# W_uk into its query and W_uv into its output and attends the latents
# as they are stored (decompressing every key for one query would be the
# whole cost of a prompt, every step).

KEY_BLOCK = 1024    # keys a step of the window form decompresses
_MASKED = -1e30     # a masked score: finite, so no (-inf) - (-inf)
# the window kernel's score tile, (keys, queries): a head's [key_block,
# w] scores are worked through in tiles of this size (the whole array
# where it is smaller), each done by what its place demands
WINDOW_TILE = (512, 256)
# ``head_window_attention``'s: a visit of a tile costs ~0.7 us beside its
# arithmetic (4.3 us a head and 1,024 x 1,024 block in 512-wide tiles,
# 5.6 in 256-wide, at 64 and at 128 lanes a head: my chip runs, PR 53,
# benchmarks/window_walk.py), so a head's tiles are as wide as the
# padding of a half-real chunk still lets it skip
HEAD_TILE = (512, 512)


def _spans(n: int, tile: int) -> list:
    """``range(n)`` in runs of ``tile``: [(first, count)], the last run
    what is left."""
    return [(a, min(tile, n - a)) for a in range(0, n, tile)]


def _tile_kind(k_lo, k_hi, q_lo, q_hi):
    """Where a score tile lies against the causal edge: its keys are at
    positions ``k_lo .. k_hi``, ``q_lo`` is the least position of its
    queries and ``q_hi`` the greatest of its REAL ones (-1: the tile's
    queries are all padding lanes).  -> (unseen, plain): no real query
    of the tile sees a key of it; every query sees every key.  A tile
    that is neither is DIAGONAL: the edge crosses it.  (Numbers or
    arrays, on the host or in the kernel.)"""
    return k_lo > q_hi, (k_hi <= q_lo) & (k_lo <= q_hi)


def window_tiles(pos: int, n_q: int, w: int,
                 key_block: int = KEY_BLOCK) -> dict:
    """What ``latent_window_attention`` does with a window of ``w``
    lanes at positions ``pos ..`` whose first ``n_q`` queries are real,
    a head and layer: the tiles of each kind over the key blocks it
    walks, and the (query, key) pairs of the tiles it does not skip —
    the kernel's own classification (``_tile_kind``), on the host."""
    import numpy as np
    tile = WINDOW_TILE
    out = dict(unseen=0, plain=0, diagonal=0, pairs=0)
    if n_q <= 0:
        return out
    keys = np.asarray([(j * key_block + a, j * key_block + a + n - 1, n)
                       for j in range((pos + n_q - 1) // key_block + 1)
                       for a, n in _spans(key_block, tile[0])])
    for q0, tq in _spans(w, tile[1]):
        real = min(q0 + tq, n_q) - q0
        unseen, plain = _tile_kind(keys[:, 0], keys[:, 1], pos + q0,
                                   pos + q0 + real - 1 if real > 0 else -1)
        out["unseen"] += int(unseen.sum())
        out["plain"] += int(plain.sum())
        out["diagonal"] += int((~unseen & ~plain).sum())
        out["pairs"] += int(keys[~unseen, 2].sum()) * tq
    return out


def _attend_tiles(scores, vt_ref, k0, edge_ref, pos_ref, carry_in, carry_out,
                  *, n: int, w: int, tile, window: int = 0):
    """One head's window of ``w`` queries against one block of ``n``
    keys, the running softmax carried in (``carry_in``: m [1, w], l
    [1, w], acc [dv, w]) and out: the body the window kernels share.
    Everything is held TRANSPOSED — ``scores(ks, qs)`` gives the float32
    scores [keys, queries] of a tile, the values are ``vt_ref`` [dv,
    keys], the running output [dv, queries] — so that a query's maximum
    and sum are lane-dense rows [1, queries] and no product needs a
    transposed operand: the scores never leave VMEM.

    The scores are worked through in tiles of ``tile`` (keys, queries),
    and a tile is done by what its place demands (``_tile_kind``, from
    the block's first key position ``k0`` and ``edge_ref``: a query
    tile's least position and its real queries' greatest): UNSEEN — no
    product, the carry as it came; PLAIN — products, maximum, exp, sum
    and rescale with no mask; DIAGONAL — the same under the mask of
    each query's own position (``pos_ref`` [1, w]).  Where all of the
    block's tiles are plain for a tile of queries (every block but the
    last of a walk) they are done as one: one maximum and one rescale
    of the running output, not one a tile.

    ``window`` > 0: a query sees its last ``window`` keys only
    (``pos_ref`` [2, w]: below a query's position, the last key
    position it no longer sees).  A tile wholly behind its queries'
    windows is unseen too, and every other one is masked."""
    f32 = jnp.float32
    m_ref, l_ref, acc_ref = carry_in
    m_out, l_out, acc_out = carry_out

    def attend(ks, qs, k_lo):
        """The tile's update of its queries' carry; ``k_lo``: the tile's
        first key position where the causal edge crosses it."""
        s = scores(ks, qs)
        if k_lo is not None:
            key = lax.broadcasted_iota(jnp.int32, s.shape, 0)
            seen = key <= pos_ref[0:1, qs] - k_lo          # [keys, queries]
            if window:
                seen &= key > pos_ref[1:2, qs] - k_lo
            s = jnp.where(seen, s, _MASKED)
        m_prev = m_out[:, qs]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        if k_lo is not None:
            p = jnp.where(seen, p, 0.0)
        m_out[:, qs] = m_next
        l_out[:, qs] = l_out[:, qs] * alpha + jnp.sum(p, axis=0,
                                                      keepdims=True)
        acc_out[:, qs] = acc_out[:, qs] * alpha + jnp.dot(
            vt_ref[:, ks], p.astype(vt_ref.dtype),
            preferred_element_type=f32)

    def query_tile(t, qs):
        """Query tile ``t``, the window's lanes ``qs``, against the
        block."""
        m_out[:, qs], l_out[:, qs] = m_ref[:, qs], l_ref[:, qs]
        acc_out[:, qs] = acc_ref[:, qs]
        edge = edge_ref[2 * t], edge_ref[2 * t + 1]
        if window:
            for a, tk in _spans(n, tile[0]):
                k_lo, k_hi = k0 + a, k0 + a + tk - 1
                unseen, _ = _tile_kind(k_lo, k_hi, *edge)
                pl.when(jnp.logical_not(unseen | (k_hi <= edge[0] - window)))(
                    functools.partial(attend, slice(a, a + tk), qs, k_lo))
            return
        # a block of nothing but plain tiles is taken as ONE
        _, whole = _tile_kind(k0, k0 + n - 1, *edge)
        crossed = jnp.logical_not(whole)
        pl.when(whole)(functools.partial(attend, slice(0, n), qs, None))
        for a, tk in _spans(n, tile[0]):
            ks = slice(a, a + tk)
            unseen, plain = _tile_kind(k0 + a, k0 + a + tk - 1, *edge)
            pl.when(crossed & plain)(functools.partial(attend, ks, qs, None))
            pl.when(crossed & jnp.logical_not(unseen | plain))(
                functools.partial(attend, ks, qs, k0 + a))

    # the whole tiles in a loop (ONE copy of the code, whatever the
    # window's width), then what is left of the window
    tq = tile[1]
    full = w // tq
    if full:
        lax.fori_loop(0, full, lambda t, _: query_tile(
            t, pl.ds(pl.multiple_of(t * tq, tq), tq)), None)
    if w % tq:
        query_tile(full, slice(full * tq, w))


def _window_block_kernel(k0_ref, edge_ref, pos_ref, qn_ref, qr_ref, kn_ref,
                         kr_ref, vt_ref, m_ref, l_ref, acc_ref, m_out, l_out,
                         acc_out, *, tile):
    """One head's window of queries (scaled) against one block of
    decompressed keys, a key the sum of its two parts' products
    (``_attend_tiles``)."""
    nt = (((1,), (1,)), ((), ()))

    def scores(ks, qs):
        return (lax.dot_general(kn_ref[ks, :], qn_ref[qs, :], nt,
                                preferred_element_type=jnp.float32)
                + lax.dot_general(kr_ref[ks, :], qr_ref[qs, :], nt,
                                  preferred_element_type=jnp.float32))

    _attend_tiles(scores, vt_ref, k0_ref[0], edge_ref, pos_ref,
                  (m_ref, l_ref, acc_ref), (m_out, l_out, acc_out),
                  n=kn_ref.shape[0], w=qn_ref.shape[0], tile=tile)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _window_block(qn, qr, k_nope, k_rope, v_t, pos, edges, k0, carry, *,
                  tile, interpret):
    """``carry`` (m [h, 1, w], l [h, 1, w], acc [h, dv, w]) advanced by
    one block of keys: one grid step a head, the carry updated in
    place.  Jitted, so that a program's equal layers trace and lower
    the kernel ONCE (XLA inlines the calls): traced a layer each, a
    copy of the code a query tile, it added 3-5 s to the chunk
    program's bring-up from a warm compile cache; so, and with the
    query tiles in a loop, 0.9 (my chip runs, PR 49)."""
    from jax.experimental.pallas import tpu as pltpu

    h, w, dn = qn.shape
    dr, n, dv = qr.shape[-1], k_nope.shape[1], v_t.shape[1]

    def per_head(*shape):
        return pl.BlockSpec((None, *shape), lambda i: (i, 0, 0))

    def shared(*shape):
        return pl.BlockSpec(shape, lambda i: (0, 0))

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    stats = [per_head(1, w), per_head(1, w), per_head(dv, w)]
    return pl.pallas_call(
        functools.partial(_window_block_kernel, tile=tile),
        grid=(h,),
        in_specs=[smem, smem, shared(1, w), per_head(w, dn), per_head(w, dr),
                  per_head(n, dn), shared(n, dr), per_head(dv, n)] + stats,
        out_specs=stats,
        out_shape=[jax.ShapeDtypeStruct(c.shape, c.dtype) for c in carry],
        input_output_aliases={8: 0, 9: 1, 10: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="latent_window_attention",
    )(jnp.asarray(k0, jnp.int32)[None], edges, pos, qn, qr, k_nope, k_rope,
      v_t, *carry)


def _tile_edges(pos, real, tq: int):
    """What ``_attend_tiles`` places a tile of queries by, [2 x tiles]
    int32: a query tile's least position and its real queries' greatest
    (``real``: ``real_positions``)."""
    return jnp.stack([reach(x[q0:q0 + n])
                      for q0, n in _spans(pos.shape[0], tq)
                      for reach, x in ((jnp.min, pos), (jnp.max, real))])


def real_positions(q_pos, n_valid=None):
    """``q_pos`` [w] with the padding lanes (those from ``n_valid`` on;
    None: there are none) at -1, before every key."""
    q_pos = q_pos.astype(jnp.int32)
    if n_valid is None:
        return q_pos
    return jnp.where(jnp.arange(q_pos.shape[0]) < n_valid, q_pos, -1)


def latent_window_attention(q_nope, q_rope, read_keys, w_uk, w_uv, q_pos, *,
                            scale: float, key_block: int = KEY_BLOCK,
                            n_blocks=None, n_valid=None):
    """ONE row's window of queries over the row's cached latents, the
    keys decompressed ``key_block`` at a time under a running softmax.

    q_nope    [w, h, dn], q_rope [w, h, dr] (rotated)
    read_keys ``(j, n) -> [n, >= kv_rank + dr]``: the latents of key
              positions ``j * n .. (j + 1) * n`` (what lies past the
              row's last key may be anything)
    w_uk      [h, kv_rank, dn], w_uv [h, kv_rank, dv]
    q_pos     [w] int32: a query attends the keys at positions <= its
              own (key 0 is every query's)
    n_blocks  key blocks walked; None: those that hold a key of the
              window's last real query (a traced count, a ``while``
              loop)
    n_valid   the window's first ``n_valid`` queries are real, the rest
              padding lanes whose output is finite and nothing more;
              None: all are real
    -> [w, h * dv]

    A block is decompressed ONCE for the whole window (``c_kv W_uk``,
    ``c_kv W_uv``: the compiler's products) and attended in one Pallas
    kernel, a grid step a head, whose scores stay in VMEM
    (``_window_block_kernel``): as a plain ``lax`` loop the float32
    scores ``[h, w, keys]`` went through HBM four times a block and the
    form ran at 17 % of its roofline (PR 42, on the chip).  The kernel
    takes its scores a tile at a time and masks only the tiles the
    causal edge crosses; the tiles past every real query of theirs it
    skips.  The softmax scale is folded into the queries, once.  No
    array here has more than ``key_block`` keys, whatever the row's
    length."""
    from ray_tpu.ops.flash_attention import _interpret_mode

    f32 = jnp.float32
    w, h, dn = q_nope.shape
    dr, (_, kv_rank, dv) = q_rope.shape[-1], w_uv.shape
    tile = WINDOW_TILE
    pos = q_pos.astype(jnp.int32)
    real = real_positions(q_pos, n_valid)
    last = jnp.max(real)
    if n_blocks is None:
        n_blocks = last // key_block + 1
    qn, qr = ((q.transpose(1, 0, 2).astype(f32) * scale).astype(q.dtype)
              for q in (q_nope, q_rope))
    edges = _tile_edges(pos, real, tile[1])

    def body(j, carry):
        lat = read_keys(j, key_block)
        k_pos = j * key_block + jnp.arange(key_block, dtype=jnp.int32)
        # 0 x NaN is NaN: what no query of the window may see is zeroed
        lat = jnp.where((k_pos <= last)[:, None], lat, jnp.zeros_like(lat))
        c = lat[:, :kv_rank]
        return _window_block(
            qn, qr, jnp.einsum("kc,hcd->hkd", c, w_uk),
            lat[:, kv_rank:kv_rank + dr], jnp.einsum("kc,hcd->hdk", c, w_uv),
            pos[None, :], edges, j * key_block, carry, tile=tile,
            interpret=_interpret_mode())

    init = (jnp.full((h, 1, w), _MASKED, f32), jnp.zeros((h, 1, w), f32),
            jnp.zeros((h, dv, w), f32))
    _, l, acc = lax.fori_loop(0, n_blocks, body, init)
    # a padding lane in a tile of nothing else was never visited: l 0
    o = (acc / jnp.where(l > 0, l, 1.0)).astype(q_nope.dtype)  # [h, dv, w]
    return o.transpose(2, 0, 1).reshape(w, h * dv)


def _head_block_kernel(k0_ref, edge_ref, pos_ref, q_ref, k_ref, vt_ref, m_ref,
                       l_ref, acc_ref, m_out, l_out, acc_out, *, scale: float,
                       tile, window: int):
    """``_window_block_kernel`` for keys that are stored a head's lanes
    each: one head's window of queries against one block of its K/V
    head's keys (``_attend_tiles``; the scores scaled in float32, as
    ``packed_attention`` scales them)."""
    def scores(ks, qs):
        return lax.dot_general(k_ref[ks, :], q_ref[qs, :],
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32) * scale

    _attend_tiles(scores, vt_ref, k0_ref[0], edge_ref, pos_ref,
                  (m_ref, l_ref, acc_ref), (m_out, l_out, acc_out),
                  n=k_ref.shape[0], w=q_ref.shape[0], tile=tile,
                  window=window)


@functools.partial(jax.jit, static_argnames=("rep", "scale", "tile", "window",
                                             "interpret"))
def _head_block(q, k, v_t, pos, edges, k0, carry, *, rep, scale, tile,
                window, interpret):
    """``carry`` (m [h, 1, w], l [h, 1, w], acc [h, hd, w]) advanced by
    one block of keys: one grid step a query head, the carry updated in
    place; the ``rep`` query heads of a K/V head follow one another, so
    its block is read once.  ``k``: the block as stored, [n, >= kv x
    hd], where a head's lanes are whole tiles, or a head each, [kv, n,
    hd].  (Jitted as ``_window_block`` is: the layers lower the kernel
    once.)"""
    from jax.experimental.pallas import tpu as pltpu

    h, w, hd = q.shape
    n = v_t.shape[-1]

    def per_head(*shape):
        return pl.BlockSpec((None, *shape), lambda i: (i, 0, 0))

    k_spec = (pl.BlockSpec((n, hd), lambda i: (0, i // rep)) if k.ndim == 2
              else pl.BlockSpec((None, n, hd), lambda i: (i // rep, 0, 0)))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    stats = [per_head(1, w), per_head(1, w), per_head(hd, w)]
    return pl.pallas_call(
        functools.partial(_head_block_kernel, scale=scale, tile=tile,
                          window=window),
        grid=(h,),
        in_specs=[smem, smem, pl.BlockSpec(pos.shape, lambda i: (0, 0)),
                  per_head(w, hd), k_spec,
                  pl.BlockSpec((None, hd, n), lambda i: (i // rep, 0, 0))]
        + stats,
        out_specs=stats,
        out_shape=[jax.ShapeDtypeStruct(c.shape, c.dtype) for c in carry],
        input_output_aliases={6: 0, 7: 1, 8: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="head_window_attention",
    )(jnp.asarray(k0, jnp.int32)[None], edges, pos, q, k, v_t, *carry)


def head_window_attention(q, read_keys, q_pos, *, n_kv_heads: int,
                          scale: float, key_block: int = KEY_BLOCK,
                          n_blocks=None, window: int = 0, n_valid=None):
    """ONE row's window of queries over the row's cached K/V, head by
    head, ``key_block`` keys at a time under a running softmax.

    q         [h, w, hd]
    read_keys ``(j, n) -> (k, v)`` each [n, >= n_kv_heads * hd], as the
              pool stores them (a token's heads side by side): the keys
              at positions ``j * n .. (j + 1) * n`` (what lies past the
              row's last key may be anything)
    q_pos     [w] int32: a query attends the keys at positions <= its
              own (key 0 is every query's)
    n_blocks  key blocks walked; None: those that hold a key of the
              window's last real query
    window    > 0: a query attends its last ``window`` keys only (its
              own among them), and the walk starts at the block that
              holds the first key of the window's FIRST query: the
              work is bounded by ``window + w`` keys, whatever the
              row's length (what ``read_keys`` gives for the blocks
              before may be anything)
    n_valid   the window's first ``n_valid`` queries are real, the rest
              padding lanes that attend nothing (their output is
              finite and nothing more); None: all are real
    -> [h, w, hd]

    Each query head multiplies its own K/V head's lanes only (``h /
    n_kv_heads`` consecutive query heads share one, and one read of its
    block), where ``packed_attention`` multiplies the full stored width
    a head — ``n_kv_heads`` x the arithmetic — and holds a float32
    score array of the whole table.  One Pallas kernel a block, a grid
    step a head, the scores in VMEM a tile at a time and the tiles no
    real query sees skipped (``latent_window_attention``'s walk and
    ``_attend_tiles``' body); the values of a block are transposed
    once, [n_kv_heads, hd, n].  Where a head is whole lane tiles (``hd``
    a multiple of 128) its keys ARE a tile-aligned slice of the stored
    block; narrower heads' keys are re-laid beside the values, a head
    each [n_kv_heads, n, hd], and contracted over their ``hd`` lanes."""
    from ray_tpu.ops.flash_attention import _interpret_mode

    f32 = jnp.float32
    h, w, hd = q.shape
    kv, n = n_kv_heads, key_block
    real = real_positions(q_pos, n_valid)
    last = jnp.max(real)
    if n_blocks is None:
        n_blocks = last // n + 1
    edges = _tile_edges(q_pos.astype(jnp.int32), real, HEAD_TILE[1])
    # the padding lanes lie before every key: masked wherever a mask is
    pos = real[None, :]
    first, j0 = 0, 0
    if window:
        pos = jnp.concatenate([pos, pos - window])
        first = jnp.maximum(jnp.min(q_pos) - window + 1, 0)
        j0 = first // n

    def body(j, carry):
        k, v = read_keys(j, n)
        k_pos = j * n + jnp.arange(n, dtype=jnp.int32)
        # 0 x NaN is NaN: what no query of the window may see is zeroed
        seen = k_pos <= last
        if window:
            seen &= k_pos >= first
        v = jnp.where(seen[:, None], v, jnp.zeros_like(v))
        v_t = v[:, :kv * hd].reshape(n, kv, hd).transpose(1, 2, 0)
        if hd % 128:
            k = k[:, :kv * hd].reshape(n, kv, hd).transpose(1, 0, 2)
        return tuple(_head_block(
            q, k, v_t, pos, edges, j * n, carry, rep=h // kv, scale=scale,
            tile=HEAD_TILE, window=window, interpret=_interpret_mode()))

    init = (jnp.full((h, 1, w), _MASKED, f32), jnp.zeros((h, 1, w), f32),
            jnp.zeros((h, hd, w), f32))
    _, l, acc = lax.fori_loop(j0, n_blocks, body, init)
    # a padding lane in a tile of nothing else was never visited: l 0
    o = acc / jnp.where(l > 0, l, 1.0)
    return o.astype(q.dtype).transpose(0, 2, 1)


# VMEM the latent kernel's wave buffers take (ONE pool, double
# buffered): 64 blocks of 16 x 640 bf16
LATENT_WAVE_BYTES = 4 << 20


def _latent_decode_kernel(base_ref, len_ref, tab_ref, q_ref, pool_hbm, o_ref,
                          rows_ref, buf, sem, acc_ref, *, wave: int,
                          n_table: int, value_lanes: int, scale: float):
    """``_decode_kernel``'s walk (live rows listed first; wave w + 1, or
    the next live row's first, on its way into one half of the buffer
    while wave w is attended in the other) over ONE pool whose every
    token is one key for ALL heads: a row's [heads, W] queries times the
    wave's [tokens, W] keys is a plain MXU product, and the values are
    the same tile's first ``value_lanes`` lanes."""
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    bs = buf.shape[1] // wave
    tokens = wave * bs
    base = base_ref[0]

    count = _list_live_rows(len_ref, rows_ref)

    def each_copy(row, w, half, do):
        def one(i, _):
            at = pl.ds(pl.multiple_of(i * bs, bs), bs)
            src = base + tab_ref[row * n_table + w * wave + i]
            do(pltpu.make_async_copy(pool_hbm.at[src], buf.at[half, at],
                                     sem.at[half]))
        # blocks of the row's wave w that hold a key: 0 .. wave
        lax.fori_loop(0, jnp.clip(pl.cdiv(len_ref[row], bs) - w * wave,
                                  0, wave), one, None)

    def start(row, w, half):
        each_copy(row, w, half, lambda c: c.start())

    def wait(row, w, half):
        each_copy(row, w, half, lambda c: c.wait())

    @pl.when(count > 0)
    def _():
        start(rows_ref[0], 0, 0)

    def row_body(slot, done):
        row = rows_ref[slot]
        kv_len = len_ref[row]
        n_waves = pl.cdiv(kv_len, tokens)
        q = q_ref[row]                                      # [heads, W]
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def wave_body(w, carry):
            m_prev, l_prev = carry
            half = (done + w) % 2
            more = w + 1 < n_waves

            @pl.when(more | (slot + 1 < count))
            def _():
                start(jnp.where(more, row, rows_ref[
                    jnp.minimum(slot + 1, rows_ref.shape[0] - 1)]),
                    jnp.where(more, w + 1, 0), 1 - half)

            wait(row, w, half)

            # a key past kv_len weighs exactly 0, and what lies there (a
            # block not copied, a block's unwritten tail) may be
            # anything: 0 x NaN is NaN
            @pl.when((w + 1) * tokens > kv_len)
            def _():
                pos = w * tokens + lax.broadcasted_iota(
                    jnp.int32, buf.shape[1:], 0)
                k = buf[half]
                buf[half] = jnp.where(pos < kv_len, k, jnp.zeros_like(k))

            s = lax.dot_general(
                q, buf[half], (((1,), (1,)), ((), ())),
                preferred_element_type=f32) * scale     # [heads, tokens]
            key = w * tokens + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(key < kv_len, s, -jnp.inf)
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(s - m_next)
            acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
                p.astype(buf.dtype), buf[half, :, :value_lanes],
                preferred_element_type=f32)
            return m_next, l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)

        heads = q.shape[0]
        _, l = lax.fori_loop(
            0, n_waves, wave_body,
            (jnp.full((heads, 1), -jnp.inf, f32), jnp.zeros((heads, 1), f32)))
        o_ref[row] = (acc_ref[...] / l).astype(o_ref.dtype)
        return done + n_waves

    lax.fori_loop(0, count, row_body, 0)


def latent_decode_attention(q, pool, base, tables, kv_lengths, *,
                            value_lanes: int,
                            scale: float) -> jax.Array:
    """One token a row over the latent pool AS STORED, reading only the
    blocks a row holds: ``paged_decode_attention`` for a pool whose
    token is ONE key for all heads and whose values are the key's first
    ``value_lanes`` lanes.

    q           [b, h, W]: a head's absorbed query ``[q_nope W_uk^T |
                q_rope]`` in the pool's lanes, zeros in its padding
    pool        [rows, bs, W] (inference/cache.PoolLayout, one head)
    base        int32 scalar: the pool's row of this layer's block 0
    tables      [b, T] int32; kv_lengths [b] int32 (0: the row sits out)
    -> [b, h, value_lanes] (``P c_kv``; the caller multiplies by W_uv);
       a row that sits out gets zeros."""
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.ops.flash_attention import _interpret_mode

    b, h, width = q.shape
    n_table = tables.shape[1]
    _, bs, _ = pool.shape
    item = pool.dtype.itemsize
    wave = max(1, LATENT_WAVE_BYTES // (2 * bs * width * item))
    wave = min(1 << (wave.bit_length() - 1), n_table)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    # every row's queries and outputs stay in VMEM beside the waves
    need = (b * h * (width + value_lanes) * item
            + 2 * wave * bs * width * item
            + h * (value_lanes + 3 * wave * bs) * 4)
    out = pl.pallas_call(
        functools.partial(_latent_decode_kernel, wave=wave, n_table=n_table,
                          value_lanes=value_lanes, scale=scale),
        in_specs=[smem] * 3 + [vmem, hbm],
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct((b, h, value_lanes), pool.dtype),
        scratch_shapes=[
            pltpu.SMEM((b,), jnp.int32),
            pltpu.VMEM((2, wave * bs, width), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((h, value_lanes), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(32 << 20, 2 * need)),
        interpret=_interpret_mode(),
        name="latent_decode_attention",
    )(jnp.asarray(base, jnp.int32)[None], kv_lengths.astype(jnp.int32),
      tables.reshape(-1), q.astype(pool.dtype), pool)
    return jnp.where((kv_lengths > 0)[:, None, None], out,
                     jnp.zeros_like(out))


def on_tpu() -> bool:
    """THE definition of "this process computes on a TPU" for kernel
    dispatch (flash vs reference) and interpret-mode selection.  A
    broken backend raises here instead of quietly answering "no"."""
    return jax.default_backend() == "tpu"


def _per_shard(fn, mesh, in_specs, out_specs):
    """Mosaic kernels cannot be partitioned by GSPMD, so under a
    multi-device mesh a kernel runs per shard inside shard_map: manual
    over every mesh axis not already manual in the enclosing context
    (the pp pipeline binds ``pp`` itself), its operands and result laid
    out by ``in_specs`` / ``out_specs``."""
    if mesh is None or mesh.size == 1:
        return fn
    ctx = jax.sharding.get_abstract_mesh()
    bound = set(ctx.manual_axes)
    free = frozenset(a for a in mesh.axis_names if a not in bound)
    if not free:
        return fn
    # nested under a manual axis, shard_map must be given the context's
    # own (abstract) mesh: the concrete Mesh no longer matches it
    return jax.shard_map(fn, mesh=ctx if bound else mesh, axis_names=free,
                         in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


def attention(q, k, v, *, causal: bool = True,
              scale: Optional[float] = None,
              mask: Optional[jax.Array] = None,
              kv_lengths: Optional[jax.Array] = None,
              impl: Optional[str] = None,
              block_q: int = 512, block_k: int = 512,
              mesh: Optional[Mesh] = None,
              spec: Optional[PartitionSpec] = None) -> jax.Array:
    """Dispatching multi-head attention, [batch, heads, seq, head_dim].

    impl: "flash" (pallas TPU kernel), "reference", or None = auto
    (flash on TPU when shapes are tile-friendly and there is no custom
    mask or per-row kv_lengths, reference otherwise).  ``kv_lengths``
    [b] limits each batch row to its own valid kv prefix (slot-batched
    decode; see mha_reference).

    ``mesh`` + ``spec`` (the PartitionSpec of q/k/v on that mesh, seq
    unsharded): the flash kernel then runs per shard under shard_map —
    the reference and xla_fused impls are plain XLA and partition on
    their own.
    """
    from ray_tpu.ops.flash_attention import flash_attention

    if impl is None:
        tile_ok = (q.shape[-2] % 128 == 0 and k.shape[-2] % 128 == 0
                   and q.shape[-1] in (64, 128, 256))
        impl = ("flash" if on_tpu() and tile_ok and mask is None
                and kv_lengths is None
                else "reference")
    if impl == "flash":
        if mask is not None or kv_lengths is not None:
            raise ValueError(
                "flash impl has no custom-mask / kv_lengths support; use "
                "impl='reference' (causal masking is built in)")
        fn = functools.partial(flash_attention, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k)
        if mesh is not None and mesh.size > 1:
            if spec is None:
                raise ValueError("flash attention under a mesh needs the "
                                 "[batch, heads, seq, kv] PartitionSpec")
            if len(spec) > 2 and spec[2] is not None:
                raise ValueError(
                    f"flash attention needs whole sequences per shard, got "
                    f"seq sharded over {spec[2]!r}; use ring attention for "
                    f"sp meshes")
        return _per_shard(fn, mesh, (spec, spec, spec), spec)(q, k, v)
    if impl == "reference":
        return mha_reference(q, k, v, causal=causal, scale=scale, mask=mask,
                             kv_lengths=kv_lengths)
    if impl == "xla_fused":
        # XLA's own fused attention path (jax.nn.dot_product_attention,
        # [b, s, h, d] layout)
        if mask is not None or kv_lengths is not None:
            raise ValueError("xla_fused impl has no custom-mask / "
                             "kv_lengths support")
        out = jax.nn.dot_product_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), scale=scale, is_causal=causal)
        return out.transpose(0, 2, 1, 3)
    raise ValueError(f"unknown attention impl {impl!r}")
