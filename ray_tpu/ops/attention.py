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


def _decode_kernel(base_ref, len_ref, tab_ref, q_ref, k_hbm, v_hbm, o_ref,
                   rows_ref, k_buf, v_buf, sem, qx_ref, acc_ref, *,
                   wave: int, n_table: int, n_kv: int, head_dim: int,
                   scale: float):
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
    its own head's."""
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    bs, width = k_buf.shape[1] // wave, k_buf.shape[2]
    tokens = wave * bs
    reps, gp = q_ref.shape[1], qx_ref.shape[0] // q_ref.shape[1]
    base = base_ref[0]

    def list_live(row, n):
        live = len_ref[row] > 0

        @pl.when(live)
        def _():
            rows_ref[n] = row
        return n + live.astype(jnp.int32)

    count = lax.fori_loop(0, rows_ref.shape[0], list_live, 0)

    def blocks_of(row, w):
        """Blocks of the row's wave w that hold a key: 0 .. wave."""
        return jnp.clip(pl.cdiv(len_ref[row], bs) - w * wave, 0, wave)

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
        lax.fori_loop(0, blocks_of(row, w), one, None)

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
        start(rows_ref[0], 0, 0)

    def row_body(slot, done):
        """``done``: waves attended so far, whose parity says which
        half of the buffers this row's first wave is arriving in."""
        row = rows_ref[slot]
        kv_len = len_ref[row]
        n_waves = pl.cdiv(kv_len, tokens)
        q = q_ref[row]                                          # [R, W]
        for r in range(reps):
            qx_ref[r * gp:(r + 1) * gp, :] = jnp.where(
                own(), q[r:r + 1, :], 0.0).astype(qx_ref.dtype)
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
            s = lax.dot_general(
                qx_ref[...], k_buf[half], (((1,), (1,)), ((), ())),
                preferred_element_type=f32) * scale     # [heads, tokens]
            key = w * tokens + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(key < kv_len, s, -jnp.inf)
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(s - m_next)

            # a key past kv_len weighs exactly 0, and what lies there
            # (a block not copied, a block's unwritten tail) may be
            # anything: 0 x NaN is NaN
            @pl.when((w + 1) * tokens > kv_len)
            def _():
                pos = w * tokens + lax.broadcasted_iota(
                    jnp.int32, (tokens, width), 0)
                v = v_buf[half]
                v_buf[half] = jnp.where(pos < kv_len, v, jnp.zeros_like(v))

            acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
                p.astype(v_buf.dtype), v_buf[half],
                preferred_element_type=f32)
            return m_next, l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)

        heads = qx_ref.shape[0]
        _, l = lax.fori_loop(
            0, n_waves, wave_body,
            (jnp.full((heads, 1), -jnp.inf, f32), jnp.zeros((heads, 1), f32)))
        out = acc_ref[...] / l                                  # [heads, W]
        for r in range(reps):
            o_ref[row, r:r + 1, :] = jnp.sum(
                jnp.where(own(), out[r * gp:(r + 1) * gp], 0.0),
                axis=0, keepdims=True)
        return done + n_waves

    lax.fori_loop(0, count, row_body, 0)


def paged_decode_attention(q, k_pool, v_pool, base, tables, kv_lengths, *,
                           q_per_kv: int = 1,
                           scale: Optional[float] = None) -> jax.Array:
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
                          scale=_scale_for(q, scale)),
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
              spec: Optional[PartitionSpec] = None,
              save_lse: bool = False) -> jax.Array:
    """Dispatching multi-head attention, [batch, heads, seq, head_dim].

    impl: "flash" (pallas TPU kernel), "reference", or None = auto
    (flash on TPU when shapes are tile-friendly and there is no custom
    mask or per-row kv_lengths, reference otherwise).  ``kv_lengths``
    [b] limits each batch row to its own valid kv prefix (slot-batched
    decode; see mha_reference).

    ``mesh`` + ``spec`` (the PartitionSpec of q/k/v on that mesh, seq
    unsharded): the flash kernel then runs per shard under shard_map —
    the reference and xla_fused impls are plain XLA and partition on
    their own.  ``save_lse`` picks the lse-exposing flash variant whose
    named outputs a ``dots_flash`` checkpoint policy saves.
    """
    from ray_tpu.ops.flash_attention import (flash_attention,
                                             flash_attention_with_lse)

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
        kw = dict(causal=causal, scale=scale, block_q=block_q,
                  block_k=block_k)
        if save_lse:
            # the lse itself is only a checkpoint-policy save target
            # (named inside the kernel's vjp); callers get ``out``
            def fn(q, k, v):
                return flash_attention_with_lse(q, k, v, **kw)[0]
        else:
            fn = functools.partial(flash_attention, **kw)
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
