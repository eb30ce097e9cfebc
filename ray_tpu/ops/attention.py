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


def on_tpu() -> bool:
    """THE definition of "this process computes on a TPU" for kernel
    dispatch (flash vs reference) and interpret-mode selection.  A
    broken backend raises here instead of quietly answering "no"."""
    return jax.default_backend() == "tpu"


def _per_shard(fn, mesh, spec):
    """Mosaic kernels cannot be partitioned by GSPMD, so under a
    multi-device mesh the flash call runs per shard inside shard_map:
    manual over every mesh axis not already manual in the enclosing
    context (the pp pipeline binds ``pp`` itself), q/k/v and the output
    laid out by ``spec`` — the mesh axes that shard batch and heads."""
    if mesh is None or mesh.size == 1:
        return fn
    if spec is None:
        raise ValueError("flash attention under a mesh needs the "
                         "[batch, heads, seq, kv] PartitionSpec")
    if len(spec) > 2 and spec[2] is not None:
        raise ValueError(
            f"flash attention needs whole sequences per shard, got seq "
            f"sharded over {spec[2]!r}; use ring attention for sp meshes")
    ctx = jax.sharding.get_abstract_mesh()
    bound = set(ctx.manual_axes)
    free = frozenset(a for a in mesh.axis_names if a not in bound)
    if not free:
        return fn
    # nested under a manual axis, shard_map must be given the context's
    # own (abstract) mesh: the concrete Mesh no longer matches it
    return jax.shard_map(fn, mesh=ctx if bound else mesh, axis_names=free,
                         in_specs=(spec, spec, spec), out_specs=spec,
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
        return _per_shard(fn, mesh, spec)(q, k, v)
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
