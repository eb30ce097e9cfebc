"""Pallas TPU flash attention (block-wise, online softmax).

Forward is a pallas kernel: one grid step per (batch·head, q-block); the
kv stream for that head is processed in VMEM-resident blocks with an
online-softmax carry, so the O(s²) score matrix never touches HBM and the
matmuls stay MXU-shaped ([block_q × d] @ [d × block_k]).  Causal masking
prunes the kv loop to the lower triangle.

Backward is a custom VJP that recomputes probabilities block-by-block from
the saved logsumexp (the standard flash trade: extra FLOPs for O(s·block)
memory).  What the forward rule keeps for it is q, k, v, the output and
ONE float32 a query row of logsumexp, the last two named ``flash_out`` /
``flash_lse`` for a ``jax.checkpoint`` policy to keep (models/gpt.py,
remat_policy="dots"): a layer rematerialised under such a policy then
runs no forward kernel in its backward pass.  On block-aligned shapes it
runs as two fused pallas kernels — one grid pass over kv blocks producing
dk/dv, one over q blocks producing dq — with bf16 matmul operands and f32
accumulation; ragged shapes fall back to a plain-jax scan that XLA fuses.

Reference capability context: the reference framework has no fused
attention of its own (it rides torch/CUDA kernels); this is the TPU-native
equivalent of that dependency, per SURVEY.md §7's "pallas kernels for the
hot ops".
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

NEG_INF = -1e30
# Inside a kernel lse/delta ride broadcast across one full lane register,
# the same convention as jax's reference TPU flash kernel (MIN_BLOCK_SIZE
# lanes): scalar-per-row vectors are awkward on the VPU, a [rows, 128]
# tile is not.  In HBM they are ONE float32 a row, [b*h, 1, seq]: the
# kernels turn a [1, rows] block into the tile and back (a transpose on
# the XLU), so neither the residual a layer keeps nor what the backward
# kernels stream a grid step is 128 copies of a number.
LANES = 128


def _lanes_to_row(col):
    """[rows, 1] -> [1, rows]: a per-row statistic turned along the
    lanes, the form it is stored in (one float32 a row, not 128)."""
    rows = col.shape[0]
    return jax.lax.broadcast_in_dim(col[:, 0], (rows, LANES), (0,)).T[:1, :]


def _row_to_lanes(row_ref):
    """[1, rows] ref -> [rows, LANES]: the stored row turned back into
    the column the [rows, block_k] score tiles subtract, broadcast over
    one lane register (``jnp.tile`` carries it across the rest)."""
    rows = row_ref.shape[1]
    return jnp.broadcast_to(row_ref[...], (LANES, rows)).T


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *maybe_lse,
                scale: float, causal: bool, block_k: int, kv_len: int,
                q_len: int):
    qi = pl.program_id(1)
    block_q, d = q_ref.shape

    q = q_ref[...].astype(jnp.float32)  # [bq, d]
    # global key position of each q row's diagonal: cross-length causal
    # (decode with kv cache) puts q at the TAIL of the kv sequence, same
    # convention as mha_reference's (k_len - q_len) offset
    q_offset = qi * block_q + (kv_len - q_len)
    # k_ref/v_ref are zero-padded to a block multiple by the caller; the
    # padded columns are masked below (col >= kv_len)
    ragged = kv_len % block_k != 0

    num_kv_blocks = pl.cdiv(kv_len, block_k)
    if causal:
        # kv blocks strictly above the diagonal contribute nothing
        last_needed = jnp.minimum(
            (q_offset + block_q + block_k - 1) // block_k, num_kv_blocks)
    else:
        last_needed = num_kv_blocks

    def body(j, carry):
        acc, m_prev, l_prev = carry
        k = k_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        col = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if ragged:
            s = jnp.where(col < kv_len, s, NEG_INF)
        if causal:
            row = q_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            s = jnp.where(row >= col, s, NEG_INF)
        m_cur = jnp.max(s, axis=1, keepdims=True)  # [bq, 1]
        m_next = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)  # [bq, bk]
        l_next = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)
        return acc, m_next, l_next

    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, last_needed, body, (acc0, m0, l0))

    l = jnp.maximum(l, 1e-30)
    o_ref[...] = (acc / l).astype(o_ref.dtype)
    if maybe_lse:
        # training path only: inference skips the extra HBM write (the
        # pallas body is opaque to XLA, so an unused output would not be
        # dead-code-eliminated)
        l_ref, = maybe_lse
        l_ref[...] = _lanes_to_row(m + jnp.log(l))   # [1, bq]


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, need_lse=False):
    b, h, sq, d = q.shape
    kv_len = k.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, kv_len)
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, kv_len, d)
    vf = v.reshape(b * h, kv_len, d)
    kv_pad = (-kv_len) % block_k
    if kv_pad:
        # zero-pad ragged kv to a block multiple; kernel masks col>=kv_len
        # (in-kernel ds clamping is not portable: interpret mode returns
        # zeros for out-of-bounds rows instead of clamping the start)
        kf = jnp.pad(kf, ((0, 0), (0, kv_pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, kv_pad), (0, 0)))

    grid = (b * h, pl.cdiv(sq, block_q))
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_k=block_k, kv_len=kv_len, q_len=sq)
    o_spec = pl.BlockSpec((None, block_q, d), lambda bh, qi: (bh, qi, 0))
    o_shape = jax.ShapeDtypeStruct((b * h, sq, d), q.dtype)
    lse_spec = pl.BlockSpec((None, 1, block_q), lambda bh, qi: (bh, 0, qi))
    lse_shape = jax.ShapeDtypeStruct((b * h, 1, sq), jnp.float32)
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((None, kv_len + kv_pad, d), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((None, kv_len + kv_pad, d), lambda bh, qi: (bh, 0, 0)),
        ],
        out_specs=[o_spec, lse_spec] if need_lse else [o_spec],
        out_shape=[o_shape, lse_shape] if need_lse else [o_shape],
        interpret=_interpret_mode(),
        name="flash_fwd",
    )(qf, kf, vf)
    out = res[0].reshape(b, h, sq, d)
    return (out, res[1][:, 0]) if need_lse else (out, None)


def _interpret_mode() -> bool:
    """Compile for the TPU; interpret only on the CPU platform (the
    tests' path).  Any other backend has no lowering for these kernels
    and must not be served by the interpreter in silence."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise NotImplementedError(
        f"pallas flash attention targets TPU (interpreted on CPU for "
        f"tests); backend {backend!r} is unsupported")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, scale, causal, block_q, block_k):
    s = (q.shape[-1] ** -0.5) if scale is None else scale
    return _flash_fwd(q, k, v, s, causal, block_q, block_k)[0]


def flash_attention(q, k, v, *, scale: Optional[float] = None,
                    causal: bool = True, block_q: int = 512,
                    block_k: int = 512):
    """Fused attention, [batch, heads, seq, head_dim] layout."""
    return _flash(q, k, v, scale, causal, block_q, block_k)


def _fwd_rule(q, k, v, scale, causal, block_q, block_k):
    s = (q.shape[-1] ** -0.5) if scale is None else scale
    out, lse = _flash_fwd(q, k, v, s, causal, block_q, block_k,
                          need_lse=True)
    # the two residuals the forward made itself are named, which costs
    # nothing where no checkpoint policy reads the names.  The primal
    # output IS the named value, so nothing downstream asks the kernel
    # for it again.
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")               # [b*h, sq] f32
    return out, (q, k, v, out, lse)


def _recompute_p_ds(qj, doj, k, v, lse, delta, row0, col0, scale, causal):
    """Shared backward recompute: probabilities p from the saved lse and
    the softmax-jacobian product ds, for one (q block, kv block) pair.
    row0/col0 are the blocks' global offsets (row0 includes the causal
    diagonal offset).  Returns (p f32, ds in model dtype, both [bq, bk])."""
    block_q, block_k = qj.shape[0], k.shape[0]
    lanes_rep = block_k // LANES
    s = jax.lax.dot_general(
        qj, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # [bq, bk]
    if causal:
        row = row0 + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        col = col0 + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(row >= col, s, NEG_INF)
    p = jnp.exp(s - jnp.tile(lse, (1, lanes_rep)))       # [bq, bk] f32
    # dp = do @ vᵀ
    dp = jax.lax.dot_general(
        doj, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # [bq, bk]
    ds = (p * (dp - jnp.tile(delta, (1, lanes_rep)))
          * scale).astype(qj.dtype)
    return p, ds


def _bwd_kv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                   dk_ref, dv_ref, dk_acc, dv_acc, *,
                   scale: float, causal: bool, nq: int,
                   q_len: int, kv_len: int):
    """Grid (bh, kv-block, q-block): the innermost q dimension streams one
    [block_q, d] slice of q/do/lse/delta per step (VMEM stays O(block),
    independent of sequence length), accumulating dk/dv for the resident
    kv block in f32 VMEM scratch, flushed on the last q step."""
    ki = pl.program_id(1)
    j = pl.program_id(2)
    block_k = k_ref.shape[0]
    block_q = q_ref.shape[0]
    off = kv_len - q_len

    @pl.when(j == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # causal: skip q blocks fully above the diagonal for this kv block
    live = (j * block_q + off + block_q - 1 >= ki * block_k) \
        if causal else (j >= 0)

    @pl.when(live)
    def _accumulate():
        qj = q_ref[...]       # [bq, d] model dtype
        doj = do_ref[...]
        p, ds = _recompute_p_ds(
            qj, doj, k_ref[...], v_ref[...], _row_to_lanes(lse_ref),
            _row_to_lanes(delta_ref),
            row0=j * block_q + off, col0=ki * block_k,
            scale=scale, causal=causal)
        # dv += pᵀ @ do
        dv_acc[...] += jax.lax.dot_general(
            p.astype(qj.dtype), doj, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bk, d]
        # dk += dsᵀ @ q
        dk_acc[...] += jax.lax.dot_general(
            ds, qj, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bk, d]

    @pl.when(j == nq - 1)
    def _flush():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                   dq_ref, dq_acc, *,
                   scale: float, causal: bool, nk: int,
                   q_len: int, kv_len: int):
    """Grid (bh, q-block, kv-block): streams one kv block per innermost
    step, accumulating dq for the resident q block in f32 scratch."""
    qi = pl.program_id(1)
    j = pl.program_id(2)
    block_q = q_ref.shape[0]
    block_k = k_ref.shape[0]
    off = kv_len - q_len

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    # causal: kv blocks fully above the diagonal contribute nothing
    live = (qi * block_q + off + block_q - 1 >= j * block_k) \
        if causal else (j >= 0)

    @pl.when(live)
    def _accumulate():
        kj = k_ref[...]         # [bk, d]
        _, ds = _recompute_p_ds(
            q_ref[...], do_ref[...], kj, v_ref[...], _row_to_lanes(lse_ref),
            _row_to_lanes(delta_ref),
            row0=qi * block_q + off, col0=j * block_k,
            scale=scale, causal=causal)
        dq_acc[...] += jax.lax.dot_general(
            ds, kj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bq, d]

    @pl.when(j == nk - 1)
    def _flush():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_pallas(scale, causal, bq, bk, res, do):
    from jax.experimental.pallas import tpu as pltpu

    q, k, v, out, lse = res
    b, h, sq, d = q.shape
    kv_len = k.shape[2]
    bh = b * h
    nq = sq // bq
    nk = kv_len // bk

    qf = q.reshape(bh, sq, d)
    kf = k.reshape(bh, kv_len, d)
    vf = v.reshape(bh, kv_len, d)
    dof = do.reshape(bh, sq, d)
    # delta_i = Σ_d do·o — cheap rowwise reduce, XLA fuses it; like lse
    # one float32 a row, handed over as [bh, 1, sq]
    delta = jnp.sum(dof.astype(jnp.float32)
                    * out.reshape(bh, sq, d).astype(jnp.float32),
                    axis=-1)[:, None, :]
    lse = lse[:, None, :]

    interpret = _interpret_mode()
    # the innermost grid dim revisits the same output block (accumulation)
    params = {} if interpret else dict(compiler_params=pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")))

    # grid (bh, ki, j): q/do/lse/delta stream along j, k/v pinned by ki
    q_j = pl.BlockSpec((None, bq, d), lambda g, ki, j: (g, j, 0))
    row_j = pl.BlockSpec((None, 1, bq), lambda g, ki, j: (g, 0, j))
    kv_ki = pl.BlockSpec((None, bk, d), lambda g, ki, j: (g, ki, 0))

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_kv_kernel, scale=scale, causal=causal,
                          nq=nq, q_len=sq, kv_len=kv_len),
        grid=(bh, nk, nq),
        in_specs=[q_j, q_j, row_j, row_j, kv_ki, kv_ki],
        out_specs=[kv_ki, kv_ki],
        out_shape=[jax.ShapeDtypeStruct((bh, kv_len, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, kv_len, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
        **params,
    )(qf, dof, lse, delta, kf, vf)

    # grid (bh, qi, j): k/v stream along j, q/do/lse/delta pinned by qi
    q_qi = pl.BlockSpec((None, bq, d), lambda g, qi, j: (g, qi, 0))
    row_qi = pl.BlockSpec((None, 1, bq), lambda g, qi, j: (g, 0, qi))
    kv_j = pl.BlockSpec((None, bk, d), lambda g, qi, j: (g, j, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          nk=nk, q_len=sq, kv_len=kv_len),
        grid=(bh, nq, nk),
        in_specs=[q_qi, q_qi, row_qi, row_qi, kv_j, kv_j],
        out_specs=q_qi,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
        **params,
    )(qf, dof, lse, delta, kf, vf)

    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, kv_len, d),
            dv.reshape(b, h, kv_len, d))


def _bwd_rule(scale, causal, block_q, block_k, res, do):
    q, k, v, out, lse = res
    s = (q.shape[-1] ** -0.5) if scale is None else scale
    b, h, sq, d = q.shape
    kv_len = k.shape[2]
    bq = min(block_q, sq)
    bk = min(block_k, kv_len)
    if sq % bq == 0 and kv_len % bk == 0 and bk % LANES == 0:
        return _bwd_pallas(s, causal, bq, bk, res, do)

    # ragged fallback: plain jax, one full-matrix kv block if ragged
    nk = kv_len // bk if kv_len % bk == 0 else None
    if nk is None:
        bk, nk = kv_len, 1

    # Matmul INPUTS stay in the model dtype (bf16 rides the MXU at full
    # rate; f32 inputs run at a fraction of it and quadruple the HBM
    # traffic of the big [sq, bk] intermediates).  Accumulation is f32
    # via preferred_element_type; softmax math is f32 throughout.
    qf = q
    dof = do
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                              # [b,h,sq] f32
    row = jnp.arange(sq)[:, None] + (kv_len - sq)

    kb = k.reshape(b, h, nk, bk, d)
    vb = v.reshape(b, h, nk, bk, d)

    lse = lse.reshape(b, h, sq)

    def kv_step(dq, j):
        kj = kb[:, :, j]  # [b,h,bk,d]
        vj = vb[:, :, j]
        logits = jnp.einsum("bhqd,bhkd->bhqk", qf, kj,
                            preferred_element_type=jnp.float32) * s
        if causal:
            col = j * bk + jnp.arange(bk)[None, :]
            logits = jnp.where(row >= col, logits, NEG_INF)
        p = jnp.exp(logits - lse[..., None])  # [b,h,sq,bk] f32
        pb = p.astype(q.dtype)                # matmul operand in bf16
        dvj = jnp.einsum("bhqk,bhqd->bhkd", pb, dof,
                         preferred_element_type=jnp.float32)
        dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vj,
                        preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[..., None])).astype(q.dtype)  # [b,h,sq,bk]
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, kj,
                             preferred_element_type=jnp.float32) * s
        dkj = jnp.einsum("bhqk,bhqd->bhkd", ds, qf,
                         preferred_element_type=jnp.float32) * s
        return dq, (dkj, dvj)

    dq0 = jnp.zeros((b, h, sq, d), jnp.float32)
    dq, (dk_blocks, dv_blocks) = jax.lax.scan(kv_step, dq0, jnp.arange(nk))
    dk = jnp.moveaxis(dk_blocks, 0, 2).reshape(b, h, kv_len, d)
    dv = jnp.moveaxis(dv_blocks, 0, 2).reshape(b, h, kv_len, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_fwd_rule, _bwd_rule)
