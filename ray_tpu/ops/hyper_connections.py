"""Manifold-constrained hyper-connections: a residual of ``n`` streams
round a sublayer ``F`` that sees ONE stream.

With the streams of a token ``X`` in ``R^{n x C}``:

    v      = RMSNorm_{nC}(vec(X)) * w            one norm over all n C lanes
    A_pre  = alpha_pre  * (v Phi_pre)  + b_pre                      [n]
    A_post = alpha_post * (v Phi_post) + b_post                     [n]
    A_res  = clamp(alpha_res * mat(v Phi_res) + b_res)              [n, n]
    H_pre  = sigmoid(A_pre);  H_post = 2 sigmoid(A_post)
    H_res  = SK(exp(A_res)): ``iters`` times (rows to sum 1, then columns)
    X'     = H_res X + H_post^T F(H_pre X)

``mix_in`` gives ``H_pre X`` and the two maps the way back needs,
``mix_out`` the new streams.  The norm, the product with ``Phi``, the
exponentials and every Sinkhorn-Knopp iteration are float32 (the
product at the ``highest`` precision: the chip's default multiplies
float32 operands in one bfloat16 pass).

Shapes on the chip.  The streams arrive ``[.., n, C]`` and are read as
``[T, n C]``: a stream is then a slice of whole lane tiles (``C`` a
multiple of 128) and ``T`` fills the sublanes, where ``[.., n, C]``
would hold ``n`` = 4 of a tile's 16 sublanes.  The maps are computed
with ``T`` MINOR — ``Phi`` is held ``[n + n + n n, n C]``, the product
is ``[maps, T]``, ``H_res`` is ``[n, n, T]`` — so that the 2 x ``iters``
dependent normalisations of a 4 x 4 matrix a token run over whole
lanes of tokens and not over 4 x 4 tiles of padding.  Both mixes are
sums of ``n`` scaled slices, elementwise: one pass over the streams
each, no ``[T, n, n, C]`` product and no batched 4 x 4 matmul.

A parameter set (``init``): ``w [n C]``, ``phi [n + n + n n, n C]``
float32, ``alpha [3]`` and ``b [n + n + n n]`` float32 (pre | post |
res, the matrix row-major).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def init(key, n: int, width: int, param_dtype):
    """``alpha`` 1, ``b`` 0, ``Phi`` N(0, 0.02): the normed streams have
    n C lanes of mean square 1, so ``v Phi`` has a standard deviation of
    0.02 sqrt(n C) (2.4 at 4 x 3,584): every map depends on its input
    to order 1."""
    m = 2 * n + n * n
    return {"w": jnp.ones((n * width,), param_dtype),
            "phi": jax.random.normal(key, (m, n * width), F32) * 0.02,
            "alpha": jnp.ones((3,), F32),
            "b": jnp.zeros((m,), F32)}


def sinkhorn(m, iters: int):
    """m [n, n, T] positive -> ``iters`` times: every row (over axis 1)
    to sum 1, then every column (over axis 0)."""
    def one(_, m):
        m = m / m.sum(1, keepdims=True)
        return m / m.sum(0, keepdims=True)
    return jax.lax.fori_loop(0, iters, one, m, unroll=True)


def maps(x, hp, *, n: int, iters: int, eps: float, clamp: tuple):
    """x [T, n C] -> (H_pre [n, T], H_post [n, T], H_res [n, n, T]),
    float32."""
    with jax.named_scope("mhc_pre_map"):
        xf = x.astype(F32)
        r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1) + eps)         # [T]
        # the norm's scalar a token factors out of the product
        a = jnp.einsum("mk,tk->mt", hp["phi"], xf * hp["w"].astype(F32),
                       precision=jax.lax.Precision.HIGHEST) * r
        pre, post, res = (a[:n], a[n:2 * n], a[2 * n:])
        alpha, b = hp["alpha"], hp["b"][:, None]
        h_pre = jax.nn.sigmoid(alpha[0] * pre + b[:n])
        h_post = 2.0 * jax.nn.sigmoid(alpha[1] * post + b[n:2 * n])
        a_res = jnp.clip(alpha[2] * res + b[2 * n:], *clamp)
    with jax.named_scope("mhc_sinkhorn"):
        h_res = sinkhorn(jnp.exp(a_res).reshape(n, n, -1), iters)
    return h_pre, h_post, h_res


def mix_in(x, hp, *, iters: int, eps: float, clamp: tuple):
    """x [.., n, C] -> (``H_pre X`` [.., C] in x's dtype, what
    ``mix_out`` needs)."""
    *lead, n, width = x.shape
    with jax.named_scope("mhc"):
        flat = x.reshape(-1, n * width)
        h_pre, h_post, h_res = maps(flat, hp, n=n, iters=iters, eps=eps,
                                    clamp=clamp)
        with jax.named_scope("mhc_mix_in"):
            g = h_pre.T                                           # [T, n]
            h = sum(flat[:, j * width:(j + 1) * width].astype(F32)
                    * g[:, j, None] for j in range(n))
        return (h.astype(x.dtype).reshape(*lead, width),
                (flat, h_post.T, jnp.moveaxis(h_res, -1, 0)))


def mix_out(carried, f):
    """``H_res X + H_post^T F``: f [.., C] -> the new streams [.., n,
    C]."""
    flat, h_post, h_res = carried             # [T, n C], [T, n], [T, n, n]
    *lead, width = f.shape
    n = h_post.shape[-1]
    with jax.named_scope("mhc"), jax.named_scope("mhc_mix_out"):
        ff = f.reshape(-1, width).astype(F32)
        own = [flat[:, j * width:(j + 1) * width].astype(F32)
               for j in range(n)]
        out = jnp.concatenate(
            [sum(own[j] * h_res[:, i, j, None] for j in range(n))
             + ff * h_post[:, i, None] for i in range(n)], axis=-1)
        return out.astype(f.dtype).reshape(*lead, n, width)
