"""Gated delta rule: linear attention over a MATRIX state (Yang, Kautz
& Hatamizadeh 2024, "Gated Delta Networks", arXiv:2412.06464).

Per head (state ``S`` is ``[V, K]``, value width V, key width K; ``k``
has unit length, ``alpha`` in (0, 1], ``beta`` in [0, 2])::

    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t

The state is float32 and STORED transposed with the heads folded into
the minor dim, ``[K, H * V]`` (``[96, 5760]`` at 30 heads of 192 x 96:
12 sublane groups x 45 lane tiles, whole tiles, where ``[30, 192, 96]``
is none): a pool ``[layers, rows, K, H * V]`` has one natural tiling
and no program re-lays it out (``models/hybrid.state_geometry``).

Two forms of the same recurrence, chosen by the window's static width
as ``ops/ssm.py``'s are:

  * a WINDOW of tokens (``delta_window``): the paper's chunkwise form.
    Inside a block of ``BLOCK`` tokens the updates are resolved by the
    inverse of ``I + strict_lower(diag(beta) (K K^T * decay))`` (unit
    lower triangular: inverted two blocks at a time from blocks of one
    up, batched products and no substitution loop), for every block of
    the window at once; the state then crosses the blocks through a
    ``lax.scan`` of four products against it.
  * ONE token (``delta_step``): the recurrence itself as a rank-one
    correction of the state, a Pallas kernel over the state POOL where
    it is stored: a live row's state is read once and written once, in
    place, and a row that sits the pass out is not touched.

Both take ``n_valid`` [b]: only the first ``n_valid`` tokens of a row's
window are real.  In the window form a token past it has ``beta`` = 0
and ``log alpha`` = 0, the identity on the state; the one-token form
does not visit a row with ``n_valid`` 0 at all.

Every product that touches the state is float32: the one-token form on
the vector unit, the window form as matmuls at ``Precision.HIGHEST``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

BLOCK = 64          # tokens a block of the window form resolves at once
# lanes of a [K, H * V] state that one grid step of the one-token form
# holds in VMEM (a [96, 1920] float32 tile is 737 KB, in and out each
# double-buffered), and a vector register's lanes
TILE_LANES, LANES = 1920, 128


def _tile(hv: int) -> int:
    """The widest whole-lane-tile divisor of ``hv`` up to TILE_LANES;
    ``hv`` itself where it has none (test sizes)."""
    for t in range(min(TILE_LANES, hv) // LANES * LANES, 0, -LANES):
        if hv % t == 0:
            return t
    return hv


def _split3(x):
    """float32 -> three bfloat16 arrays that sum to it (to ~2^-24): a
    0/1 matrix then moves it through ONE bfloat16 MXU product exactly,
    whatever precision a float32 product would be given."""
    bf = jnp.bfloat16
    hi = x.astype(bf)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(bf)
    return hi, mid, (r - mid.astype(jnp.float32)).astype(bf)


def _step_kernel(layer_ref, count_ref, rows_ref, s_ref, kq_ref, e_ref,
                 lane_ref, o_ref, y_ref, *, key_dim: int):
    """One tile [K, TL] of one live row's state (lanes: some heads'
    value channels): read, corrected, reduced to ``y`` and written, in
    one visit.

    ``kq`` holds the row's ``k^T`` then ``q^T``, [2K, 3 Hp] (a head a
    column, in three bfloat16 parts side by side); ``e`` [3 Hp, TL] has
    a one where a lane belongs to a head, so their product lays every
    head's k and q along that head's lanes: [2K, TL] float32, exact.
    ``lane`` [3, TL]: alpha, beta and v, a number a lane."""
    f32 = jnp.float32
    slot = pl.program_id(0)
    count = count_ref[0]

    @pl.when(slot < count)
    def _():
        kq = jnp.dot(kq_ref[0], e_ref[...], preferred_element_type=f32)
        kx, qx = kq[:key_dim], kq[key_dim:]
        alpha, beta, v = (lane_ref[0, i:i + 1] for i in range(3))
        s = s_ref[0, 0]
        u = jnp.sum(s * kx, axis=0, keepdims=True)              # S k
        new = alpha * s + kx * (beta * (v - alpha * u))
        o_ref[0, 0] = new
        y_ref[0] = jnp.sum(new * qx, axis=0, keepdims=True)     # S' q

    # no row advances: every slot maps to ONE block, which the pipeline
    # writes back at the end — give it back the bits it was read with
    @pl.when((count == 0) & (slot == 0) & (pl.program_id(1) == 0))
    def _():
        o_ref[...] = s_ref[...]


def delta_step(q, k, v, g, beta, pool, layer, n_valid):
    """One token, on the state where it is stored.  q, k [b, 1, H, K]
    (k of unit length, q scaled), v [b, 1, H, V], g = log alpha and
    beta [b, 1, H]; ``pool`` [L, b, K, H * V] float32 holds the rows'
    state of L layers and ``layer`` (an int32 scalar, traced or not)
    says which one this is.  -> (o [b, 1, H, V] float32, pool).

    The walk is ``ops/ssm.ssd_step``'s: the rows that advance
    (``n_valid > 0``) compacted on the device into a list and a count
    handed to the kernel as scalars, a live row's state read once, tile
    by tile, corrected and reduced while it is in VMEM, and written to
    the place it came from (the pool operand IS the pool result); a row
    that sits the pass out is neither read nor written (its ``o`` is
    0), no other layer is touched, and a grid slot past the count maps
    to the block the pipeline already holds."""
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.ops.flash_attention import _interpret_mode

    f32, bf = jnp.float32, jnp.bfloat16
    b, _, H, K = q.shape
    V = v.shape[-1]
    L, _, _, HV = pool.shape
    tl = _tile(HV)
    n_tiles = HV // tl
    hp = -(-H // 16) * 16                   # bfloat16 sublane tiles

    active = n_valid > 0
    rows = jnp.argsort(~active, stable=True).astype(jnp.int32)  # live first
    count = active.sum(dtype=jnp.int32)[None]

    # [b, 2K, 3 hp]: k^T over q^T, each head a column, in three parts
    kq = jnp.concatenate([k[:, 0], q[:, 0]], axis=-1).astype(f32)
    kq = jnp.pad(kq.transpose(0, 2, 1), [(0, 0), (0, 0), (0, hp - H)])
    kq = jnp.concatenate(_split3(kq), axis=-1)
    own = (jnp.arange(HV)[None, :] // V == jnp.arange(hp)[:, None])
    e = jnp.tile(own.astype(bf), (3, 1))                      # [3 hp, HV]

    def per_lane(t):                        # [b, H] -> [b, H * V]
        return jnp.repeat(t[:, 0].astype(f32), V, axis=-1)
    lane = jnp.stack([per_lane(jnp.exp(g.astype(f32))), per_lane(beta),
                      v[:, 0].astype(f32).reshape(b, HV)], axis=1)

    def at(slot, t, refs):
        """(row, tile) of a grid step: a slot past the count stays on
        the last live row's last tile."""
        _, count_ref, rows_ref = refs
        live = slot < count_ref[0]
        last = jnp.maximum(count_ref[0] - 1, 0)
        return (rows_ref[jnp.where(live, slot, last)],
                jnp.where(live, t, n_tiles - 1))

    state = pl.BlockSpec((1, 1, K, tl), lambda slot, t, *refs:
                         (refs[0][0], at(slot, t, refs)[0], 0,
                          at(slot, t, refs)[1]))
    per_row = pl.BlockSpec((1, 2 * K, 3 * hp), lambda slot, t, *refs:
                           (at(slot, t, refs)[0], 0, 0))
    heads = pl.BlockSpec((3 * hp, tl), lambda slot, t, *refs:
                         (0, at(slot, t, refs)[1]))

    def lanes(n):
        return pl.BlockSpec((1, n, tl), lambda slot, t, *refs:
                            (at(slot, t, refs)[0], 0,
                             at(slot, t, refs)[1]))
    interpret = _interpret_mode()
    params = {} if interpret else dict(compiler_params=pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary")))
    pool, y = pl.pallas_call(
        functools.partial(_step_kernel, key_dim=K),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, n_tiles),
            in_specs=[state, per_row, heads, lanes(3)],
            out_specs=[state, lanes(1)]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, f32),
                   jax.ShapeDtypeStruct((b, 1, HV), f32)],
        input_output_aliases={3: 0},            # the pool, after 3 scalars
        interpret=interpret,
        name="delta_step",
        **params,
    )(jnp.asarray(layer, jnp.int32)[None], count, rows, pool, kq, e, lane)
    y = jnp.where(active[:, None, None], y.reshape(b, H, V), 0.0)
    return y[:, None], pool


def _unit_lower_inverse(n):
    """(I + n)^-1 for strictly lower triangular ``n`` [..., c, c], c a
    power of two: ``[[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1,
    B^-1]]`` from blocks of one up, ``log2 c`` rounds of two batched
    products.  As stable as the substitution it stands for: every
    intermediate is a block of the inverse itself (the powers of ``n``
    that the nilpotent series sums grow to ~1e15 at 64 keys of 8 lanes
    before they cancel)."""
    hi = lax.Precision.HIGHEST
    c, lead = n.shape[-1], n.shape[:-2]

    def diagonal_blocks(x, size):   # [..., c, c] -> [..., c/size, size, size]
        x = x.reshape(*lead, c // size, size, c // size, size)
        return jnp.moveaxis(jnp.diagonal(x, axis1=-4, axis2=-2), -1, -3)

    inv = jnp.ones((*lead, c, 1, 1), n.dtype)       # blocks of one: [1]
    m = 1
    while m < c:
        low = diagonal_blocks(n, 2 * m)[..., m:, :m]    # C of every pair
        a, b = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        off = -jnp.matmul(jnp.matmul(b, low, precision=hi), a, precision=hi)
        inv = jnp.concatenate([
            jnp.concatenate([a, jnp.zeros_like(a)], axis=-1),
            jnp.concatenate([off, b], axis=-1)], axis=-2)
        m *= 2
    return inv[..., 0, :, :]


def delta_window(q, k, v, g, beta, state, n_valid, block: int = BLOCK):
    """A window of s tokens in blocks of ``block``.  q, k [b, s, H, K],
    v [b, s, H, V], g, beta [b, s, H]; ``state`` [b, H, K, V] float32
    (each head's S^T).  -> (o [b, s, H, V] float32, state)."""
    f32, hi = jnp.float32, lax.Precision.HIGHEST
    b, s, H, K = q.shape
    live = (jnp.arange(s)[None, :] < n_valid[:, None])[..., None]
    g = jnp.where(live, g.astype(f32), 0.0)
    beta = jnp.where(live, beta.astype(f32), 0.0)
    c = min(int(block), 1 << max(s - 1, 0).bit_length())     # a power of two
    pad = -s % c

    def blocks(t):                 # [b, s, H, ...] -> [b, H, n, c, ...]
        if pad:
            t = jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        t = t.astype(f32).reshape(b, -1, c, *t.shape[2:])
        return jnp.moveaxis(t, 3, 1)

    q, k, v = blocks(q), blocks(k), blocks(v)      # [b, H, n, c, K | V]
    g, beta = blocks(g), blocks(beta)              # [b, H, n, c]
    gc = jnp.cumsum(g, axis=-1)                    # inclusive, <= 0
    tri = jnp.tril(jnp.ones((c, c), bool))
    # decay from token j (exclusive) to token i (inclusive), j <= i
    decay = jnp.exp(jnp.where(tri, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf))
    kb, vb = k * beta[..., None], v * beta[..., None]
    kk = jnp.einsum("bhnik,bhnjk->bhnij", kb, k, precision=hi) * decay
    t = _unit_lower_inverse(jnp.where(tri & ~jnp.eye(c, dtype=bool), kk, 0.0))
    w = jnp.matmul(t, kb * jnp.exp(gc)[..., None], precision=hi)
    u = jnp.matmul(t, vb, precision=hi)
    qk = jnp.einsum("bhnik,bhnjk->bhnij", q, k, precision=hi) * decay
    q_in = q * jnp.exp(gc)[..., None]              # against the carried state
    k_out = k * jnp.exp(gc[..., -1:] - gc)[..., None]      # to the block's end
    end = jnp.exp(gc[..., -1])                     # [b, H, n]

    def body(state, xs):
        w, u, qk, q_in, k_out, end = xs
        new = u - jnp.matmul(w, state, precision=hi)            # [b,H,c,V]
        o = jnp.matmul(q_in, state, precision=hi) \
            + jnp.matmul(qk, new, precision=hi)
        state = end[..., None, None] * state + jnp.einsum(
            "bhck,bhcv->bhkv", k_out, new, precision=hi)
        return state, o

    state, o = lax.scan(body, state, tuple(
        jnp.moveaxis(x, 2, 0) for x in (w, u, qk, q_in, k_out, end)))
    o = jnp.moveaxis(o, 0, 2)                                   # [b,H,n,c,V]
    return jnp.moveaxis(o, 1, 3).reshape(b, s + pad, H, -1)[:, :s], state


def delta_rule(q, k, v, g, beta, pool, layer, n_valid, block: int = BLOCK):
    """The recurrence over a window, in the form its static width calls
    for.  The rows' state is addressed as it is stored: ``pool`` [L, b,
    K, H * V] float32 and the ``layer`` of it that is this one's.  The
    one-token form works on the pool in place; the window form takes
    the layer out and puts it back, which costs nothing for a pool of
    one layer (what a caller with the rows' state in hand passes, with
    layer 0).  -> (o [b, s, H, V] float32, pool)."""
    if q.shape[1] == 1:
        return delta_step(q, k, v, g, beta, pool, layer, n_valid)
    b, _, H, K = q.shape
    state = pool[layer].reshape(b, K, H, -1).transpose(0, 2, 1, 3)
    o, state = delta_window(q, k, v, g, beta, state, n_valid, block)
    return o, pool.at[layer].set(
        state.transpose(0, 2, 1, 3).reshape(pool.shape[1:]))


def delta_recurrence(q, k, v, g, beta, state):
    """The definition, token by token (the oracle of the tests).
    Shapes as ``delta_window``.  -> (o [b, s, H, V] float32, state)."""
    f32 = jnp.float32

    def step(state, xs):
        qt, kt, vt, gt, bt = xs         # [b,H,K] [b,H,K] [b,H,V] [b,H] [b,H]
        state = jnp.exp(gt)[..., None, None] * state
        seen = jnp.einsum("bhk,bhkv->bhv", kt, state, precision="highest")
        state = state + jnp.einsum("bhk,bhv->bhkv", kt,
                                   bt[..., None] * (vt - seen),
                                   precision="highest")
        return state, jnp.einsum("bhk,bhkv->bhv", qt, state,
                                 precision="highest")

    xs = tuple(jnp.moveaxis(t.astype(f32), 1, 0) for t in (q, k, v, g, beta))
    state, o = lax.scan(step, state, xs)
    return jnp.moveaxis(o, 0, 1), state
