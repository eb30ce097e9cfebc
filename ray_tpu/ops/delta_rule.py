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

  * a WINDOW of tokens (``delta_window``): the paper's chunkwise form,
    ONE Pallas kernel.  The grid walks (row, group of heads, block of
    ``BLOCK`` tokens), the blocks in order: a group's state ``[heads,
    K, V]`` is read into VMEM before the window's first block, stays
    there, and is written once after the last.  A visit reads the
    block's q, k, v, log decay and beta once, resolves the block's
    updates by the inverse of ``I + strict_lower(diag(beta) (K K^T *
    decay))`` (unit lower triangular: diagonal blocks of ``SOLVE_BLOCK``
    by forward substitution on the vector unit, merged upward two
    blocks at a time by matrix products, all in VMEM) applied to ``K
    e^gc`` and ``V``, does the four products against the resident state
    (``W S`` and ``Q S`` as one, ``A (U - W S)``, ``K_out^T (U - W
    S)``) and writes the block's ``o`` once, a token's heads side by
    side as the mixer reads them.
  * ONE token (``delta_step``): the recurrence itself as a rank-one
    correction of the state, a Pallas kernel over the state POOL where
    it is stored: a live row's state is read once and written once, in
    place, and a row that sits the pass out is not touched.

Both take ``n_valid`` [b]: only the first ``n_valid`` tokens of a row's
window are real.  In the window form a token past it has ``beta`` = 0
and ``log alpha`` = 0, the identity on the state bit for bit; the
one-token form does not visit a row with ``n_valid`` 0 at all.

Every product that touches the state is float32: the one-token form on
the vector unit, the window form's matmuls (every one of them: the
block's ``K K^T`` and ``Q K^T`` and the solve's merges too) at
``Precision.HIGHEST`` inside the kernel, which Mosaic runs as bfloat16
passes over the operands' split parts that add up to float32.
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


# heads a grid step of the window form holds in VMEM (their state, a
# block's q, k, v, o and the block's [c, c] intermediates), and the
# side of the diagonal blocks its solve resolves by substitution
WINDOW_HEADS, SOLVE_BLOCK = 10, 8


def _heads_a_step(H: int, V: int) -> int:
    """The largest divisor of ``H`` up to WINDOW_HEADS whose heads'
    values are whole lane tiles side by side; all ``H`` where there is
    none (test sizes)."""
    return max((d for d in range(1, min(WINDOW_HEADS, H) + 1)
                if H % d == 0 and d * V % LANES == 0), default=H)


def _mm(a, b, contract):
    """Batched float32-exact product of a [h, ., .] and b [h, ., .]
    over ``contract`` = (a's dim, b's dim)."""
    return lax.dot_general(a, b, (((contract[0],), (contract[1],)),
                                  ((0,), (0,))),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _unit_lower_solve(n, row, col):
    """(I + n)^-1 for strictly lower triangular ``n`` [h, c, c], c a
    power of two (``row``, ``col`` [1, c, c]: an element's indices).
    Diagonal blocks of SOLVE_BLOCK by forward substitution (a rank-one
    update a column, on the vector unit), merged upward two blocks at a
    time: with ``T`` the inverse of the block diagonal and ``C`` the
    lower-left block of every pair, ``[[A, 0], [C, B]]^-1 = T - T C T``.
    As stable as the substitution it stands for: every intermediate is
    a block of the inverse itself (the powers of ``n`` that the
    nilpotent series sums grow to ~1e15 at 64 keys of 8 lanes before
    they cancel)."""
    c = n.shape[-1]
    r = min(SOLVE_BLOCK, c)
    eye = jnp.where(row == col, 1.0, 0.0)
    parts = []
    for m in range(0, c, r):
        x = jnp.broadcast_to(eye[:, m:m + r], (n.shape[0], r, c))
        for j in range(r - 1):
            # row j is final: take its multiple out of the rows below
            x = x - n[:, m:m + r, m + j:m + j + 1] * x[:, j:j + 1]
        parts.append(x)
    t = jnp.concatenate(parts, axis=1)
    while r < c:
        pair = ((row ^ col) < 2 * r) & ((row & r) != 0) & ((col & r) == 0)
        low = jnp.where(pair, n, 0.0)
        t = t - _mm(t, _mm(low, t, (2, 1)), (2, 1))
        r *= 2
    return t


def _window_kernel(q_ref, k_ref, v_ref, gb_ref, s_in_ref, o_ref, s_ref):
    """One block of c tokens of some heads: q, k [h, c, K]; v and o as
    the mixer holds them, a token's heads side by side [c, h * V];
    ``gb`` [h, 2, c] (log decay and beta, a token a lane); the heads'
    state [h, K, V], which stays in ``s_ref`` from the window's first
    block to its last."""
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = s_in_ref[...]

    q, k = q_ref[...], k_ref[...]
    h, c, _ = q.shape
    V = s_ref.shape[-1]
    v = jnp.stack([v_ref[:, i * V:(i + 1) * V] for i in range(h)]).astype(f32)
    row = lax.broadcasted_iota(jnp.int32, (1, c, c), 1)
    col = lax.broadcasted_iota(jnp.int32, (1, c, c), 2)
    g_row, beta_row = gb_ref[:, 0:1, :], gb_ref[:, 1:2, :]      # [h, 1, c]

    def column(t, keep):    # [h, 1, c] -> [h, c, 1]: row i sums lanes ``keep``
        return jnp.sum(jnp.where(keep, t, 0.0), axis=2, keepdims=True)

    beta = column(beta_row, row == col)
    # the running log decay, inclusive, a token a sublane and a token a lane
    gc = column(g_row, col <= row)
    gc_row = jnp.sum(jnp.where(row <= col, column(g_row, row == col), 0.0),
                     axis=1, keepdims=True)
    # decay from token j (exclusive) to token i (inclusive), j <= i
    decay = jnp.exp(jnp.where(row >= col, gc - gc_row, -jnp.inf))
    kb = k * beta
    both = _mm(jnp.concatenate([q, kb], axis=1), k, (2, 2))     # [h, 2c, c]
    qk, kk = both[:, :c] * decay, both[:, c:] * decay
    t = _unit_lower_solve(jnp.where(row > col, kk, 0.0), row, col)
    grow = jnp.exp(gc)
    w = _mm(t, kb * grow, (2, 1))                               # [h, c, K]
    u = _mm(t, v * beta, (2, 1))                                # [h, c, V]
    state = s_ref[...]
    seen = _mm(jnp.concatenate([w, q * grow], axis=1), state, (2, 1))
    new = u - seen[:, :c]                                       # [h, c, V]
    o = seen[:, c:] + _mm(qk, new, (2, 1))
    for i in range(h):
        o_ref[:, i * V:(i + 1) * V] = o[i]
    end = jnp.sum(g_row, axis=2, keepdims=True)                 # [h, 1, 1]
    s_ref[...] = jnp.exp(end) * state + _mm(k * jnp.exp(end - gc), new,
                                            (1, 1))


def delta_window(q, k, v, g, beta, state, n_valid, block: int = BLOCK):
    """A window of s tokens in blocks of ``block``.  q, k [b, s, H, K],
    v [b, s, H, V], g, beta [b, s, H]; ``state`` [b, H, K, V] float32
    (each head's S^T).  -> (o [b, s, H, V] float32, state).

    ONE Pallas kernel: the grid walks (row, group of heads, block), the
    blocks in order; the group's state is read into VMEM before its
    first block and written after its last."""
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.ops.flash_attention import _interpret_mode

    f32 = jnp.float32
    b, s, H, K = q.shape
    V = v.shape[-1]
    live = (jnp.arange(s)[None, :] < n_valid[:, None])[..., None]
    g = jnp.where(live, g.astype(f32), 0.0)
    beta = jnp.where(live, beta.astype(f32), 0.0)
    c = min(int(block), 1 << max(s - 1, 0).bit_length())     # a power of two
    pad = -s % c
    n = (s + pad) // c

    def blocks(t):                 # [b, s, H, ...] -> [b, n, c, H, ...]
        if pad:
            t = jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        return t.reshape(b, n, c, *t.shape[2:])

    def by_head(t):                # [b, s, H, K] -> [b, H, n, c, K]
        return blocks(t.astype(f32)).transpose(0, 3, 1, 2, 4)

    gb = jnp.stack([blocks(g), blocks(beta)], axis=-1)
    gb = gb.transpose(0, 1, 3, 4, 2)                    # [b, n, H, 2, c]
    hg = _heads_a_step(H, V)
    per_block = pl.BlockSpec((None, hg, None, c, K),
                             lambda i, h, j: (i, h, j, 0, 0))
    by_token = pl.BlockSpec((None, None, c, hg * V),
                            lambda i, h, j: (i, j, 0, h))
    whole = pl.BlockSpec((None, hg, K, V), lambda i, h, j: (i, h, 0, 0))
    interpret = _interpret_mode()
    params = {} if interpret else dict(compiler_params=pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")))
    o, state = pl.pallas_call(
        _window_kernel,
        grid=(b, H // hg, n),
        in_specs=[per_block, per_block, by_token,
                  pl.BlockSpec((None, None, hg, 2, c),
                               lambda i, h, j: (i, j, h, 0, 0)), whole],
        out_specs=[by_token, whole],
        out_shape=[jax.ShapeDtypeStruct((b, n, c, H * V), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={4: 1},
        interpret=interpret,
        name="delta_window",
        **params,
    )(by_head(q), by_head(k), blocks(v).reshape(b, n, c, H * V), gb,
      state.astype(f32))
    return o.reshape(b, s + pad, H, V)[:, :s], state


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
