"""Mamba-2 mixer core: causal depthwise convolution + selective
state-space recurrence (Dao & Gu 2024, "state space duality").

Per head (state ``S`` is ``[P, N]``, head width P, state width N)::

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t B_t^T
    y_t = S_t C_t + D * x_t

``B`` and ``C`` come in G groups, ``[..., G, N]``: the H heads are split
into G runs of ``H / G`` consecutive heads and head h reads group ``h //
(H / G)`` (one group: every head reads the same B and C).

Two forms of the same recurrence, chosen by the window's static width:

  * a WINDOW of tokens (``ssd_window``): the chunked scan.  Inside a
    chunk of ``chunk`` tokens the outputs are one masked matrix product
    (the quadratic, "attention-like" form); the state crosses chunks
    through a ``lax.scan``, so a window of any width costs one compiled
    chunk body.  It takes the state the row arrives with and returns
    the state it leaves with.
  * ONE token (``ssd_step``): the recurrence itself, elementwise on the
    state, as a Pallas kernel over the state POOL where it is stored:
    a decode pass reads a live row's state once and writes it once, in
    place, and leaves a row that sits the pass out untouched.

Both take ``n_valid`` [b]: only the first ``n_valid`` tokens of a row's
window are real.  In the window form a token past it has its ``dt`` set
to 0, which makes it the identity on the state (decay exp(0) = 1, input
0), so a padded last chunk of a prompt leaves the state exactly as it
was; the one-token form does not visit a row with ``n_valid`` 0 at all.
The convolution's state (the last ``K - 1`` inputs) is likewise taken
at ``n_valid``, not at the window's end.

The state is float32 and every product that touches it is computed in
float32: the one-token form on the vector unit (multiply + sum), the
chunk form as matmuls at ``Precision.HIGHEST`` (a float32 ``dot`` at
the default precision runs in bfloat16 passes on a TPU); at one chunk
of 256 tokens that is ~6 GFLOP a layer, nothing beside the projections.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


def causal_conv(x, state, w, b, n_valid):
    """Depthwise causal convolution of width K over a window.

    x [b, s, C] window inputs; state [b, K-1, C] the K-1 inputs before
    the window; w [K, C] (tap K-1 multiplies the current token), b [C]
    or None (no bias); n_valid [b].  -> (silu(conv) [b, s, C], new state
    [b, K-1, C] = the last K-1 inputs up to and including token
    n_valid - 1)."""
    k = w.shape[0]
    s = x.shape[1]
    full = jnp.concatenate([state.astype(x.dtype), x], axis=1)  # [b,K-1+s,C]
    out = 0.0 if b is None else b.astype(jnp.float32)
    for j in range(k):
        out = out + full[:, j:j + s].astype(jnp.float32) \
            * w[j].astype(jnp.float32)
    new_state = jax.vmap(
        lambda row, n: lax.dynamic_slice_in_dim(row, n, k - 1, axis=0)
    )(full, n_valid)
    return jax.nn.silu(out).astype(x.dtype), new_state.astype(state.dtype)


def _valid_dt(dt, n_valid):
    """dt [b, s, H] with the tokens past n_valid [b] zeroed."""
    live = jnp.arange(dt.shape[1])[None, :] < n_valid[:, None]
    return jnp.where(live[..., None], dt, 0.0)


# rows of a [H * P, N] state that one grid step holds in VMEM (2 MB of
# float32 at N = 128, in and out each double-buffered, inside the 16 MB
# a kernel has without asking; read on the chip, PR 32, nine layers of
# 12 live rows: 1,024 / 2,048 / 4,096 / 8,192 rows took 2.77 / 2.53 /
# 2.44 / 2.42 ms with the first body and 4,096 / 8,192 take 1.69 / 1.64
# with this one), the rows one pass of the body's loop takes through
# the vector unit, and a vector register's lanes
TILE_ROWS, CHUNK_ROWS, LANES = 4096, 128, 128


def _step_kernel(layer_ref, count_ref, rows_ref, decay_ref,
                 s_ref, dtx_ref, b_ref, c_ref, o_ref, y_ref, *,
                 head_rows: int, group_rows: int):
    """One tile [TR, N] of one live row's state: read, advanced, reduced
    to ``y`` and written, in one visit, a chunk of ``ck`` rows at a
    time.

    ``dtx`` (in) and ``y`` (out) hold one number a state row and travel
    dense, the state's rows along LANES: block [chunks, ck].  The update
    needs them along the tile's SUBLANES.  One transpose a tile turns
    ``dtx`` to [ck, chunks]; chunk c's column is picked out by a lane
    mask and a lane sum, and ``y``'s column is put into lane c of an
    accumulator the same way round, which one transpose a tile turns
    back.  (Two 128 x 128 transposes a CHUNK made the kernel compute
    bound at 20 us a row and layer; this way it runs at the DMA's 12.)

    ``b_ref`` / ``c_ref`` hold the row's ``[G, N]`` B and C.  A chunk
    lies inside ONE group's ``group_rows`` state rows (the caller sees
    to it) and picks its group's row of them by its own index.
    """
    f32 = jnp.float32
    slot, t = pl.program_id(0), pl.program_id(1)
    count = count_ref[0]
    n_chunks, ck = dtx_ref.shape[1:]
    heads = ck // head_rows

    @pl.when(slot < count)
    def _():
        row = rows_ref[slot]
        one_group = b_ref.shape[1] == 1
        if one_group:
            Bv, Cv = b_ref[0], c_ref[0]                         # [1, N]
        lane = lax.broadcasted_iota(jnp.int32, (ck, LANES), 1)
        dtx_t = jnp.concatenate(
            [dtx_ref[0], jnp.zeros((LANES - n_chunks, ck), f32)]).T

        def chunk(c, y_t):
            r0 = pl.multiple_of(c * ck, ck)
            head0 = (t * n_chunks + c) * heads
            if one_group:
                B, C = Bv, Cv
            else:
                g = (t * n_chunks + c) * ck // group_rows
                B, C = b_ref[0, pl.ds(g, 1), :], c_ref[0, pl.ds(g, 1), :]
            dtx = jnp.sum(jnp.where(lane == c, dtx_t, 0.0), axis=1,
                          keepdims=True)                        # [ck, 1]
            new = jnp.concatenate([
                decay_ref[row, head0 + h]
                * s_ref[0, 0, pl.ds(r0 + h * head_rows, head_rows), :]
                + dtx[h * head_rows:(h + 1) * head_rows] * B
                for h in range(heads)])                         # [ck, N]
            o_ref[0, 0, pl.ds(r0, ck), :] = new
            y = jnp.sum(new * C, axis=1, keepdims=True)         # [ck, 1]
            return jnp.where(lane == c, y, y_t)

        y_t = lax.fori_loop(0, n_chunks, chunk, jnp.zeros((ck, LANES), f32))
        y_ref[0] = y_t.T[:n_chunks]

    # no row advances: every slot maps to ONE block, which the pipeline
    # writes back at the end — give it back the bits it was read with
    @pl.when((count == 0) & (slot == 0) & (t == 0))
    def _():
        o_ref[...] = s_ref[...]


def ssd_step(x, dt, A, B, C, D, pool, layer, n_valid):
    """One token, on the state where it is stored.  x [b, 1, H, P], dt
    [b, 1, H] (after softplus), A, D [H], B, C [b, 1, G, N]; ``pool``
    [L, b, H * P, N] float32 holds the rows' state of L layers
    and ``layer`` (an int32 scalar, traced or not) says which one this
    is.  -> (y [b, 1, H, P] float32, pool).

    A Pallas kernel walks the rows that advance (``n_valid > 0``),
    compacted on the device into a list and a count that it is handed
    as scalars.  A live row's state is read once, tile by tile, ``y``
    is reduced from the updated tile while it is in VMEM, and the tile
    goes back to the place it came from (the pool operand IS the pool
    result); a row that sits the pass out is neither read nor written
    (its ``y`` is ``D x``), and no other layer of the pool is touched.
    The grid is static, row slots x tiles: a slot past the count maps
    to the block the pipeline already holds, so it moves nothing."""
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.ops.flash_attention import _interpret_mode

    f32 = jnp.float32
    b, _, H, P = x.shape
    L, _, HP, N = pool.shape
    G = B.shape[2]
    group_rows = HP // G        # a group's heads lie in one run of rows
    ck = CHUNK_ROWS if (group_rows % CHUNK_ROWS == 0
                        and CHUNK_ROWS % P == 0) else group_rows
    tr = TILE_ROWS if HP % TILE_ROWS == 0 and TILE_ROWS % ck == 0 else HP
    n_tiles, n_chunks = HP // tr, tr // ck
    if n_chunks > LANES:        # a tile's y: one lane of a register a chunk
        raise ValueError(f"a tile of {tr} rows is more than {LANES} chunks "
                         f"of {ck}")

    active = n_valid > 0
    dt, xf = dt[:, 0].astype(f32), x[:, 0].astype(f32)   # [b, H] [b, H, P]
    rows = jnp.argsort(~active, stable=True).astype(jnp.int32)  # live first
    count = active.sum(dtype=jnp.int32)[None]

    def at(slot, t, refs):
        """(row, tile) of a grid step: a slot past the count stays on
        the last live row's last tile."""
        _, count_ref, rows_ref, _ = refs
        live = slot < count_ref[0]
        last = jnp.maximum(count_ref[0] - 1, 0)
        return (rows_ref[jnp.where(live, slot, last)],
                jnp.where(live, t, n_tiles - 1))

    state = pl.BlockSpec((1, 1, tr, N), lambda slot, t, *refs:
                         (refs[0][0], *at(slot, t, refs), 0))
    per_chunk = pl.BlockSpec((1, n_chunks, ck), lambda slot, t, *refs:
                             (*at(slot, t, refs), 0))
    per_row = pl.BlockSpec((1, G, N), lambda slot, t, *refs:
                           (at(slot, t, refs)[0], 0, 0))
    interpret = _interpret_mode()
    params = {} if interpret else dict(compiler_params=pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary")))
    pool, y = pl.pallas_call(
        functools.partial(_step_kernel, head_rows=P,
                          group_rows=group_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(b, n_tiles),
            in_specs=[state, per_chunk, per_row, per_row],
            out_specs=[state, per_chunk]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, f32),
                   jax.ShapeDtypeStruct((b, HP // ck, ck), f32)],
        input_output_aliases={4: 0},            # the pool, after 4 scalars
        interpret=interpret,
        name="ssd_step",
        **params,
    )(jnp.asarray(layer, jnp.int32)[None], count, rows, jnp.exp(dt * A),
      pool, (dt[..., None] * xf).reshape(b, HP // ck, ck),
      B[:, 0].astype(f32), C[:, 0].astype(f32))
    y = jnp.where(active[:, None, None], y.reshape(b, H, P), 0.0) \
        + D[:, None] * xf
    return y[:, None], pool


def _ssd_chunk(x, dt, A, B, C, state):
    """One chunk of Q tokens in the quadratic form.  x [b, Q, H, P]
    float32, dt [b, Q, H] float32 (0 = identity token), B, C [b, Q, G,
    N], state [b, H, P, N] float32.  -> (y [b, Q, H, P] float32 without
    the D term, state after the chunk)."""
    hi = lax.Precision.HIGHEST       # float32 products, not bf16 passes
    b, q, H, P = x.shape
    G = B.shape[2]
    R = H // G                       # heads that read one group
    dA = dt * A                                                 # [b, Q, H]
    cs = jnp.cumsum(dA, axis=1)                                 # inclusive
    # decay from token s (exclusive) to token l (inclusive), s <= l
    seg = cs[:, :, None, :] - cs[:, None, :, :]                 # [b, l, s, H]
    tri = jnp.tril(jnp.ones((q, q), bool))
    L = jnp.where(tri[None, :, :, None], jnp.exp(seg), 0.0)     # [b, l, s, H]
    CB = jnp.einsum("blgn,bsgn->blsg", C, B, precision=hi)      # [b, l, s, G]
    xdt = x * dt[..., None]                                     # [b, Q, H, P]
    M = (CB[..., None] * L.reshape(b, q, q, G, R)).reshape(b, q, q, H)
    y = jnp.einsum("blsh,bshp->blhp", M, xdt, precision=hi)
    # what the incoming state adds: y_l += exp(cs_l) * (state C_l)
    sc = jnp.einsum("bgrpn,blgn->blgrp", state.reshape(b, G, R, P, -1), C,
                    precision=hi).reshape(b, q, H, P)
    y = y + jnp.exp(cs)[..., None] * sc
    # the state after the chunk
    to_end = jnp.exp(cs[:, -1:, :] - cs)                        # [b, Q, H]
    add = jnp.einsum("bsgrp,bsgn->bgrpn",
                     (xdt * to_end[..., None]).reshape(b, q, G, R, P), B,
                     precision=hi).reshape(state.shape)
    state = jnp.exp(cs[:, -1])[:, :, None, None] * state + add
    return y, state


def ssd_window(x, dt, A, B, C, D, state, n_valid, chunk: int):
    """A window of s tokens as a scan over chunks of ``chunk``.  Shapes
    as ``ssd_step`` with s in place of 1.  -> (y [b, s, H, P] float32,
    state)."""
    f32 = jnp.float32
    b, s = x.shape[:2]
    dt = _valid_dt(dt.astype(f32), n_valid)
    q = min(int(chunk), s)
    pad = -s % q

    def chunks(t):                 # [b, s, ...] -> [c, b, q, ...]
        if pad:
            t = jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        return jnp.moveaxis(t.reshape(b, -1, q, *t.shape[2:]), 1, 0)

    xf = x.astype(f32)

    def body(state, xs):
        y, state = _ssd_chunk(*xs[:2], A, *xs[2:], state)
        return state, y

    state, y = lax.scan(body, state,
                        tuple(chunks(t) for t in
                              (xf, dt, B.astype(f32), C.astype(f32))))
    y = jnp.moveaxis(y, 0, 1).reshape(b, s + pad, *x.shape[2:])[:, :s]
    return y + D[:, None] * xf, state


def ssd(x, dt, A, B, C, D, pool, layer, n_valid, chunk: int):
    """The recurrence over a window, in the form its static width calls
    for.  The rows' state is addressed as it is stored: ``pool`` [L, b,
    H * P, N] float32 and the ``layer`` of it that is this one's.  The
    one-token form works on the pool in place; the window form takes
    the layer out and puts it back, which costs nothing for a pool of
    one layer (what a caller with the rows' state in hand passes, with
    layer 0).  -> (y [b, s, H, P] float32, pool)."""
    if x.shape[1] == 1:
        return ssd_step(x, dt, A, B, C, D, pool, layer, n_valid)
    b, _, H, P = x.shape
    y, state = ssd_window(x, dt, A, B, C, D,
                          pool[layer].reshape(b, H, P, -1), n_valid, chunk)
    return y, pool.at[layer].set(state.reshape(pool.shape[1:]))


def ssd_recurrence(x, dt, A, B, C, D, state):
    """The definition, token by token (the oracle of the tests).
    -> (y [b, s, H, P] float32, state)."""
    f32 = jnp.float32

    def step(state, xs):
        xt, dtt, Bt, Ct = xs                # [b,H,P] [b,H] [b,G,N] [b,G,N]
        rep = xt.shape[1] // Bt.shape[1]    # head h reads group h // rep
        Bt, Ct = jnp.repeat(Bt, rep, axis=1), jnp.repeat(Ct, rep, axis=1)
        state = (jnp.exp(dtt * A)[:, :, None, None] * state
                 + jnp.einsum("bhp,bhn->bhpn", dtt[..., None] * xt, Bt,
                              precision="highest"))
        y = jnp.einsum("bhpn,bhn->bhp", state, Ct, precision="highest") \
            + D[:, None] * xt
        return state, y

    xs = tuple(jnp.moveaxis(t.astype(f32), 1, 0) for t in (x, dt, B, C))
    state, y = lax.scan(step, state, xs)
    return jnp.moveaxis(y, 0, 1), state
