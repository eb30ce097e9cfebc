"""Mamba-2 mixer core: causal depthwise convolution + selective
state-space recurrence (Dao & Gu 2024, "state space duality").

Per head (state ``S`` is ``[P, N]``, head width P, state width N)::

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t B_t^T
    y_t = S_t C_t + D * x_t

Two forms of the same recurrence, chosen by the window's static width:

  * a WINDOW of tokens (``ssd_window``): the chunked scan.  Inside a
    chunk of ``chunk`` tokens the outputs are one masked matrix product
    (the quadratic, "attention-like" form); the state crosses chunks
    through a ``lax.scan``, so a window of any width costs one compiled
    chunk body.  It takes the state the row arrives with and returns
    the state it leaves with.
  * ONE token (``ssd_step``): the recurrence itself, elementwise on the
    state — a decode pass reads and writes each row's state once.

Both take ``n_valid`` [b]: only the first ``n_valid`` tokens of a row's
window are real.  A token past it has its ``dt`` set to 0, which makes
it the identity on the state (decay exp(0) = 1, input 0), so a padded
last chunk of a prompt, and a row that sits a decode pass out, leave
their state exactly as it was.  The convolution's state (the last
``K - 1`` inputs) is likewise taken at ``n_valid``, not at the window's
end.

The state is float32 and every product that touches it is computed in
float32: the one-token form on the vector unit (multiply + sum), the
chunk form as matmuls at ``Precision.HIGHEST`` (a float32 ``dot`` at
the default precision runs in bfloat16 passes on a TPU); at one chunk
of 256 tokens that is ~6 GFLOP a layer, nothing beside the projections.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def causal_conv(x, state, w, b, n_valid):
    """Depthwise causal convolution of width K over a window.

    x [b, s, C] window inputs; state [b, K-1, C] the K-1 inputs before
    the window; w [K, C] (tap K-1 multiplies the current token), b [C];
    n_valid [b].  -> (silu(conv) [b, s, C], new state [b, K-1, C] = the
    last K-1 inputs up to and including token n_valid - 1)."""
    k = w.shape[0]
    s = x.shape[1]
    full = jnp.concatenate([state.astype(x.dtype), x], axis=1)  # [b,K-1+s,C]
    out = b.astype(jnp.float32)
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


def ssd_step(x, dt, A, B, C, D, state, n_valid):
    """One token.  x [b, 1, H, P], dt [b, 1, H] (after softplus), A, D
    [H], B, C [b, 1, N] (one group), state [b, H, P, N] float32.
    -> (y [b, 1, H, P] float32, state)."""
    dt = _valid_dt(dt.astype(jnp.float32), n_valid)[:, 0]       # [b, H]
    xf = x[:, 0].astype(jnp.float32)                            # [b, H, P]
    Bf, Cf = B[:, 0].astype(jnp.float32), C[:, 0].astype(jnp.float32)
    decay = jnp.exp(dt * A)                                     # [b, H]
    state = (decay[:, :, None, None] * state
             + (dt[..., None] * xf)[..., None] * Bf[:, None, None, :])
    y = (state * Cf[:, None, None, :]).sum(-1) + D[:, None] * xf
    return y[:, None], state


def _ssd_chunk(x, dt, A, B, C, state):
    """One chunk of Q tokens in the quadratic form.  x [b, Q, H, P]
    float32, dt [b, Q, H] float32 (0 = identity token), B, C [b, Q, N],
    state [b, H, P, N] float32.  -> (y [b, Q, H, P] float32 without the
    D term, state after the chunk)."""
    hi = lax.Precision.HIGHEST       # float32 products, not bf16 passes
    dA = dt * A                                                 # [b, Q, H]
    cs = jnp.cumsum(dA, axis=1)                                 # inclusive
    # decay from token s (exclusive) to token l (inclusive), s <= l
    seg = cs[:, :, None, :] - cs[:, None, :, :]                 # [b, l, s, H]
    q = x.shape[1]
    tri = jnp.tril(jnp.ones((q, q), bool))
    L = jnp.where(tri[None, :, :, None], jnp.exp(seg), 0.0)     # [b, l, s, H]
    G = jnp.einsum("bln,bsn->bls", C, B, precision=hi)          # [b, l, s]
    xdt = x * dt[..., None]                                     # [b, Q, H, P]
    y = jnp.einsum("blsh,bshp->blhp", G[..., None] * L, xdt, precision=hi)
    # what the incoming state adds: y_l += exp(cs_l) * (state C_l)
    sc = jnp.einsum("bhpn,bln->blhp", state, C, precision=hi)
    y = y + jnp.exp(cs)[..., None] * sc
    # the state after the chunk
    to_end = jnp.exp(cs[:, -1:, :] - cs)                        # [b, Q, H]
    add = jnp.einsum("bshp,bsn->bhpn", xdt * to_end[..., None], B,
                     precision=hi)
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


def ssd(x, dt, A, B, C, D, state, n_valid, chunk: int):
    """The recurrence over a window, in the form its static width
    calls for."""
    if x.shape[1] == 1:
        return ssd_step(x, dt, A, B, C, D, state, n_valid)
    return ssd_window(x, dt, A, B, C, D, state, n_valid, chunk)


def ssd_recurrence(x, dt, A, B, C, D, state):
    """The definition, token by token (the oracle of the tests).
    -> (y [b, s, H, P] float32, state)."""
    f32 = jnp.float32

    def step(state, xs):
        xt, dtt, Bt, Ct = xs                      # [b,H,P] [b,H] [b,N] [b,N]
        state = (jnp.exp(dtt * A)[:, :, None, None] * state
                 + jnp.einsum("bhp,bn->bhpn", dtt[..., None] * xt, Bt,
                              precision="highest"))
        y = jnp.einsum("bhpn,bn->bhp", state, Ct, precision="highest") \
            + D[:, None] * xt
        return state, y

    xs = tuple(jnp.moveaxis(t.astype(f32), 1, 0) for t in (x, dt, B, C))
    state, y = lax.scan(step, state, xs)
    return jnp.moveaxis(y, 0, 1), state
