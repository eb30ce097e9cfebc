"""The gated short convolution's recurrence: a depthwise causal
convolution of ``L`` taps with no bias and no activation, whose whole
past is the last ``L - 1`` inputs.

    c_t = sum_{j=0..L-1} w[j] * v_{t-(L-1)+j}          (w [L, d]; tap L-1
                                                        multiplies v_t)
    state after token t: v_{t-L+2} .. v_t             ([L-1, d])

Two forms, as ``ops/ssm.py`` and ``ops/delta_rule.py`` have them: the
window form over ``[state | v]`` (a prefill chunk, a full sequence from
zero state) and the one-token step on the rows' state.  Because the
state IS inputs, the state after ANY token of a window is a slice of
``[state | v]`` (``state_at``): a cache can keep the state at every
block boundary a window crosses for the price of a gather, with no
second pass — what no SSM or matrix state allows.  The taps are a few
multiply-adds an element beside the two products on either side of them
(``models/hybrid.py``), so both forms are plain ``jax.numpy``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def conv_window(v, state, w):
    """v [b, s, d] the window's inputs; state [b, L-1, d] the L-1 inputs
    before it; w [L, d].  -> (c [b, s, d] in v's dtype, ``full`` [b,
    L-1+s, d] = [state | v]: what ``state_at`` slices)."""
    L, s = w.shape[0], v.shape[1]
    full = jnp.concatenate([state.astype(v.dtype), v], axis=1)
    out = 0.0
    for j in range(L):
        out = out + full[:, j:j + s].astype(jnp.float32) \
            * w[j].astype(jnp.float32)
    return out.astype(v.dtype), full


def state_at(full, n, width: int):
    """The state after the window's first ``n`` [b] tokens (0: the state
    the window arrived with): ``full[:, n : n + width]``, [b, width,
    d]."""
    return jax.vmap(
        lambda row, k: lax.dynamic_slice_in_dim(row, k, width, axis=0)
    )(full, n)


def conv_step(v, state, w, active):
    """ONE token a row: v [rows, d], state [rows, L-1, d], ``active``
    [rows] (0: the row sits the step out and its state comes back as it
    was).  -> (c [rows, d], state)."""
    full = jnp.concatenate([state.astype(v.dtype), v[:, None]], axis=1)
    c = jnp.einsum("rjd,jd->rd", full.astype(jnp.float32),
                   w.astype(jnp.float32)).astype(v.dtype)
    new = jnp.where((active > 0)[:, None, None], full[:, 1:], full[:, :-1])
    return c, new.astype(state.dtype)


def conv_loop(v, w):
    """The recurrence token by token from zero state, float32: what the
    two forms are held to.  v [s, d] -> c [s, d]."""
    L = w.shape[0]
    past = [jnp.zeros_like(v[0], jnp.float32)] * (L - 1)
    out = []
    for t in range(v.shape[0]):
        taps = past + [v[t].astype(jnp.float32)]
        out.append(sum(w[j].astype(jnp.float32) * taps[j] for j in range(L)))
        past = taps[1:]
    return jnp.stack(out)
