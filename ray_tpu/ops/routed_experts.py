"""Dropless routed experts: every token goes to its top-k experts, no
capacity, no dropped token.

The layer is told WHICH experts it holds (``held = (lo, hi)``, a range
of the router's outputs) — what expert parallelism asks of a layer, and
what one chip's share of a deployment is.  It routes over ALL experts
(the router keeps its published width and top-k), computes the part of
the result that its own experts give for the tokens routed to them, and
leaves out what the absent experts would have added: on several chips
the parts are summed by the exchange, on one chip there is none, and
nothing here stands in for it.

Mechanics: the (token, choice) assignments are sorted by expert, so each
held expert's tokens lie in one run of rows, and the two projections are
grouped matmuls over those runs (``jax.lax.ragged_dot``; the TPU
compiler has its own kernel for it, the work is the assignments' and not
tokens x experts).  Assignments to absent experts sort past the last run,
are multiplied by nothing and carry a gate of 0.

Each expert is a gated MLP: ``W_out (silu(a) * b)``, ``[a | b] = W_in h``.
Gates are the softmax over the k chosen router logits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def route(h, w_router, top_k: int):
    """h [T, d], w_router [d, E] -> (experts [T, k] int32, gates [T, k]
    float32): the k largest router logits and the softmax over them."""
    logits = jnp.dot(h, w_router.astype(h.dtype),
                     preferred_element_type=jnp.float32)
    top, experts = lax.top_k(logits, top_k)
    return experts.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


def gated_mlp(h, w_in, w_out):
    """h [T, d], w_in [d, 2f], w_out [f, d]: W_out (silu(a) * b)."""
    a, b = jnp.split(jnp.dot(h, w_in.astype(h.dtype)), 2, axis=-1)
    return jnp.dot(jax.nn.silu(a) * b, w_out.astype(h.dtype))


def routed_experts(h, w_router, w_in, w_out, *, top_k: int,
                   held: tuple, valid=None):
    """The held experts' part of a routed-expert layer.

    h [T, d]; w_router [d, E]; w_in [E_held, d, 2f], w_out [E_held, f, d]
    the weights of experts ``held[0] .. held[1] - 1``; ``valid`` [T] bool
    marks real tokens (padding routes like any token but is not
    counted).
    -> (out [T, d], counts [E_held] int32: real assignments per held
        expert, total int32: real assignments to ANY expert)."""
    T, d = h.shape
    lo, hi = held
    n_held = hi - lo
    if w_in.shape[0] != n_held:
        raise ValueError(f"{w_in.shape[0]} expert weights for the held "
                         f"range {held}")
    experts, gates = route(h, w_router, top_k)
    flat = experts.reshape(-1)                                  # [T*k]
    mine = (flat >= lo) & (flat < hi)
    local = jnp.where(mine, flat - lo, n_held)      # absent: past the runs
    order = jnp.argsort(local, stable=True)
    token = order // top_k
    group_sizes = jnp.bincount(local, length=n_held + 1)[:n_held] \
        .astype(jnp.int32)
    x = h[token]                                                # [T*k, d]
    ab = lax.ragged_dot(x, w_in.astype(h.dtype), group_sizes)
    a, b = jnp.split(ab, 2, axis=-1)
    y = lax.ragged_dot(jax.nn.silu(a) * b, w_out.astype(h.dtype),
                       group_sizes)                             # [T*k, d]
    gate = jnp.where(mine, gates.reshape(-1), 0.0)[order]
    y = jnp.where(gate[:, None] > 0, y.astype(jnp.float32), 0.0) \
        * gate[:, None]
    # back to (token, choice) order: a gather, then the k parts add up
    out = y[jnp.argsort(order)].reshape(T, top_k, d).sum(1)
    if valid is None:
        valid = jnp.ones((T,), bool)
    real = jnp.repeat(valid, top_k)
    counts = jnp.bincount(jnp.where(real, local, n_held),
                          length=n_held + 1)[:n_held].astype(jnp.int32)
    total = (valid.sum() * top_k).astype(jnp.int32)
    return out.astype(h.dtype), counts, total
