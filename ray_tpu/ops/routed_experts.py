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
grouped matmuls over those runs (``grouped_matmul``: the work is the
assignments' and not tokens x experts, and an expert no token chose is
not read).  Assignments to absent experts sort past the last run,
are multiplied by nothing and carry a gate of 0.

Three published forms of the router, two of an expert:

  * no selection ``bias``: the k largest router logits, gates their
    softmax; with a ``bias`` [E]: scores ``sigmoid(logits)``, the k
    largest of ``score + bias`` chosen, gates the UNBIASED scores of the
    chosen, normalised to sum 1 (``eps`` added to the sum first, where a
    published recipe has one) and times ``scale``; with ``groups``
    (n_group, topk_group): scores the softmax over ALL E logits in
    float32, the experts in n_group consecutive groups of which only
    the topk_group with the largest MAXIMUM score may be chosen from
    (device-limited routing: a group is a device's experts), the k
    largest scores left chosen, gates those scores times ``scale``,
    normalised to sum 1 first only if ``normalise``;
  * ``gated``: ``W_out (silu(a) * b)``, ``[a | b] = W_in h`` with ``w_in
    [.., d, 2f]``; else ``W_out relu(W_in h)^2`` with ``w_in [.., d,
    f']``, f' = f or f rounded up to whole 128-lane tiles with zero
    columns (see ``lanes``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def route(h, w_router, top_k: int, bias=None, scale: float = 1.0,
          groups: tuple = (), normalise: bool = True, eps: float = 0.0):
    """h [T, d], w_router [d, E] -> (experts [T, k] int32, gates [T, k]
    float32): see the module's text for the three forms."""
    logits = jnp.dot(h, w_router.astype(h.dtype),
                     preferred_element_type=jnp.float32)
    if groups:
        n_group, topk_group = groups
        scores = jax.nn.softmax(logits, axis=-1)
        limited = scores
        if n_group > 1:
            T, E = scores.shape
            best = scores.reshape(T, n_group, E // n_group).max(-1)
            _, keep = lax.top_k(best, topk_group)               # [T, g]
            kept = (jnp.arange(n_group)[None, :, None]
                    == keep[:, None, :]).any(-1)                # [T, G]
            limited = jnp.where(jnp.repeat(kept, E // n_group, axis=1),
                                scores, 0.0)
        gates, experts = lax.top_k(limited, top_k)
        if normalise:
            gates = gates / gates.sum(-1, keepdims=True)
        return experts.astype(jnp.int32), gates * scale
    if bias is None:
        top, experts = lax.top_k(logits, top_k)
        return experts.astype(jnp.int32), jax.nn.softmax(top, axis=-1)
    scores = jax.nn.sigmoid(logits)
    _, experts = lax.top_k(scores + bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    total = chosen.sum(-1, keepdims=True)
    gates = chosen / (total + eps if eps else total) * scale
    return experts.astype(jnp.int32), gates


LANES = 128


def lanes(width: int) -> int:
    """``width`` rounded up to whole 128-lane tiles: what the minor dim
    of a stacked ``w_in`` is STORED at.  The grouped matmul's kernel
    wants its right operand ``[experts, d, width]`` with ``width``
    minor; the TPU pads a minor dim to whole tiles anyway, and for a
    width that is not a multiple (1856 = 14.5 tiles) it prefers to hold
    the array the other way round and re-lays it out for the kernel on
    every pass — a 660 MB copy a layer at 64 experts of 2688 x 1856,
    seen in the described-chip compile.  Stored with its zero columns
    the array has one natural layout and is read where it lies."""
    return -(-width // LANES) * LANES


def _act(ab, f: int, gated: bool):
    """The hidden activation, ``f`` wide: ``silu(a) * b`` of ``ab = [a |
    b]``, or the square of ``relu`` of ``ab``'s first f columns."""
    if gated:
        a, b = jnp.split(ab, 2, axis=-1)
        return jax.nn.silu(a) * b
    return jnp.square(jax.nn.relu(ab[..., :f]))


def mlp(h, w_in, w_out, gated: bool = True):
    """h [T, d], w_in [d, 2f] (gated) or [d, f'], w_out [f, d]: one
    expert."""
    return jnp.dot(_act(jnp.dot(h, w_in.astype(h.dtype)), w_out.shape[0],
                        gated), w_out.astype(h.dtype))


# bytes of one ``[k, tn]`` tile of an expert's matrix in VMEM (double
# buffered beside the rows' tile and the accumulator, inside the 16 MB a
# kernel has without asking)
TILE_BYTES = 7 * 2 ** 19


def grouped_matmul(x, w, group_sizes):
    """x [m, k], its rows sorted by group; w [g, k, n]; group_sizes [g]
    -> [m, n]: rows of group i times ``w[i]``.  Rows past the last
    group hold nothing a caller may read.

    The Pallas grouped matmul that ships with JAX (``megablox.gmm``),
    given whole-k tiles ``[k, tn]`` of a few MB, tn the largest divisor
    of n in whole lane tiles that fits ``TILE_BYTES``: ~200 grid steps a
    matmul, an expert's matrix streamed once at 65-78 % of the HBM's
    rate at both published layouts (PR 38, on the chip).  The compiler's
    own (``lax.ragged_dot``) tiles k and n by powers of two: 50 % at
    4096 x 1536, and where a dim is an ODD number of 128-lane tiles
    (2688 = 21, 1920 = 15) it falls to 128 x 128 tiles, 20,160 grid
    steps, 12 %."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from ray_tpu.ops.flash_attention import _interpret_mode

    m, k = x.shape
    n = w.shape[2]
    fits = [t for t in range(LANES, n + 1, LANES)
            if n % t == 0 and k * t * w.dtype.itemsize <= TILE_BYTES]
    tn = max(fits) if fits else n if n < LANES else LANES
    tm = 128
    x = jnp.pad(x, ((0, -m % tm), (0, 0)))
    return gmm(x, w, group_sizes.astype(jnp.int32),
               preferred_element_type=x.dtype, tiling=(tm, k, tn),
               interpret=_interpret_mode())[:m]


def routed_experts(h, w_router, w_in, w_out, *, top_k: int,
                   held: tuple, valid=None, gated: bool = True, bias=None,
                   scale: float = 1.0, groups: tuple = (),
                   normalise: bool = True, eps: float = 0.0):
    """The held experts' part of a routed-expert layer.

    h [T, d]; w_router [d, E]; w_in [E_held, d, 2f or f'], w_out
    [E_held, f, d] the weights of experts ``held[0] .. held[1] - 1`` in
    the form ``gated`` says; ``valid`` [T] bool marks real tokens
    (padding joins no expert's run, gets no routed part and is not
    counted), or [P, T] the real tokens of P sets counted apart; ``bias`` [E],
    ``scale``, ``groups``, ``normalise`` and ``eps`` as ``route`` takes
    them.
    -> (out [T, d], counts [E_held] int32: real assignments per held
        expert, total int32: real assignments to ANY expert; [P, E_held]
        and [P] for P sets)."""
    T, d = h.shape
    lo, hi = held
    n_held = hi - lo
    if w_in.shape[0] != n_held:
        raise ValueError(f"{w_in.shape[0]} expert weights for the held "
                         f"range {held}")
    experts, gates = route(h, w_router, top_k, bias, scale, groups,
                           normalise, eps)
    if valid is None:
        valid = jnp.ones((T,), bool)
    flat = experts.reshape(-1)                                  # [T*k]
    # padding joins no expert's run: a decode pass's empty rows would
    # else have the experts of THEIR choice streamed for nothing
    real = jnp.repeat(valid if valid.ndim == 1 else valid.any(0), top_k)
    mine = (flat >= lo) & (flat < hi) & real
    local = jnp.where(mine, flat - lo, n_held)      # absent: past the runs
    order = jnp.argsort(local, stable=True)
    token = order // top_k
    group_sizes = jnp.bincount(local, length=n_held + 1)[:n_held] \
        .astype(jnp.int32)
    x = h[token]                                                # [T*k, d]
    ab = grouped_matmul(x, w_in.astype(h.dtype), group_sizes)
    y = grouped_matmul(_act(ab, w_out.shape[1], gated),
                       w_out.astype(h.dtype), group_sizes)      # [T*k, d]
    gate = jnp.where(mine, gates.reshape(-1), 0.0)[order]
    y = jnp.where(gate[:, None] > 0, y.astype(jnp.float32), 0.0) \
        * gate[:, None]
    # back to (token, choice) order: a gather, then the k parts add up
    out = y[jnp.argsort(order)].reshape(T, top_k, d).sum(1)

    def count(valid):
        real = jnp.repeat(valid, top_k)
        counts = jnp.bincount(jnp.where(real, local, n_held),
                              length=n_held + 1)[:n_held].astype(jnp.int32)
        return counts, (valid.sum() * top_k).astype(jnp.int32)

    if valid.ndim == 1:
        counts, total = count(valid)
    else:
        counts, total = (jnp.stack(t) for t in zip(*map(count, valid)))
    return out.astype(h.dtype), counts, total
