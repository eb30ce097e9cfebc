"""Pipeline parallelism: GPipe-style microbatch schedule over the ``pp``
mesh axis.

TPU-native design (green-field — the reference has no pipeline engine;
SURVEY.md §2.4 makes PP a first-class axis requirement): the layer stack
is sharded over ``pp`` (each stage holds a contiguous block of layers),
the batch is split into M microbatches, and one compiled ``lax.scan``
runs T = M + S - 1 ticks.  Each tick every stage applies its layer block
to its resident microbatch, then hands the activation to the next stage
with a single-hop ``ppermute`` riding the ICI ring.  Reverse-mode AD
through the scan + ppermute yields the mirrored backward pipeline
automatically — fill/drain bubble fraction (S-1)/(T), so more
microbatches amortize it.

The stage loop runs under ``shard_map`` manual ONLY over ``pp``
(``axis_names={"pp"}``): dp/fsdp/tp axes stay in GSPMD auto mode, so the
per-stage compute keeps its usual logical-axis sharding constraints and
XLA still inserts the tensor-parallel collectives inside each stage.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def num_stages(mesh: Mesh) -> int:
    return mesh.shape.get("pp", 1)


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   x_mb: jax.Array, stage_params: Any, *,
                   mesh: Mesh, axis: str = "pp",
                   carry_aux: bool = False):
    """Run ``stage_fn`` as an S-stage pipeline over microbatched inputs.

    Args:
      stage_fn: ``(local_stage_params, x) -> x`` — applies ONE stage's
        layer block; input/output shapes must match (residual stream).
        With ``carry_aux``: ``(lp, x, aux) -> (x, aux)`` where ``aux``
        is a scalar accumulated ACROSS stages (it rides the same
        ppermute hand-off as the activation — the MoE load-balance loss
        for MoE+pp composition).
      x_mb: ``[M, mb, ...]`` microbatched activations, replicated over
        ``axis`` (other mesh axes stay auto-sharded).
      stage_params: pytree whose leaves have a leading layers dim
        divisible by the stage count; sharded over ``axis`` on dim 0.
      mesh: mesh containing ``axis``.

    Returns ``[M, mb, ...]`` final-stage outputs, plus (with
    ``carry_aux``) the summed aux scalar over all microbatches+stages.
    """
    S = mesh.shape[axis]
    M = x_mb.shape[0]
    if S == 1:
        return _single_stage(stage_fn, x_mb, stage_params,
                             carry_aux=carry_aux)
    perm = [(i, (i + 1) % S) for i in range(S)]

    def body(x_mb, lp):
        r = lax.axis_index(axis)

        def tick(carry, t):
            state, aux_state, outs, aux_done = carry
            mbi = jnp.clip(t, 0, M - 1)
            fresh = x_mb[mbi]
            # stage 0 injects a fresh microbatch; later stages consume
            # the activation handed over by the previous stage last tick
            x = jnp.where(r == 0, fresh, state)
            aux_in = jnp.where(r == 0, 0.0, aux_state)
            if carry_aux:
                x, aux_in = stage_fn(lp, x, aux_in)
            else:
                x = stage_fn(lp, x)
            li = t - (S - 1)
            ci = jnp.clip(li, 0, M - 1)
            valid = li >= 0  # li < M always holds: t <= M+S-2
            outs = outs.at[ci].set(jnp.where(valid, x, outs[ci]))
            # the LAST stage banks each microbatch's completed aux sum
            aux_done = aux_done + jnp.where(
                valid & (r == S - 1), aux_in, 0.0)
            state = lax.ppermute(x, axis, perm)
            aux_state = lax.ppermute(aux_in, axis, perm)
            return (state, aux_state, outs, aux_done), None

        state0 = jnp.zeros_like(x_mb[0])
        outs0 = jnp.zeros_like(x_mb)
        (_, _, outs, aux_done), _ = lax.scan(
            tick, (state0, jnp.zeros(()), outs0, jnp.zeros(())),
            jnp.arange(M + S - 1))
        # per-stage buffers stack over pp; only the last stage's slice
        # holds final-layer activations — the caller reads [-1].  The
        # aux total lives on the last stage; psum replicates it.
        aux_total = lax.psum(aux_done, axis)
        return outs[None], aux_total[None]

    in_specs = (P(), jax.tree.map(lambda _: P(axis), stage_params))
    staged, aux = shard_map(body, mesh=mesh, in_specs=in_specs,
                            out_specs=(P(axis), P(axis)),
                            axis_names={axis},
                            check_vma=False)(x_mb, stage_params)
    if carry_aux:
        return staged[-1], aux[0]
    return staged[-1]


def _single_stage(stage_fn, x_mb, stage_params, carry_aux=False):
    """Degenerate pp=1 path: plain scan over microbatches."""
    if carry_aux:
        def mb_step(acc, x):
            y, a = stage_fn(stage_params, x, jnp.zeros(()))
            return acc + a, y
        aux, outs = lax.scan(mb_step, jnp.zeros(()), x_mb)
        return outs, aux

    def mb_step(_, x):
        return None, stage_fn(stage_params, x)
    _, outs = lax.scan(mb_step, None, x_mb)
    return outs
