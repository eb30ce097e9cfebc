"""1F1B pipeline schedule: fused forward+backward with bounded
activation liveness.

GPipe (parallel/pipeline.py) runs ALL forwards, then reverse-mode AD
replays them backwards — every stage must hold M microbatch inputs
live.  1F1B (PipeDream-flush / Megatron's non-interleaved schedule)
starts microbatch i's backward as soon as it leaves the last stage, so
a stage holds at most S in-flight activations: the activation footprint
drops from O(M) to O(S) microbatches (M = 2S halves it; long schedules
gain more).  Same bubble fraction as GPipe.

Autodiff cannot express this — jax.grad over a forward program runs the
whole forward first — so the schedule here is a MANUAL value-and-grads
program: one ``lax.scan`` over ticks under ``shard_map`` manual over
``pp``; each tick a stage takes its scheduled action (branchy
``lax.cond`` — cores diverge for real in manual mode, so a tick costs
one action, not all of them):

  * F(i): apply the stage block to microbatch i's input, stash the
    input in slot i mod S, hand the output right (ppermute).
  * B(i): re-linearize the stage at the stashed input (jax.vjp =
    recompute + backward — activation-memory-optimal, compute parity
    with GPipe+remat), apply the incoming cotangent, accumulate the
    local parameter gradient, hand the input-cotangent left.
  * last stage folds the loss tail (head + CE) into B, so its F only
    stashes.

The schedule table (which action each stage takes at each tick, and
what the hand-off wires carry) is SIMULATED host-side at trace time and
validated for dependency- and stash-safety, then baked into the scan as
static arrays — the compiled program has no data-dependent control
flow.

Green-field vs the reference (no pipeline engine at all, SURVEY.md
§2.4); schedule shape follows Megatron/PipeDream-flush.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


class Schedule(NamedTuple):
    """Static per-(tick, stage) action tables."""
    do_f: np.ndarray       # [T, S] bool
    f_mb: np.ndarray       # [T, S] int32
    do_b: np.ndarray       # [T, S] bool
    b_mb: np.ndarray       # [T, S] int32
    recv_f: np.ndarray     # [T, S] bool  — store arriving fwd hand-off
    recv_f_mb: np.ndarray  # [T, S] int32
    recv_b: np.ndarray     # [T, S] bool  — store arriving bwd hand-off
    recv_b_mb: np.ndarray  # [T, S] int32


def build_1f1b_schedule(S: int, M: int) -> Schedule:
    """Greedy simulation of the non-interleaved 1F1B schedule, with
    dependency + stash-slot safety asserted."""
    assert M >= S, f"1F1B needs microbatches >= stages ({M} < {S})"
    f_done = [[-1] * M for _ in range(S)]   # tick F(i) completed
    b_done = [[-1] * M for _ in range(S)]
    next_f = [0] * S
    next_b = [0] * S
    # per-stage action pattern: warmup forwards, then 1F1B, then drain
    warmup = [min(S - 1 - r, M) for r in range(S)]
    actions: list[list[tuple]] = [[] for _ in range(S)]

    t = 0
    while any(next_b[r] < M for r in range(S)) and t < 8 * (M + S):
        acts = []
        for r in range(S):
            act = None
            want_f = next_f[r] < M
            want_b = next_b[r] < M
            # steady-state preference: after warmup forwards, do B
            # before the next F (that's what bounds liveness to S)
            prefer_b = want_b and next_f[r] >= warmup[r] + next_b[r]
            order = (("B", "F") if prefer_b or not want_f else ("F", "B"))
            for kind in order:
                if kind == "F" and want_f:
                    i = next_f[r]
                    ready = (r == 0 or (0 <= f_done[r - 1][i] < t))
                    # stash slot i%S must be free: B(i-S) already done
                    slot_free = i < S or b_done[r][i - S] >= 0
                    if ready and slot_free:
                        act = ("F", i)
                        break
                if kind == "B" and want_b:
                    i = next_b[r]
                    ready = (0 <= f_done[r][i] < t if r == S - 1
                             else 0 <= b_done[r + 1][i] < t)
                    if ready:
                        act = ("B", i)
                        break
            acts.append(act)
        for r, act in enumerate(acts):
            if act is None:
                continue
            kind, i = act
            if kind == "F":
                f_done[r][i] = t
                next_f[r] += 1
            else:
                b_done[r][i] = t
                next_b[r] += 1
        for r in range(S):
            actions[r].append(acts[r])
        t += 1
    assert all(next_b[r] == M for r in range(S)), "1F1B schedule stuck"
    T = t

    do_f = np.zeros((T, S), bool)
    f_mb = np.zeros((T, S), np.int32)
    do_b = np.zeros((T, S), bool)
    b_mb = np.zeros((T, S), np.int32)
    for r in range(S):
        for tt, act in enumerate(actions[r]):
            if act is None:
                continue
            kind, i = act
            if kind == "F":
                do_f[tt, r] = True
                f_mb[tt, r] = i
            else:
                do_b[tt, r] = True
                b_mb[tt, r] = i

    # hand-off receive tables: what arrives at tick t was sent at t-1
    recv_f = np.zeros((T, S), bool)
    recv_f_mb = np.zeros((T, S), np.int32)
    recv_b = np.zeros((T, S), bool)
    recv_b_mb = np.zeros((T, S), np.int32)
    for tt in range(1, T):
        for r in range(S):
            if r > 0 and do_f[tt - 1, r - 1]:
                recv_f[tt, r] = True
                recv_f_mb[tt, r] = f_mb[tt - 1, r - 1]
            if r < S - 1 and do_b[tt - 1, r + 1]:
                recv_b[tt, r] = True
                recv_b_mb[tt, r] = b_mb[tt - 1, r + 1]
    return Schedule(do_f, f_mb, do_b, b_mb,
                    recv_f, recv_f_mb, recv_b, recv_b_mb)


def pipeline_value_and_grads_1f1b(
        stage_fn: Callable[[Any, jax.Array], jax.Array],
        last_fn: Callable[[Any, jax.Array, jax.Array], jax.Array],
        x_mb: jax.Array, y_mb: jax.Array,
        stage_params: Any, last_params: Any, *,
        mesh: Mesh, axis: str = "pp"):
    """Fused 1F1B training pass.

    Args:
      stage_fn: ``(local_stage_params, x) -> x`` one stage's block.
      last_fn: ``(last_params, x, y) -> scalar`` loss tail (final norm +
        head + CE) applied to the LAST stage's output per microbatch —
        must return the SUM-convention loss contribution of one
        microbatch such that total loss = mean over microbatches.
      x_mb: [M, mb, ...] pipeline inputs (post-embedding).
      y_mb: [M, mb, ...] per-microbatch targets.
      stage_params: leading-dim layers pytree, sharded over ``axis``.
      last_params: loss-tail params, replicated.

    Returns ``(loss, d_stage_params, d_last_params, d_x_mb)`` — plug
    d_x_mb into the embedding's vjp outside.
    """
    S = mesh.shape[axis]
    M = x_mb.shape[0]
    sched = build_1f1b_schedule(S, M)
    T = sched.do_f.shape[0]
    tables = jax.tree.map(jnp.asarray, sched)
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    bwd_perm = [((i + 1) % S, i) for i in range(S)]
    inv_m = 1.0 / M

    def body(x_mb, y_mb, lp, tp):
        r = lax.axis_index(axis)
        is_last = r == S - 1

        def stage_and_tail(p_stage, p_tail, x, y):
            out = stage_fn(p_stage, x)
            return last_fn(p_tail, out, y) * inv_m

        def tick(carry, tab):
            (stash, dstash, fwd_wire, bwd_wire, dP, dT, dX, loss) = carry
            (do_f, f_mb, do_b, b_mb,
             recv_f, recv_f_mb, recv_b, recv_b_mb) = [x[r] for x in tab]

            # 1. bank last tick's hand-offs into the slot stashes
            stash = lax.cond(
                recv_f,
                lambda s: s.at[recv_f_mb % S].set(fwd_wire), lambda s: s,
                stash)
            dstash = lax.cond(
                recv_b,
                lambda s: s.at[recv_b_mb % S].set(bwd_wire), lambda s: s,
                dstash)

            # 2. forward action
            def run_f(args):
                stash, wire = args
                x_in = jnp.where(r == 0, x_mb[f_mb], stash[f_mb % S])
                stash = stash.at[f_mb % S].set(x_in)
                # the last stage folds its compute into B: F just
                # stashes, the wire content is unused there
                y = lax.cond(is_last, lambda: x_in,
                             lambda: stage_fn(lp, x_in))
                return stash, y

            stash, fwd_out = lax.cond(
                do_f, run_f, lambda a: (a[0], a[1]),
                (stash, fwd_wire))

            # 3. backward action (re-linearize at the stashed input)
            def run_b(args):
                dP, dT, dX, loss = args
                x_in = stash[b_mb % S]

                def at_last():
                    l, vjp = jax.vjp(
                        lambda ps, pt, xi: stage_and_tail(
                            ps, pt, xi, y_mb[b_mb]), lp, tp, x_in)
                    dp, dt, dx = vjp(jnp.ones(()))
                    return l, dp, dt, dx

                def mid():
                    _, vjp = jax.vjp(lambda ps, xi: stage_fn(ps, xi),
                                     lp, x_in)
                    dp, dx = vjp(dstash[b_mb % S])
                    return jnp.zeros(()), dp, \
                        jax.tree.map(jnp.zeros_like, tp), dx

                l, dp, dt, dx = lax.cond(is_last, at_last, mid)
                dP = jax.tree.map(jnp.add, dP, dp)
                dT = jax.tree.map(jnp.add, dT, dt)
                loss = loss + l
                # stage 0's input-cotangent belongs to the embedding
                dX = lax.cond(r == 0,
                              lambda b: b.at[b_mb].set(dx), lambda b: b,
                              dX)
                return (dP, dT, dX, loss), dx

            (dP, dT, dX, loss), bwd_out = lax.cond(
                do_b, run_b,
                lambda a: (a, bwd_wire), (dP, dT, dX, loss))

            # 4. hand-offs for the next tick
            fwd_wire = lax.ppermute(fwd_out, axis, fwd_perm)
            bwd_wire = lax.ppermute(bwd_out, axis, bwd_perm)
            return (stash, dstash, fwd_wire, bwd_wire, dP, dT, dX,
                    loss), None

        mb_shape = x_mb.shape[1:]
        zeros_act = jnp.zeros((S,) + mb_shape, x_mb.dtype)
        carry0 = (zeros_act, zeros_act,
                  jnp.zeros(mb_shape, x_mb.dtype),
                  jnp.zeros(mb_shape, x_mb.dtype),
                  jax.tree.map(jnp.zeros_like, lp),
                  jax.tree.map(jnp.zeros_like, tp),
                  jnp.zeros_like(x_mb),
                  jnp.zeros(()))
        (stash, dstash, _, _, dP, dT, dX, loss), _ = lax.scan(
            tick, carry0, tables)
        # loss and tail grads live on the last stage; dX on stage 0 —
        # psum replicates each (zeros elsewhere).  dP stays LOCAL: its
        # out_spec concatenates the per-stage layer blocks back into
        # the full leading-layers gradient.
        loss = lax.psum(loss, axis)
        dT = jax.tree.map(lambda v: lax.psum(v, axis), dT)
        dX = lax.psum(dX, axis)
        return (loss[None], dP,
                jax.tree.map(lambda v: v[None], dT), dX[None])

    in_specs = (P(), P(), jax.tree.map(lambda _: P(axis), stage_params),
                jax.tree.map(lambda _: P(), last_params))
    out_specs = (P(axis), jax.tree.map(lambda _: P(axis), stage_params),
                 jax.tree.map(lambda _: P(axis), last_params), P(axis))
    loss, dP, dT, dX = shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names={axis}, check_vma=False)(x_mb, y_mb, stage_params,
                                            last_params)
    return (loss[0], dP, jax.tree.map(lambda v: v[0], dT), dX[0])
