"""Ring attention: exact attention over a sequence-sharded axis.

Green-field capability (SURVEY.md §5 "long-context … not present" in the
reference): each `sp` shard holds a contiguous sequence block of q/k/v;
kv blocks rotate around the ICI ring with ``jax.lax.ppermute`` while every
shard folds the incoming block into an online-softmax accumulator.  After
``axis_size`` steps each query position has attended to the full sequence,
with peak memory O(s_local²) and the permute overlapping compute (XLA
schedules the ppermute DMA concurrently with the block matmuls).

Use inside ``shard_map`` with sequence dim sharded over ``axis_name``;
the train layer wires this up when the mesh has an `sp` axis.  The whole
computation is differentiable — jax autodiffs through ppermute, giving the
reverse ring for gradients.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def ring_attention(q, k, v, axis_name: str, *, causal: bool = True,
                   scale: Optional[float] = None) -> jax.Array:
    """Exact attention, q/k/v = local shards [b, h, s_local, d].

    Global sequence order = shard order along `axis_name` (shard i holds
    positions [i*s_local, (i+1)*s_local)).
    """
    s = (q.shape[-1] ** -0.5) if scale is None else scale
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, h, sl, d = q.shape
    qf = q.astype(jnp.float32)

    q_pos = my_idx * sl + jnp.arange(sl)  # global positions of local q
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def step(carry, i):
        acc, m_prev, l_prev, k_cur, v_cur = carry
        # after i forward rotations we hold the kv of shard (my_idx - i)
        src = (my_idx - i) % axis_size
        logits = jnp.einsum("bhqd,bhkd->bhqk", qf,
                            k_cur.astype(jnp.float32),
                            preferred_element_type=jnp.float32) * s
        if causal:
            k_pos = src * sl + jnp.arange(sl)
            mask = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(mask[None, None], logits, NEG_INF)
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(logits - m_next)
        l_next = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_cur.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (acc, m_next, l_next, k_nxt, v_nxt), None

    acc0, m0, l0 = jax.lax.pcast(
        (jnp.zeros((b, h, sl, d), jnp.float32),
         jnp.full((b, h, sl, 1), NEG_INF, jnp.float32),
         jnp.zeros((b, h, sl, 1), jnp.float32)), (axis_name,),
        to="varying")
    (acc, m, l, _, _), _ = jax.lax.scan(
        step, (acc0, m0, l0, k, v), jnp.arange(axis_size))
    l = jnp.maximum(l, 1e-30)
    return (acc / l).astype(q.dtype)
