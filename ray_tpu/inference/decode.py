"""Incremental (KV-cache) decode for the GPT: paged decode, chunked
prefill, speculative verify and draft, and the full-width prefill.

All programs have STATIC shapes so each compiles exactly once
regardless of request mix — and (no-mesh path) once per (config,
rules, geometry) across ALL engines, so a fleet scaling out replicas
or multiplexing model variants reuses the compiled set instead of
paying a per-engine recompile.

Every program runs the model's ONE layer function
(``gpt._transformer_layer``) on its window of tokens and hands it the
attention step.  The four paged programs hand it ``paged_attend``: the
window's K/V is committed to the block pool (cache.BlockPool) at the
window's (block, offset) pairs, and each query then attends what its
length or mask admits of the committed pool.  The ONE-TOKEN programs
(decode step, draft burst; the hybrid family's decode step in
recurrent.py) give a length a row and attend through one Pallas kernel
that walks each live row's block table over the pool as stored and
reads the blocks that hold a key, no other
(ops/attention.paged_decode_attention); the WINDOW programs (chunk
prefill, verify) give a mask, gather their rows' tables and attend them
as stored (ops/attention.packed_attention).  What a program states
itself is what differs: the index arithmetic of its window (dead lanes
go to the scratch block), the embedding, the head.  ``latent_attend`` is
the same meeting place for a model whose attention layers cache ONE
latent a token (the hybrid family's latent layout, recurrent.py): one
pool, the one-token form with the up-projection absorbed
(ops/attention.latent_decode_attention), the window form a block of
decompressed keys at a time (latent_window_attention).

  * chunk_prefill — a fixed-width window of ONE prompt ([C] tokens at
    positions start..start+C), each query row masked to its OWN causal
    horizon over the gathered table (earlier chunks' K/V included).
    Long prompts therefore prefill as a sequence of bounded-cost steps
    the engine interleaves with decode iterations — a long prompt
    stops stalling neighbors' token cadence.
  * paged_decode_step — one token for EVERY row at once, each live
    row over the blocks of its valid prefix (the table-walking kernel;
    ops/attention.paged_attention is the formulation it is held to);
    inactive rows write to the scratch block, attend nothing and get
    zeros.

    These two are what a serving pass runs, and they exchange small
    integers with the host: ONE packed int32 array in (``pack_step`` /
    ``pack_chunk``), and beside the logits, which stay on the device,
    the greedy tokens out — the argmax of the float32 logits taken
    inside the program (every row's; the chunk's last real
    position's).  recurrent.py's two programs do the same.  The same
    tokens also STAY on the device, in the token array every program of
    a pass carries beside the pools (``feed``, int32 a decode row,
    donated): a step reads a row's input token there where the packed
    array says ``FEED`` and writes every stepped row's greedy token
    back, a chunk writes its own at its row — so the engine can launch
    the next pass before it has read this one's integers.
  * paged_step_chunk — the two above as ONE program, for the pass that
    holds both a chunk and decoding rows: the rows' tokens and the
    chunk's are one window of the layer function, so a layer's weights
    stream once for both, and only the attention step treats the two
    parts apart (``paged_attend`` with a length a row AND a mask).
  * spec_verify_step — the decode step widened to a [b, W] token
    window (W = speculate_k + 1): column 0 is each row's current input
    token, columns 1.. are DRAFTED continuations.  One call scores all
    W positions per row (each query masked to its own causal horizon,
    exactly the chunk-prefill formulation batched over rows) and lands
    every position's K/V in the donated pool — draft-then-verify
    speculation's verify pass (Leviathan et al. 2023).  Lanes past a
    row's real draft count are redirected to the scratch block so a
    short draft can ride a fixed-width program.
  * paged_draft_step — the truncated-layer self-draft BURST: k
    autoregressive draft tokens in one compiled call (a lax.scan over
    draft positions, each scanning only the FIRST ``draft_layers``
    layers straight into the head, argmax feeding the next step — zero
    extra weights).  K/V for layers < draft_layers are
    bit-identical to what the full model writes at those layers (layer
    l only depends on layers < l), so drafting through the real pool
    corrupts nothing, and the verify pass overwrites every drafted
    position at all layers anyway.
  * prefill — the training forward with ``return_kv=True`` over a
    prompt padded to the cache width: the engine seeds a cold long
    prompt's blocks from one call of it.

The host-side n-gram drafter (``ngram_propose`` — prompt-lookup
decoding, Saxena 2023) lives here too: it proposes the continuation
that followed the most recent earlier occurrence of the sequence's
trailing n-gram.  Zero weights, zero device work — repetitive
generations (and shared-prefix serving mixes) accept most of it.

With a mesh the programs are sharding-annotated for Megatron-style
tensor parallelism: pools heads-sharded per POOL_AXES, per-device
attention over local heads (the one-token kernel inside ``shard_map``,
each shard on its own slice of the pool's width and its share of the
rows), one collective at the output projection,
the donated pool committed per shard (the scatter's indexed dims — row,
offset — are unsharded).  MoE configs dispatch through gpt._moe_mlp per
token window.  The pools' stored layout is defined once, in
cache.PoolLayout; the programs touch them through its ``commit``, its
``read`` and the kernel (which takes ``PoolLayout.rows`` of the layer's
block 0 and adds the table's ids) only.  Greedy token-parity with full-recompute
``generate()`` is pinned by tests/test_inference.py +
tests/test_paged_cache.py (mesh=None) and tests/test_sharded_decode.py
(multi-device CPU meshes).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.inference.cache import POOL_AXES, PoolLayout, heads_shards
from ray_tpu.models import gpt
from ray_tpu.models.gpt import GPTConfig
from ray_tpu.ops.attention import (KEY_BLOCK, _per_shard,
                                   head_window_attention,
                                   latent_decode_attention,
                                   latent_window_attention, packed_attention,
                                   paged_decode_attention, real_positions)
from ray_tpu.parallel.sharding import DEFAULT_LLM_RULES, Rules, spec_for


class SpeculationUnsupported(ValueError):
    """Speculative decoding was requested for a configuration that has
    no speculation path.  Typed and raised at engine CONSTRUCTION time
    so the gap fails early and callers can tell the known capability
    boundary from a generic failure: the self-drafter needs
    ``1 <= draft_layers < n_layers`` (a full-depth draft is just the
    model twice), and a model with a recurrent state has no rollback.
    ``temperature > 0`` requests are NOT an error — they
    transparently fall back to non-speculative decode per row (see
    InferenceEngine.submit)."""


# engines with the same (cfg, rules, mesh) share ONE jitted
# prefill/step pair: the compiled programs are stateless (params/cache
# are arguments; donation is per-call), and a fleet of N replicas x M
# model variants would otherwise pay N*M identical compilations — a
# multi-second head-of-line stall every time the autoscaler grows or
# the multiplexer loads a variant.  Meshed engines key on the mesh's
# IDENTITY plus its axis shape: a Mesh is not hashable-by-value across
# tests, but the same mesh object reused by every replica of a sharded
# fleet must hit the cache (the exact regression the no-mesh path fixed
# once already).  The shape tuple bounds the blast radius of id() reuse
# after GC: a recycled id only collides with a mesh of identical axes.
_FN_CACHE: dict = {}


def _cached(kind: str, cfg: GPTConfig, mesh, rules, build):
    mesh_key = (None if mesh is None
                else (id(mesh), tuple(mesh.shape.items())))
    key = (kind, cfg, mesh_key,
           rules if isinstance(rules, tuple) else id(rules))
    fn = _FN_CACHE.get(key)
    if fn is None:
        fn = _FN_CACHE[key] = build()
    return fn


def make_prefill_fn(cfg: GPTConfig, *, mesh=None,
                    rules: Rules = DEFAULT_LLM_RULES):
    """jitted (params, tokens [b, S]) -> (logits [b, S, V], k, v
    [L, b, h, S, hd] each).  MoE configs ride gpt.forward's own expert
    dispatch; with a mesh the K/V come back heads-sharded, matching the
    pool layout (POOL_AXES)."""

    def build():
        @jax.jit
        def prefill(params, tokens):
            logits, (k, v) = gpt.forward(params, tokens, cfg, mesh=mesh,
                                         rules=rules, return_kv=True)
            return logits, k, v
        return prefill

    return _cached("prefill", cfg, mesh, rules, build)


# ---------------------------------------------------------------------------
# paged path
#
# The K and V pools are stored as cache.PoolLayout says ([L*(N+1), bs,
# W], see there).  The pools ride the layer scan as its CARRY: each
# layer's ``attend`` commits its window's new K/V into the carried pool
# and THEN attends the committed pool through the rows' tables, so the
# attended context holds the new tokens at their own positions with no
# insertion step.
# What the chip does with that: a carried, donated buffer is updated in
# place — the compiled programs hold no copy of a pool nor of a layer's
# share of one, and the pools enter and leave in the layout the scan
# computes in (tests/test_chip_compile.py pins all three on a described
# v5e).  The formulations this replaced each cost a pass ~75 ms at
# GPT-2 XL on the chip: pools closed over by the scan body with one
# scatter after it (two whole-pool re-tilings in, two out, and a copy
# of the layer's slice per layer), and pools as scan xs/ys (a copy of
# the whole pool).


def window_by_head(lay: PoolLayout, n_table: int) -> bool:
    """Whether one row's window of queries over ``lay``'s pools walks
    the row's table of ``n_table`` blocks a key block at a time, head by
    head (``head_window_attention``), and is not attended packed over
    the gathered table (``packed_attention``): wherever the table spans
    more than ONE key block, on an unsharded pool.

    The packed form multiplies every head over the full stored width
    (K/V heads x the arithmetic) of the row's WHOLE table, whatever the
    window's reach, through a float32 score array [w, h, table span]
    that goes to HBM and comes back: 604 MB and 3.22 ms a layer at 32
    query heads over 8 K/V heads of 64 lanes, 1,024 queries and a table
    of 4,608 keys, where the walk takes 0.16 ms at one key block, 0.43
    at three and 0.65 at all five, 0.25 where half the chunk's lanes
    are real (my chip runs, PR 53, ``benchmarks/window_walk.py``: one
    layer alone; 0.60 against 0.08-0.23 at 8 K/V heads of 128, 256
    queries and 2,304 keys, 0.21 against 0.07-0.14 at 2 of 128, 128 and
    3,072).  On a table of one key block the walk IS the packed form's
    one product, and packed keeps its trick for heads narrower than a
    lane tile (GPT-2 XL: 25 heads of 64 lanes over 1,024 keys, measured
    packed).  The cells measured on the walk: 8 K/V heads of 64 lanes
    over 4,608 keys, 30 of 128 over 8,576, 8 of 128 over 2,304 and over
    33 k, 2 of 128 over 3,072."""
    return (lay.shards == 1
            and n_table * lay.block_size > window_key_block(lay.block_size))


def window_key_block(block_size: int) -> int:
    """Keys a step of the window forms' walk takes: ``KEY_BLOCK`` in
    whole cache blocks, or one cache block where they do not divide
    it."""
    return KEY_BLOCK if KEY_BLOCK % block_size == 0 else block_size


def _key_blocks(lay: PoolLayout, tables, q_pos):
    """ONE row's table (``tables`` [1, T]) walked ``KEY_BLOCK`` keys at
    a time: whole key blocks, the last one padded with the scratch block
    (its keys lie past every query).  -> (``read_keys(pool, layer,
    j)``: key block ``j`` of that pool and layer as stored, [keys,
    width]; the walk's ``key_block`` and ``n_blocks``: the blocks that
    hold a key of the window's last query, ``q_pos`` its REAL queries'
    positions)."""
    bs = lay.block_size
    per = window_key_block(bs) // bs
    table = jnp.pad(tables[0], (0, -tables.shape[1] % per))

    def read_keys(pool, layer, j):
        ids = lax.dynamic_slice_in_dim(table, j * per, per)
        return pool[lay.rows(layer, ids)].reshape(per * bs, lay.width)

    return read_keys, dict(
        key_block=per * bs,
        n_blocks=jnp.minimum(jnp.max(q_pos) // (per * bs) + 1,
                             table.shape[0] // per))


def paged_attend(lay: PoolLayout, pools, blocks, offsets, tables, *,
                 mesh=None, rules=None, kv_lengths=None, mask=None,
                 mask_tables=None, q_per_kv: int = 1, scale=None,
                 q_pos=None, n_valid=None, window: int = 0):
    """Where a window meets the pool, for every model family:
    ``attend_for(layer)`` gives that layer's ``attend(q [b, h, w, hd],
    k, v [b, w, h_kv, hd]) -> o [b, h, w, hd]`` over the (K, V)
    ``pools``, and ``held["pools"]`` is what the last ``attend`` left.

    ``attend`` commits the window's K/V at ``(blocks, offsets)`` and
    then attends the committed pools, the window's own keys among them
    at their positions, in the form the call has:

      * ``kv_lengths`` [b] — ONE token a row, which attends its first
        ``kv_lengths`` keys (0: the row sits the pass out and gets
        zeros).  One Pallas kernel (``paged_decode_attention``) walks
        each live row's table over the pools as stored and reads the
        blocks that hold a key, no other.  Under a mesh that splits
        the pool's width it runs per shard (whole heads, then the
        shard's padding), the rows split as the batch is.
      * ``mask`` — a window of queries a row, each with a horizon of
        its own (the verify window; the GPT family's chunk, whose
        tables are one key block): the rows' ``tables`` [b, T] are
        gathered as contexts [b, T*bs, W], keys in position order,
        heads still packed as stored, and attended so
        (``packed_attention``); with a mesh the contexts are
        constrained to the pool's heads sharding.
      * ``q_pos`` [w] — ONE row's window (b = 1) of causal queries at
        those positions, the first ``n_valid`` of them real (None:
        all).  Where the row's table spans more than one key block
        (``window_by_head``), and for a window layer always: head by
        head, the table walked ``KEY_BLOCK`` keys at a time up to the
        block of the last real query's key, each block gathered and
        attended under a running softmax (``head_window_attention``);
        no array of the table's span, no product wider than a head,
        nothing where only padding lanes see.  A table of one key
        block: the ``mask`` form under those positions' mask.
      * ``kv_lengths`` and one of the two (``make_paged_step_chunk``,
        ``recurrent.make_recurrent_step_chunk``) — ONE window [1, n +
        w] that holds ``n`` = ``len(kv_lengths)`` one-token rows and
        then a window of ``w`` queries: committed together, the first
        ``n`` queries attended as one-token rows of ``tables`` [n, T],
        the rest in their form (``mask``, or ``q_pos`` [w]) over
        ``mask_tables`` [1, T], and joined.

    ``window`` > 0 (the ``kv_lengths`` and ``q_pos`` forms): the pools
    are a window layer's, and a query attends its last ``window`` keys
    only, its own among them.  Both walks then START at the block that
    holds the first of those keys, so a layer's time and bytes are
    bounded by the window; the table's entries before it may name
    anything (the cache gives those blocks back)."""
    held = {"pools": pools}
    # the table a window of queries attends: its own row's
    q_tables = tables if mask_tables is None else mask_tables
    if q_pos is not None and not (
            window or window_by_head(lay, q_tables.shape[-1])):
        S = q_tables.shape[-1] * lay.block_size
        mask = (jnp.arange(S)[None, :] <= q_pos[:, None])[None, None]
        q_pos = None
    if kv_lengths is not None:
        def sp(*axes):
            return spec_for(axes, rules, mesh)
        walk = partial(paged_decode_attention, q_per_kv=q_per_kv,
                       scale=scale, window=window)
        if mesh is not None:
            q_spec = sp("batch", "heads", None, None)
            walk = _per_shard(
                walk, mesh, (q_spec, sp(*POOL_AXES), sp(*POOL_AXES), sp(),
                             sp("batch", None), sp("batch")), q_spec)

    def rows(q, layer):
        return walk(q, *held["pools"], lay.rows(layer, 0), tables,
                    kv_lengths)

    def by_head(q, layer):
        read_keys, blocks_of = _key_blocks(
            lay, q_tables, real_positions(q_pos, n_valid))
        return head_window_attention(
            q[0], lambda j, n: tuple(read_keys(p, layer, j)
                                     for p in held["pools"]),
            q_pos, n_kv_heads=lay.n_heads,
            scale=lay.head_dim ** -0.5 if scale is None else scale,
            window=window, n_valid=n_valid, **blocks_of)[None]

    def packed(q, layer):
        ctx_k, ctx_v = (
            gpt._constrain(lay.read(p, layer, q_tables),
                           ("batch", None, "heads"), mesh, rules)
            for p in held["pools"])
        return packed_attention(q, ctx_k, ctx_v, groups=lay.shards,
                                q_per_kv=q_per_kv, scale=scale, mask=mask)

    def queries(q, layer):
        """The window of queries, in its form."""
        return by_head(q, layer) if mask is None else packed(q, layer)

    def attend_for(layer):
        def attend(q, k, v):
            new = (k.reshape(*blocks.shape, *k.shape[2:]),
                   v.reshape(*blocks.shape, *v.shape[2:]))
            held["pools"] = tuple(
                lay.commit(p, layer, blocks, offsets, x)
                for p, x in zip(held["pools"], new))
            if kv_lengths is None:
                return queries(q, layer)
            if mask is None and q_pos is None:
                return rows(q, layer)
            n = kv_lengths.shape[0]
            # [1, h, n, hd] <-> [n, h, 1, hd]: a row's one query
            o = rows(q[:, :, :n].transpose(2, 1, 0, 3), layer)
            return jnp.concatenate(
                [o.transpose(2, 1, 0, 3), queries(q[:, :, n:], layer)],
                axis=2)
        return attend
    return attend_for, held


def latent_attend(lay: PoolLayout, pools, blocks, offsets, tables, *,
                  scale: float, kv_lengths=None, q_pos=None, n_valid=None):
    """``paged_attend`` for a model whose attention layers keep ONE
    latent a token (``lay``: one head of ``kv_rank + rope`` lanes, the
    values its first ``lay.value_lanes``; ``pools``: the one pool):
    ``attend_for(layer)`` gives ``attend(q_nope [b, w, h, dn], q_rope
    [b, w, h, dr], latent [b, w, kv_rank + dr], w_uk [h, kv_rank, dn],
    w_uv [h, kv_rank, dv]) -> o [b, w, h * dv]``, which commits the
    window's latents at ``(blocks, offsets)`` and attends the committed
    pool:

      * ``kv_lengths`` [b] — ONE token a row (w = 1): the up-projection
        absorbed, ``q_lat = q_nope W_uk^T`` and ``o = (P c_kv) W_uv``,
        through the kernel that walks each live row's table over the
        pool as stored (``latent_decode_attention``);
      * ``q_pos`` [w] — ONE row's window (b = 1) at those positions:
        the table walked ``KEY_BLOCK`` keys at a time, each block
        gathered, decompressed and attended under a running softmax
        (``latent_window_attention``); no array of the table's span.
        ``n_valid``: the window's real queries, its first; the rest are
        padding lanes, and neither the walk nor the kernel's tiles go
        where only they see."""
    held = {"pools": pools}
    rank = lay.value_lanes

    def attend_for(layer):
        def attend(q_nope, q_rope, latent, w_uk, w_uv):
            pool, = held["pools"]
            pool = lay.commit(pool, layer, blocks, offsets, latent.reshape(
                *blocks.shape, 1, latent.shape[-1]))
            held["pools"] = (pool,)
            if kv_lengths is not None:
                q_lat = jnp.einsum("bhd,hcd->bhc", q_nope[:, 0], w_uk)
                q = jnp.concatenate([q_lat, q_rope[:, 0]], axis=-1)
                q = jnp.pad(q, [(0, 0), (0, 0),
                                (0, lay.width - q.shape[-1])])
                o_lat = latent_decode_attention(
                    q, pool, lay.rows(layer, 0), tables, kv_lengths,
                    value_lanes=rank, scale=scale)
                o = jnp.einsum("bhc,hcd->bhd", o_lat, w_uv)
                return o.reshape(o.shape[0], 1, -1)
            read_keys, walk = _key_blocks(lay, tables,
                                          real_positions(q_pos, n_valid))
            return latent_window_attention(
                q_nope[0], q_rope[0],
                lambda j, n: read_keys(pool, layer, j), w_uk, w_uv,
                q_pos, scale=scale, n_valid=n_valid, **walk)[None]
        return attend
    return attend_for, held


def _pools_in(cfg, k_pool, v_pool, mesh, rules):
    """-> (the pools' layout, the (K, V) pair constrained to it)."""
    lay = PoolLayout.of(cfg, k_pool, heads_shards(mesh, rules))
    return lay, tuple(gpt._constrain(p, POOL_AXES, mesh, rules)
                      for p in (k_pool, v_pool))


def _feed_out(feed, mesh, rules):
    """The token array as a program returns it: replicated, as it came
    in (so that the donated buffer is the result's)."""
    return gpt._constrain(feed, (None,), mesh, rules)


def _paged_layers(cfg, mesh, rules, layers, x, pools, attend_over):
    """The stacked ``layers`` on the window x [b, w, d], the pools their
    scan's carry.  ``attend_over(pools)`` is the program's
    ``paged_attend`` over the pools it is given: the closure is built
    inside the scan body from the carry, and the body returns what the
    closure left.  -> (x, pools)."""
    def layer(carry, xs):
        x, pools = carry
        lp, li = xs
        attend_for, held = attend_over(pools)
        x, _ = gpt._transformer_layer(x, lp, cfg, mesh, rules,
                                      attend_for(li))
        return (x, held["pools"]), None

    n = layers["wqkv"].shape[0]
    (x, pools), _ = lax.scan(layer, (x, pools), (layers, jnp.arange(n)))
    return x, pools


# What the host sends a decode step or a chunk, for either model family
# (recurrent.py's programs take the same arrays): ONE fresh int32 numpy
# array a program, handed over as it is.  Four ``jnp.asarray`` calls are
# four tiny ``convert_element_type`` programs, 1.3-1.9 ms of a pass on
# the chip with the device idle.

# In ``pack_step``'s token column: the row's input token is the one the
# device holds for it (the programs' token array ``feed``), not the
# host's.
FEED = -1


def pack_step(tables, tokens, positions, active) -> np.ndarray:
    """The decode step's host inputs as one fresh int32 ``[b, T + 3]``:
    a row's block table, then its token (``FEED``: the one the device
    holds for the row), position and whether it is active."""
    return np.concatenate(
        [tables, tokens[:, None], positions[:, None], active[:, None]],
        axis=1, dtype=np.int32)


def unpack_step(packed, T: int, feed):
    """-> (tables [b, T], tokens [b], positions [b], active [b] bool) of
    a ``pack_step`` array, inside a program; a row whose token column
    says ``FEED`` takes its token from the program's token array
    ``feed`` [b]."""
    tokens = jnp.where(packed[:, T] == FEED, feed, packed[:, T])
    return packed[:, :T], tokens, packed[:, T + 1], packed[:, T + 2] != 0


def feed_step(feed, active, greedy):
    """The token array after a decode step: every stepped row's greedy
    token is what the row feeds next."""
    return jnp.where(active, greedy, feed)


def feed_chunk(feed, row, n_valid, greedy):
    """The token array after a chunk of ``n_valid`` real tokens of
    decode row ``row``: the greedy token of its last real position is
    what the row feeds next (the prompt's first token where the chunk
    ends it; overwritten by the next chunk where it does not).  A chunk
    of nothing (the warm-up's) writes nothing."""
    return feed.at[row].set(jnp.where(n_valid > 0, greedy, feed[row]))


def pack_chunk(table, tokens, start: int, row: int,
               n_valid: int) -> np.ndarray:
    """The chunk program's host inputs as one fresh int32 ``[T + C +
    3]``: the row's block table, the window's tokens, then the window's
    first position, the decode row and the count of real tokens."""
    return np.concatenate([table, tokens, (start, row, n_valid)],
                          dtype=np.int32)


def unpack_chunk(packed, T: int, C: int):
    """-> (table [T], tokens [C], start, row, n_valid) of a
    ``pack_chunk`` array, inside a program."""
    return (packed[:T], packed[T:T + C], packed[T + C],
            packed[T + C + 1], packed[T + C + 2])


def pack_step_chunk(step: np.ndarray, chunk: np.ndarray) -> np.ndarray:
    """What ``make_paged_step_chunk``'s program takes: a ``pack_step``
    array, flat, then a ``pack_chunk`` array, as one fresh int32
    ``[b * (T + 3) + T + C + 3]``."""
    return np.concatenate([step.ravel(), chunk])


def _step_indices(tables, positions, active, bs: int):
    """Where a decode step's rows write and how far they attend:
    -> (block ids [b], offsets [b], kv lengths [b]); an inactive row
    goes to the scratch block (id 0) and attends nothing."""
    rows = jnp.arange(positions.shape[0])
    return (jnp.where(active, tables[rows, positions // bs], 0),
            jnp.where(active, positions % bs, 0),
            jnp.where(active, positions + 1, 0))              # 0: sits out


def _chunk_indices(cfg, table, start, C: int, bs: int):
    """A chunk's window at positions ``start .. start + C`` of one row:
    -> (positions for ``wpe`` [C], block ids [C], offsets [C], mask
    [C, T * bs]).  Each query's mask is its own causal horizon; a
    position past the table's span writes to the scratch block, which
    no table position of a real row names."""
    S = table.shape[0] * bs
    pos = start + jnp.arange(C, dtype=jnp.int32)
    oob = pos >= S
    safe = jnp.where(oob, 0, pos)
    return (jnp.clip(pos, 0, cfg.max_seq - 1),
            jnp.where(oob, 0, table[safe // bs]),
            jnp.where(oob, 0, pos % bs),
            jnp.arange(S)[None, :] <= pos[:, None])


def make_paged_decode_step(cfg: GPTConfig, *, block_size: int,
                           n_table: int, mesh=None,
                           rules: Rules = DEFAULT_LLM_RULES):
    """jitted one-token step over the whole row batch, block-pool cache.

    (params, k_pool, v_pool [cache.PoolLayout], feed [b] int32,
     packed [b, T + 3] int32
     (``pack_step``: tables | tokens | positions | active))
        -> (logits [b, vocab] f32, greedy [b] int32, k_pool, v_pool,
            feed)

    ``feed`` is the token each decode row feeds next, resident on the
    device and donated like the pools (replicated under a mesh): a row
    whose token column says ``FEED`` reads its input token there, and
    every stepped row's greedy token is written back to it — so the
    next step can be launched before the host has read this one's
    tokens.  ``greedy`` is the argmax of the float32 logits, taken inside the
    program (ties to the lowest index, what ``gpt.sample_token`` at
    temperature 0 gives; under a mesh over the vocabulary-sharded
    logits): a greedy pass fetches ``b`` integers and the logits stay
    on the device, where a sampled row indexes them.

    Every row's current token K/V goes to the pool at
    ``(tables[row, pos // bs], pos % bs)`` — inactive rows are
    redirected to the scratch block (id 0) so the scatter needs no
    conditional — and the row attends its valid prefix.  Tail blocks
    are per-row exclusive (the engine copy-on-writes shared tails
    before the step), so active rows never collide in the scatter.
    """
    bs, T = int(block_size), int(n_table)

    def build():
        @partial(jax.jit, donate_argnums=(1, 2, 3))
        def step(params, k_pool, v_pool, feed, packed):
            tables, tokens, positions, active = unpack_step(packed, T, feed)
            lay, pools = _pools_in(cfg, k_pool, v_pool, mesh, rules)
            x = (gpt._token_rows(params, tokens, cfg)
                 + params["wpe"][positions])
            x = x[:, None, :].astype(cfg.dtype)               # [b, 1, d]
            bidx, off, kv_len = _step_indices(tables, positions, active, bs)
            x, pools = _paged_layers(
                cfg, mesh, rules, params["layers"], x, pools,
                lambda pools: paged_attend(
                    lay, pools, bidx, off, tables, mesh=mesh, rules=rules,
                    kv_lengths=kv_len))
            k_pool, v_pool = (gpt._constrain(p, POOL_AXES, mesh, rules)
                              for p in pools)
            logits = gpt._head(params, x, cfg, mesh, rules)[:, 0, :]
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (logits, greedy, k_pool, v_pool,
                    _feed_out(feed_step(feed, active, greedy), mesh, rules))

        return step

    return _cached(("paged_step", bs, T), cfg, mesh, rules, build)


def make_chunk_prefill_fn(cfg: GPTConfig, *, chunk: int, block_size: int,
                          n_table: int, mesh=None,
                          rules: Rules = DEFAULT_LLM_RULES):
    """jitted fixed-width prefill chunk against the block pool.

    (params, k_pool, v_pool [cache.PoolLayout], feed [b] int32,
     packed [T + C + 3] int32
     (``pack_chunk``: table | tokens | start, row, n_valid))
        -> (logits [C, vocab] f32, greedy [1] int32, k_pool, v_pool,
            feed)

    ``greedy`` is the argmax of the last REAL position's logits
    (``n_valid - 1``; a prompt's first token when the chunk ends it),
    taken inside the program like the decode step's, and written to
    decode row ``row``'s entry of ``feed`` (the decode step's token
    array), where the row's first decode step finds it.

    Processes prompt positions ``start .. start+C``: the window's K/V
    goes through the block table (rows past the table's span are
    redirected to the scratch block), and each query row attends the
    gathered table masked to its OWN causal horizon (key position <=
    query position) — so earlier chunks' cached K/V, including an
    adopted prefix from the radix index, participates exactly as in a
    full forward.  Pad rows past the prompt compute garbage that lands
    in masked positions and is overwritten by decode; the caller reads
    only the rows it needs.  The engine interleaves one chunk per
    scheduler pass with decode iterations (chunked prefill: bounded
    prefill cost per token cadence).
    """
    bs, C, T = int(block_size), int(chunk), int(n_table)

    def build():
        @partial(jax.jit, donate_argnums=(1, 2, 3))
        def chunk_fn(params, k_pool, v_pool, feed, packed):
            table, tokens, start, row, n_valid = unpack_chunk(packed, T, C)
            lay, pools = _pools_in(cfg, k_pool, v_pool, mesh, rules)
            wpe_pos, bidx, off, mask = _chunk_indices(cfg, table, start,
                                                      C, bs)
            x = (gpt._token_rows(params, tokens, cfg)
                 + params["wpe"][wpe_pos])
            x = x[None, :, :].astype(cfg.dtype)                # [1, C, d]
            x, pools = _paged_layers(
                cfg, mesh, rules, params["layers"], x, pools,
                lambda pools: paged_attend(
                    lay, pools, bidx, off, table[None], mesh=mesh,
                    rules=rules, mask=mask[None, None]))
            k_pool, v_pool = (gpt._constrain(p, POOL_AXES, mesh, rules)
                              for p in pools)
            logits = gpt._head(params, x, cfg, mesh, rules)[0]  # [C, V]
            greedy = jnp.argmax(logits[jnp.maximum(n_valid, 1) - 1]
                                ).astype(jnp.int32)
            return (logits, greedy[None], k_pool, v_pool,
                    _feed_out(feed_chunk(feed, row, n_valid, greedy),
                              mesh, rules))

        return chunk_fn

    return _cached(("chunk_prefill", bs, T, C), cfg, mesh, rules, build)


def make_paged_step_chunk(cfg: GPTConfig, *, chunk: int, block_size: int,
                          n_table: int, mesh=None,
                          rules: Rules = DEFAULT_LLM_RULES):
    """jitted decode step AND one prefill chunk as ONE program: what a
    pass that holds both runs in place of ``make_paged_decode_step``'s
    and ``make_chunk_prefill_fn``'s programs back to back, so that each
    layer's weights stream from HBM once a pass, not twice.

    (params, k_pool, v_pool [cache.PoolLayout], feed [b] int32,
     packed [b * (T + 3) + T + C + 3] int32 (``pack_step_chunk``: a
     ``pack_step`` array, flat, then a ``pack_chunk`` array))
        -> (logits [b + 1, vocab] f32, greedy [b + 1] int32, k_pool,
            v_pool, feed)

    The ``b`` rows' tokens and the chunk's ``C`` are ONE window
    ``[1, b + C]`` of the layer function: every product of it reads its
    weights once for ``b + C`` rows.  The layer's attention step commits
    the whole window's K/V (inactive rows and the chunk's out-of-span
    positions to the scratch block, as in the two programs) and attends
    its two parts in the forms they have there: the first ``b`` queries
    walk their tables in the one-token kernel, the last ``C`` attend the
    chunk row's gathered table under their causal mask
    (``paged_attend`` with both).  The head runs over ``b + 1`` rows:
    the decode rows and the chunk's last REAL position (``n_valid -
    1``); ``greedy[b]`` is the prompt's first token when the chunk ends
    it.  Same dtypes, products and masks as the two programs."""
    bs, C, T = int(block_size), int(chunk), int(n_table)

    def build():
        @partial(jax.jit, donate_argnums=(1, 2, 3))
        def step_chunk(params, k_pool, v_pool, feed, packed):
            b = (packed.shape[0] - (T + C + 3)) // (T + 3)
            tables, tokens, positions, active = unpack_step(
                packed[:b * (T + 3)].reshape(b, T + 3), T, feed)
            table, chunk_tokens, start, row, n_valid = unpack_chunk(
                packed[b * (T + 3):], T, C)
            lay, pools = _pools_in(cfg, k_pool, v_pool, mesh, rules)
            bidx, off, kv_len = _step_indices(tables, positions, active, bs)
            wpe_pos, c_bidx, c_off, mask = _chunk_indices(cfg, table,
                                                          start, C, bs)
            x = (gpt._token_rows(
                     params, jnp.concatenate([tokens, chunk_tokens]), cfg)
                 + params["wpe"][jnp.concatenate([positions, wpe_pos])])
            x = x[None, :, :].astype(cfg.dtype)            # [1, b + C, d]
            x, pools = _paged_layers(
                cfg, mesh, rules, params["layers"], x, pools,
                lambda pools: paged_attend(
                    lay, pools, jnp.concatenate([bidx, c_bidx])[None],
                    jnp.concatenate([off, c_off])[None], tables,
                    mesh=mesh, rules=rules, kv_lengths=kv_len,
                    mask=mask[None, None], mask_tables=table[None]))
            k_pool, v_pool = (gpt._constrain(p, POOL_AXES, mesh, rules)
                              for p in pools)
            last = b + jnp.maximum(n_valid, 1) - 1
            x = jnp.concatenate(
                [x[:, :b], lax.dynamic_slice_in_dim(x, last, 1, axis=1)],
                axis=1)                                    # [1, b + 1, d]
            logits = gpt._head(params, x, cfg, mesh, rules)[0]
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            feed = feed_chunk(feed_step(feed, active, greedy[:b]), row,
                              n_valid, greedy[b])
            return (logits, greedy, k_pool, v_pool,
                    _feed_out(feed, mesh, rules))

        return step_chunk

    return _cached(("paged_step_chunk", bs, T, C), cfg, mesh, rules, build)


def make_spec_verify_step(cfg: GPTConfig, *, width: int, block_size: int,
                          n_table: int, mesh=None,
                          rules: Rules = DEFAULT_LLM_RULES):
    """jitted speculative VERIFY step: the paged decode step widened to
    score W = ``width`` positions per row in one call.

    (params, k_pool, v_pool [cache.PoolLayout], tables [b, T] int32,
     tokens [b, W] int32, positions [b] int32, active [b] bool,
     n_tokens [b] int32)
        -> (logits [b, W, vocab] f32, k_pool, v_pool)

    ``tokens[row, 0]`` is the row's current input token (sitting at
    ``positions[row]`` — exactly the plain step's input); columns 1..
    are drafted continuations at positions+1, +2, ...  ``n_tokens`` in
    [1, W] says how many leading columns are real; lanes past it (and
    all lanes of inactive rows) write to the scratch block and attend
    key 0 only, so their logits are garbage the caller ignores — never
    NaN, never corruption.

    Every real lane's K/V goes to its own position and each query is
    masked to keys <= positions[row]+j (the chunk-prefill causal-horizon
    mask batched over rows), so lane 0's logits are the plain decode
    step's logits and lane j's are exact next-token logits GIVEN the
    drafted prefix — greedy accept/reject on the host is therefore
    token-identical to non-speculative decode by construction.
    Rejected lanes leave garbage K/V beyond the row's committed length,
    which the kv-length masks hide until decode overwrites it (same
    rule as prefill padding).
    """
    bs, W, T = int(block_size), int(width), int(n_table)
    S = T * bs

    def build():
        @partial(jax.jit, donate_argnums=(1, 2))
        def verify(params, k_pool, v_pool, tables, tokens, positions,
                   active, n_tokens):
            b = tokens.shape[0]
            lay, pools = _pools_in(cfg, k_pool, v_pool, mesh, rules)
            rows = jnp.arange(b)
            pos = positions[:, None] + jnp.arange(W, dtype=jnp.int32)  # [b,W]
            live = ((jnp.arange(W)[None, :] < n_tokens[:, None])
                    & active[:, None] & (pos < S))        # real lanes
            wpe_pos = jnp.clip(pos, 0, cfg.max_seq - 1)
            x = (gpt._token_rows(params, tokens, cfg)
                 + params["wpe"][wpe_pos])
            x = x.astype(cfg.dtype)                       # [b, W, d]
            safe = jnp.where(live, pos, 0)
            # dead lanes collide harmlessly in the scratch block
            bidx = jnp.where(live, tables[rows[:, None], safe // bs], 0)
            off = jnp.where(live, pos % bs, 0)
            hor = jnp.where(live, pos, 0)                 # >=1 key: no NaN
            mask = (jnp.arange(S)[None, None, :]
                    <= hor[:, :, None])[:, None]          # [b, 1, W, S]
            x, pools = _paged_layers(
                cfg, mesh, rules, params["layers"], x, pools,
                lambda pools: paged_attend(
                    lay, pools, bidx, off, tables, mesh=mesh, rules=rules,
                    mask=mask))
            k_pool, v_pool = (gpt._constrain(p, POOL_AXES, mesh, rules)
                              for p in pools)
            logits = gpt._head(params, x, cfg, mesh, rules)  # [b, W, V]
            return logits, k_pool, v_pool

        return verify

    return _cached(("spec_verify", bs, T, W), cfg, mesh, rules, build)


def make_paged_draft_step(cfg: GPTConfig, *, draft_layers: int, k: int,
                          block_size: int, n_table: int, mesh=None,
                          rules: Rules = DEFAULT_LLM_RULES):
    """jitted truncated-layer SELF-DRAFT burst: ``k`` autoregressive
    draft tokens per row in ONE compiled call — a ``lax.scan`` over
    draft positions, each scanning only the first ``draft_layers``
    layers, then the head and a greedy argmax feeding the next step.

    (params, k_pool, v_pool [cache.PoolLayout], tables [b, T] int32,
     tokens [b] int32, positions [b] int32, want [b] int32)
        -> (drafts [b, k] int32, k_pool, v_pool)

    Row r drafts ``want[r]`` tokens (0 = the row sits the burst out);
    columns past ``want[r]`` are garbage the caller ignores.  Fusing
    the whole burst kills the k host round-trips of a step-at-a-time
    loop — on small models the dispatch + logits transfer per step
    costs as much as the truncated forward itself.

    Every draft step is the decode step over the first ``draft_layers``
    layers: it commits its token's K/V to the pool, which both scans
    carry, and the next step reads it back through the table.  Only
    layers < draft_layers are written, and those K/V are bit-identical
    to the full model's at the same (layer, position) because layer l
    depends only on layers below it, so drafting straight through the
    REAL pool is safe: committed positions are unchanged, and the
    verify pass rewrites every drafted position at all layers
    regardless of the accept outcome.  Cost per draft token ~
    draft_layers / n_layers of a full step, with zero extra weights.
    The truncated-layer trunk slice composes with MoE leaves because
    tree_map slices every per-layer leaf, expert weights included.
    """
    bs = int(block_size)
    D, K, T = int(draft_layers), int(k), int(n_table)
    S = T * bs
    if not (1 <= D < cfg.n_layers):
        raise SpeculationUnsupported(
            f"draft_layers must be in [1, n_layers) = [1, "
            f"{cfg.n_layers}), got {D}")
    if K < 1:
        raise SpeculationUnsupported(f"draft burst k must be >= 1, "
                                     f"got {K}")

    def build():
        @partial(jax.jit, donate_argnums=(1, 2))
        def draft(params, k_pool, v_pool, tables, tokens, positions,
                  want):
            b = tokens.shape[0]
            lay, pools = _pools_in(cfg, k_pool, v_pool, mesh, rules)
            rows = jnp.arange(b)
            trunk = jax.tree_util.tree_map(lambda a: a[:D],
                                           params["layers"])

            def step(carry, j):
                cur, pos, pools = carry
                live = (want > j) & (pos < S)
                x = (gpt._token_rows(params, cur, cfg)
                     + params["wpe"][jnp.clip(pos, 0, cfg.max_seq - 1)])
                x = x[:, None, :].astype(cfg.dtype)           # [b, 1, d]
                safe = jnp.where(live, pos, 0)
                bidx = jnp.where(live, tables[rows, safe // bs], 0)
                off = jnp.where(live, safe % bs, 0)
                kv_len = jnp.where(live, pos + 1, 0)
                x, pools = _paged_layers(
                    cfg, mesh, rules, trunk, x, pools,
                    lambda pools: paged_attend(
                        lay, pools, bidx, off, tables, mesh=mesh,
                        rules=rules, kv_lengths=kv_len))
                logits = gpt._head(params, x, cfg, mesh, rules)[:, 0, :]
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                cur = jnp.where(live, nxt, cur)
                pos = pos + live.astype(jnp.int32)
                return (cur, pos, pools), nxt

            (_, _, pools), toks = lax.scan(
                step, (tokens, positions, pools), jnp.arange(K))
            k_pool, v_pool = (gpt._constrain(p, POOL_AXES, mesh, rules)
                              for p in pools)
            return toks.T, k_pool, v_pool     # drafts [b, K]

        return draft

    return _cached(("draft_burst", bs, T, D, K), cfg, mesh,
                   rules, build)


def ngram_propose(context: np.ndarray, k: int,
                  max_ngram: int = 3) -> np.ndarray:
    """Prompt-lookup draft proposal (Saxena 2023): find the most recent
    EARLIER occurrence of the context's trailing n-gram (longest n
    first, n <= max_ngram) and propose up to ``k`` of the tokens that
    followed it.  Host-side, zero weights — the drafter for workloads
    whose generations echo their own prompt/history (shared-prefix
    serving, repetitive greedy tails).  Returns an empty array when
    nothing matches; the engine then decodes that row plainly."""
    n = int(len(context))
    if n < 2 or k < 1:
        return np.empty(0, np.int32)
    context = np.asarray(context, np.int32)
    for m in range(min(int(max_ngram), n - 1), 0, -1):
        pat = context[n - m:]
        # candidate starts s in [0, n-m-1]: the trailing n-gram itself
        # (s = n-m) is excluded, and every match has >= 1 follower
        win = np.stack([context[i:n - m + i] for i in range(m)], axis=1)
        hits = np.flatnonzero((win == pat).all(axis=1))
        if hits.size == 0:
            continue
        s = int(hits[-1])                 # most recent occurrence
        prop = context[s + m:s + m + k]
        if prop.size:
            return prop.astype(np.int32)
    return np.empty(0, np.int32)


def clear_fn_cache() -> None:
    """Drop the shared compiled-function cache (tests / benchmarks that
    want cold-compile timings)."""
    _FN_CACHE.clear()
