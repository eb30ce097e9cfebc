"""Paged serving programs of the hybrid family: the decode step and the
chunk-prefill window of ``models/hybrid.py``, and the two as ONE
program.

Each is the model's ONE layer function (``hybrid.run_layers`` over
``hybrid.block``) at a serving shape — ``[rows, 1]``, ``[1, chunk]``
and the window in two parts ``[1, rows + chunk]`` — with the past
supplied from the pools the cache manager owns
(inference/cache.py), whichever of them the model's layers keep:

  * an attention layer's keys come from the K/V ``BlockPool`` through
    the row's block table through the ``attend`` decode.py's GPT
    programs use (``decode.paged_attend``: commit the window's K/V,
    then gather the table; ``packed_attention`` attends the rows as
    stored, grouped queries sharing their K/V head's lanes);
  * a latent attention layer's come from the ONE latent pool
    (``decode.latent_attend``: commit the window's latents, then the
    one-token kernel with the up-projection absorbed, or the window
    form that decompresses a block of keys at a time), at rotary
    positions the program states;
  * a Mamba layer's convolution and SSM state come from the
    ``StatePool`` at the window's decode rows and go back there;
  * a short-convolution layer's state is its convolution's last inputs
    alone, and the state after ANY token of a window is a slice of the
    window's own activations: each program also writes the state at the
    END of every block it closes into the pool's snapshots, by the
    block's id (``_Snapshots``; the chunk program those of every block
    boundary its window crosses, the decode step one where a row's
    token is its block's last) — a gather and a scatter of a few KB a
    block, no second pass — so that every full block a row holds
    carries the state a later request needs to go on from it.

A program takes ``pools``, the tuple of block-pool arrays
(``BlockPool.pools``: K and V, or the one latent pool), ``state``,
the tuple of state arrays (``StatePool.arrays``: ``(conv, ssm)``,
``(conv, snap)`` of a state with a snapshot form, or ``()`` for a model
without recurrent layers), and ``feed``, the token each decode row
feeds next (decode.py's token array: read where the packed array says
``decode.FEED``, written with the program's greedy tokens), all donated
and updated in place.  Besides
the logits each program returns ``load`` int32 — held expert
assignments, all assignments, the busiest held expert's assignments and
the held experts touched, summed over the experts sublayers, of the
real tokens only (``hybrid.run_layers``), then the greedy tokens (the
argmax of the logits, taken on the device): every row's from the decode
step, the last real position's from the chunk.  A greedy pass then
fetches ``4 + rows`` integers and a greedy first token one vector of 5;
the ``[rows, vocab]`` logits never leave the device (12.8 MB a pass at
64 rows x 50,176, to the host and back for the argmax: 8 of the 13.5 ms
of host time a pass that the first chip runs of PR 29 read).

What the host sends a pass is ONE int32 array (``decode.pack_step`` /
``pack_chunk``), handed to the program as the fresh numpy array it is:
four ``jnp.asarray`` calls were 1.9 of a decode pass's 4.5 ms of host
time on the chip, and a chunk's five 1.2 more, all of it with the device
idle.

decode.py's GPT programs exchange the same with the host — the packed
array in, the greedy tokens out, the logits left on the device — so the
engine's pass is ONE host algorithm for both families
(``InferenceEngine._fetch_step`` / ``_emit_first`` / ``_pass_done``).
What these two add is the state they carry and the ``load`` counts in
front of the greedy tokens (``hybrid.N_LOAD`` of them).  The jitted
functions are named ``step`` and ``chunk_fn`` like decode.py's (a
device trace's program names are ``jit_step`` / ``jit_chunk_fn`` for
either model family).  No mesh: one device holds one chip's share of the
deployment (experts over an ``ep`` axis are future work).

A pass that holds a prompt chunk AND decoding rows runs the two as ONE
program, ``step_chunk`` (``make_recurrent_step_chunk``; ``jit_step_chunk``
in a trace, as the GPT family's), where the model's every sublayer kind
takes a window in two parts: Mamba-2, the short convolution, the delta
rule, attention over K/V blocks gathered by table, routed experts
(dropless: a part's tokens are dropped by nobody), the dense MLP — the
granite, nemotron, lfm2 and olmo layouts.  Every
matrix then streams from HBM once a pass, for ``rows + chunk`` tokens,
and only the recurrences and the attention run a part at a time, in the
forms the two programs give them (the chunk's queries walk their row's
table or attend it packed, as ``paged_attend`` derives from the table's
span: the fused pass does not depend on which).  A latent-attention
sublayer or a window layer has no such form yet,
and a model with one keeps the pass of two programs
(``has_step_chunk``: derived from the sublayer kinds, by the engine,
where it builds the programs).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.inference.cache import PoolLayout, snapshot_geometry
from ray_tpu.inference.decode import (_cached, _step_indices, feed_chunk,
                                      feed_step, latent_attend,
                                      paged_attend, unpack_chunk,
                                      unpack_step)
from ray_tpu.models import hybrid
from ray_tpu.models.hybrid import HybridConfig


def make_recurrent_decode_step(cfg: HybridConfig, *, block_size: int,
                               n_table: int):
    """jitted one-token step over the whole row batch.

    (params, pools, state, feed [b] int32, packed [b, T + 3] int32
     (``pack_step``: tables | tokens | positions | active))
        -> (logits [b, vocab] f32, load + greedy [4 + b] int32,
            pools, state, feed)

    ``feed``: the token each decode row feeds next, donated like the
    pools, read where a row's token column says ``decode.FEED`` and
    written with every stepped row's greedy token (decode.py's step).

    A model with window layers has two tables a row, side by side in
    ``packed`` [b, 2 T + 3]: the full layers' and the window layers'
    (``_two_groups``).

    Decode row r's state is row r of the state arrays.  An inactive row
    (free, or still prefilling) is a window of 0 real tokens: its K/V
    write goes to the scratch block and its state comes back unchanged.
    """
    bs, T = int(block_size), int(n_table) * (2 if cfg.n_window else 1)

    def build():
        @partial(jax.jit, donate_argnums=(1, 2, 3))
        def step(params, pools, state, feed, packed):
            tables, tokens, positions, active = unpack_step(packed, T, feed)
            _, off, kv_len = _step_indices(tables, positions, active, bs)
            attend_for, window_for, pools_out = _two_groups(
                cfg, pools, tables,
                lambda t: _step_indices(t, positions, active, bs)[0], off,
                kv_lengths=kv_len)
            conv, ssm = state or (None, None)
            held = {"conv": [], "ssm": ssm}
            # a state with a snapshot form: (conv, snap), and what each
            # row's token closes (``_Snapshots``)
            keep = (_Snapshots(state) if snapshot_geometry(cfg) is not None
                    else None)

            def state_out(mi, new):
                held["conv"].append(new[0])
                held["ssm"] = new[1][0]

            # The SSM state goes in as the whole pool and the layer's
            # index: the one-token form advances the live rows' state
            # where it lies (``ssm[mi]`` handed to its kernel would be
            # a 268 MB copy a layer).  Every row's convolution state is
            # made anew by a pass, so the layers' are stacked into the
            # pool once at the end: a pool updated layer by layer is
            # kept in VMEM by the compiler and moved out and back
            # around each layer's kernel, 29 MB each way.
            x, load = hybrid.run_layers(
                cfg, params, hybrid.embed(cfg, params, tokens[:, None]),
                active.astype(jnp.int32),
                state_in=keep.rows_in if keep else (
                    lambda mi: (conv[mi], (held["ssm"], mi))),
                state_out=keep.rows_out if keep else state_out,
                attend_for=attend_for,
                window_for=window_for, positions=positions[:, None])
            logits = hybrid.head(cfg, params, x[:, 0])
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if keep:
                state = keep.done(_closed(
                    *_step_indices(tables, positions, active, bs)[:2], bs))
            else:
                state = (jnp.stack(held["conv"]), held["ssm"]) if state \
                    else ()
            return (logits, jnp.concatenate([load, greedy]), pools_out(),
                    state, feed_step(feed, active, greedy))

        return step

    return _cached(("recurrent_step", bs, T), cfg, None, None, build)


def _closed(blocks, offsets, bs: int):
    """The blocks a decode step's rows CLOSE: a row whose token lands on
    its block's last offset names that block.  -> (ids [b], which rows
    write [b]); an inactive row's block is the scratch block (id 0) and
    closes nothing."""
    return blocks, (offsets == bs - 1) & (blocks > 0)


def _boundaries(table, start, n_valid, C: int, bs: int):
    """The block boundaries a chunk's window crosses, at most ``ceil(C /
    bs)``: -> (marks [J]: the token counts of the window after which a
    block ends, 0 where the real tokens end first; ids [J]: those
    blocks; which of them are real [J])."""
    j = jnp.arange(-(-C // bs), dtype=jnp.int32)
    block = start // bs + j
    marks = (block + 1) * bs - start
    real = marks <= n_valid
    ids = table[jnp.minimum(block, table.shape[0] - 1)]
    return jnp.where(real, marks, 0), ids, real


def _write_rows(snap, ids, rows, write):
    """snap [blocks, lanes] <- ``rows`` [n, lanes] at ``ids`` [n] where
    ``write`` [n], in place: a loop over the WRITERS alone, one row's
    update each (a pass closes a block for 0.3 of its rows on average:
    one in ``block_size`` tokens).  As ONE scatter of all n rows the
    compiler loops over every row, writers or not (0.35 ms a decode
    pass at 48 rows), and two scatters in one program copy the whole
    array between them (604 MB, 1.2 ms a pass that held a chunk: my
    chip run, PR 52, call 1)."""
    order = jnp.argsort(~write)                      # the writers first
    rows = rows.astype(snap.dtype)

    def one(i, snap):
        j = order[i]
        return jax.lax.dynamic_update_slice(
            snap, jax.lax.dynamic_slice_in_dim(rows, j, 1), (ids[j], 0))
    return jax.lax.fori_loop(0, write.sum(dtype=jnp.int32), one, snap)


class _Snapshots:
    """A program's traffic with a state that has a snapshot form
    (``state`` = (conv [L, rows, K-1, d], snap [blocks, L (K-1) d])):
    what ``run_layers`` is handed as ``state_in`` / ``state_out`` for the
    rows of a step (``rows_in`` / ``rows_out``), for one row's window
    (``window_in(row, marks)`` / ``rows_out``) or for both as ONE
    window (``both_in``), and the state tuple the program returns
    (``done``).  Every layer's new state is stacked and written once at
    the end, as the decode step does for its convolution states."""

    def __init__(self, state):
        self.conv, self.snap = state
        self.new, self.marked = [], []

    def rows_in(self, mi):
        return self.conv[mi], None

    def rows_out(self, mi, new):
        self.new.append(new[0])
        if new[1] is not None:
            self.marked.append(new[1][0])

    def window_in(self, row, marks):
        return lambda mi: (_row_of(self.conv, mi, row)[0], marks[None])

    def both_in(self, row, marks):
        return lambda mi: (self.conv[mi], marks[None], row)

    def done(self, closed=None, row=None, marked=None):
        """``closed`` (ids [rows], which write): the blocks the step's
        rows closed, whose snapshot is those rows' NEW state; ``marked``
        (ids [J], which are real): the blocks of a window's marks;
        ``row``: a window alone, whose new state goes to that row of the
        pool.  -> the program's state tuple; the snapshots are written
        by ONE loop over everything that writes."""
        new = jnp.stack(self.new)                    # [L, n, K-1, d]
        parts = []                       # (ids, which write, the states)
        if marked is not None:
            parts.append((*marked, jnp.stack(self.marked)))
        if closed is not None:
            parts.append((*closed, new))
        ids, write, states = zip(*parts)
        snap = _write_rows(
            self.snap, jnp.concatenate(ids), jnp.concatenate(
                [jnp.swapaxes(s, 0, 1).reshape(s.shape[1], -1)
                 for s in states]), jnp.concatenate(write))
        if closed is not None:
            return new, snap
        return self.conv.at[:, row].set(new[:, 0]), snap


def _attend_over(cfg, lay, pools, blocks, offsets, tables, *,
                 kv_lengths=None, q_pos=None, q_table=None, window: int = 0,
                 n_valid=None):
    """``paged_attend`` or ``latent_attend``, as the model's attention
    layers keep K/V heads or one latent: ONE token a row that attends
    its first ``kv_lengths`` keys, or one row's window of queries at
    positions ``q_pos`` [w], each over the keys up to its own — or, for
    K/V heads, both in ONE window (``paged_attend``'s fourth form): the
    one-token rows of ``tables`` and then the window of the row whose
    table is ``q_table`` [1, T].  ``window``: the pools and tables are
    the window layers', attended within it.  ``n_valid``: the real
    queries of the window, its first (neither form's walk goes where
    only the padding lanes behind them see).  Which form attends a
    window of queries — the walk over key blocks or, on a table of one
    key block, the packed form under a mask — is ``paged_attend``'s to
    derive from the table it is handed; no program here depends on
    it."""
    if cfg.value_lanes is not None:
        return latent_attend(lay, pools, blocks, offsets, tables,
                             scale=cfg.attention_multiplier,
                             kv_lengths=kv_lengths, q_pos=q_pos,
                             n_valid=n_valid)
    return paged_attend(lay, pools, blocks, offsets, tables,
                        q_per_kv=cfg.n_heads // cfg.n_kv_heads,
                        scale=cfg.attention_multiplier,
                        kv_lengths=kv_lengths, q_pos=q_pos, n_valid=n_valid,
                        mask_tables=q_table, window=window)


def _two_groups(cfg, pools, tables, blocks_of, offsets, **how):
    """``_attend_over`` for each of the model's groups of K/V layers:
    the full layers' over ``pools[:2]`` through the first half of
    ``tables`` [.., 2 T], the window layers' over ``pools[2:]`` through
    the second.  ``blocks_of(table)``: where the pass's tokens are
    written, by that group's table.
    -> (attend_for, window_for, ``pools()``: what the layers left, in
        ``pools``' order)."""
    if not cfg.n_window:
        attend_for, kv = _attend_over(
            cfg, PoolLayout.of(cfg, pools[0]), pools, blocks_of(tables),
            offsets, tables, **how)
        return attend_for, None, lambda: kv["pools"]
    T = tables.shape[-1] // 2
    full, within = tables[..., :T], tables[..., T:]
    attend_for, kv = _attend_over(
        cfg, PoolLayout.of(cfg, pools[0]), pools[:2], blocks_of(full),
        offsets, full, **how)
    window_for, wkv = _attend_over(
        cfg, PoolLayout.of(cfg, pools[2], window=True), pools[2:],
        blocks_of(within), offsets, within, window=cfg.window, **how)
    return attend_for, window_for, lambda: kv["pools"] + wkv["pools"]


def _chunk_window(table, start, C: int, bs: int):
    """A chunk's window at positions ``start .. start + C`` of the row
    whose table is ``table``: -> (positions [C], block ids [C], offsets
    [C]); a position past the table's span writes to the scratch
    block."""
    pos = start + jnp.arange(C, dtype=jnp.int32)
    oob = pos >= table.shape[0] * bs
    safe = jnp.where(oob, 0, pos)
    return (pos, jnp.where(oob, 0, table[safe // bs]),
            jnp.where(oob, 0, pos % bs))


def _row_of(pool, layer, row):
    """Layer ``layer``'s state of decode row ``row`` (a traced scalar)
    as a pool of one layer and one row, [1, 1, ..]: ONE dynamic slice
    of the pool (a static slice of the layer first is a 268 MB copy a
    layer on the chip)."""
    return jax.lax.dynamic_slice(
        pool, (layer, row) + (0,) * (pool.ndim - 2), (1, 1) + pool.shape[2:])


def make_recurrent_chunk_fn(cfg: HybridConfig, *, chunk: int,
                            block_size: int, n_table: int):
    """jitted fixed-width prefill chunk of ONE row.

    (params, pools, state, feed [b] int32, packed [T + C + 3] int32
     (``pack_chunk``: table | tokens | start, row, n_valid))
        -> (logits [C, vocab] f32, load + greedy [5] int32,
            pools, state, feed)

    (``feed[row]`` takes the greedy token, as decode.py's chunk's.)

    (Two tables, [2 T + C + 3], of a model with window layers: as the
    decode step.)

    Prompt positions ``start .. start + n_valid`` of decode row ``row``:
    each query attends the keys up to its own position (the row's table
    walked a block of keys at a time up to the last real query's, by
    K/V heads or latents; a table of one key block gathered and
    attended packed under the causal mask, as decode.py's chunk program
    does), the Mamba
    layers as one window from the row's state, which advances by the
    ``n_valid`` real tokens only — the padding of a partial last chunk
    is the identity on it.
    """
    bs, C = int(block_size), int(chunk)
    T = int(n_table) * (2 if cfg.n_window else 1)

    def build():
        @partial(jax.jit, donate_argnums=(1, 2, 3))
        def chunk_fn(params, pools, state, feed, packed):
            table, tokens, start, row, n_valid = unpack_chunk(packed, T, C)
            pos, _, off = _chunk_window(table[:int(n_table)], start, C, bs)
            attend_for, window_for, pools_out = _two_groups(
                cfg, pools, table[None],
                lambda t: _chunk_window(t[0], start, C, bs)[1][None],
                off[None], q_pos=pos, n_valid=n_valid)
            keep = marked = None
            if snapshot_geometry(cfg) is not None:
                marks, *marked = _boundaries(table, start, n_valid, C, bs)
                keep = _Snapshots(state)
            held = dict(zip(("conv", "ssm"), state))

            def state_in(mi):
                # the row's SSM state as a pool of one layer and one row
                return (_row_of(held["conv"], mi, row)[0],
                        (_row_of(held["ssm"], mi, row), 0))

            def state_out(mi, new):
                held["conv"] = held["conv"].at[mi, row].set(new[0][0])
                held["ssm"] = held["ssm"].at[mi, row].set(new[1][0][0, 0])

            x, load = hybrid.run_layers(
                cfg, params, hybrid.embed(cfg, params, tokens[None]),
                n_valid[None],
                state_in=keep.window_in(row, marks) if keep else state_in,
                state_out=keep.rows_out if keep else state_out,
                attend_for=attend_for, window_for=window_for,
                positions=pos[None])
            logits = hybrid.head(cfg, params, x[0])             # [C, V]
            greedy = jnp.argmax(logits[jnp.maximum(n_valid, 1) - 1]
                                ).astype(jnp.int32)
            return (logits, jnp.append(load, greedy), pools_out(),
                    keep.done(row=row, marked=marked) if keep else
                    tuple(held[k] for k in ("conv", "ssm")[:len(state)]),
                    feed_chunk(feed, row, n_valid, greedy))

        return chunk_fn

    return _cached(("recurrent_chunk", bs, T, C), cfg, None, None, build)


def has_step_chunk(cfg: HybridConfig) -> bool:
    """Whether ``make_recurrent_step_chunk`` has a program for this
    model: every sublayer kind must have the two-part form
    (``hybrid.TWO_PART``: Mamba-2, the short convolution, the delta
    rule, attention over K/V blocks — whichever form attends its window
    of queries —, routed experts, the dense MLP; not latent attention,
    not a window layer)."""
    return {kind for _, kind in cfg.sublayers} <= hybrid.TWO_PART


def make_recurrent_step_chunk(cfg: HybridConfig, *, chunk: int,
                              block_size: int, n_table: int):
    """jitted decode step AND one prefill chunk as ONE program: what a
    pass that holds both runs in place of the two programs above back to
    back, so that each layer's weights stream from HBM once a pass, not
    twice (``decode.make_paged_step_chunk``, for this family).

    (params, pools, state, feed [b] int32,
     packed [b * (T + 3) + T + C + 3] int32
     (``pack_step_chunk``: a ``pack_step`` array, flat, then a
     ``pack_chunk`` array))
        -> (logits [b + 1, vocab] f32,
            the step's load + greedy [4 + b], then the chunk's [5] int32,
            pools, state, feed)

    The ``b`` rows' tokens and the chunk's ``C`` are ONE window ``[1, b
    + C]`` of the layer function in its two-part form (``hybrid.block``
    with ``rows``): the embedding, the norms, ``in_proj`` / ``out_proj``,
    ``wqkv`` / ``wo``, the router, the shared expert and the dense MLP
    are one product each over ``b + C`` rows, and the routed experts ONE
    sort and one grouped matmul a projection over the ``(b + C) x
    top-k`` assignments.  A mixer runs its two parts in the forms they
    have in the two programs: attention commits the whole window's K/V
    and attends the first ``b`` queries as one-token rows and the last
    ``C`` over the chunk row's table in the form the chunk program
    gives them (the walk over key blocks, or packed under the chunk's
    mask: ``paged_attend``); Mamba-2 and the delta rule
    advance the ``b`` rows' state where it lies in the pool, and the
    chunk's row's from a slice of the pool that goes back there (that
    row is inactive in the step: the two touch different rows).  The
    head runs over ``b + 1`` rows: the decode rows and the chunk's last
    real position.  The int32 vector is the step's then the chunk's, as
    the two programs give them, the experts' load of each part counted
    apart.  Same dtypes, products and masks as the two programs.

    Only for a model ``has_step_chunk`` admits."""
    bs, C, T = int(block_size), int(chunk), int(n_table)

    def build():
        @partial(jax.jit, donate_argnums=(1, 2, 3))
        def step_chunk(params, pools, state, feed, packed):
            b = (packed.shape[0] - (T + C + 3)) // (T + 3)
            tables, tokens, positions, active = unpack_step(
                packed[:b * (T + 3)].reshape(b, T + 3), T, feed)
            table, chunk_tokens, start, row, n_valid = unpack_chunk(
                packed[b * (T + 3):], T, C)
            lay = PoolLayout.of(cfg, pools[0])
            bidx, off, kv_len = _step_indices(tables, positions, active, bs)
            pos, c_bidx, c_off = _chunk_window(table, start, C, bs)
            attend_for, kv = _attend_over(
                cfg, lay, pools, jnp.concatenate([bidx, c_bidx])[None],
                jnp.concatenate([off, c_off])[None], tables,
                kv_lengths=kv_len, q_pos=pos, q_table=table[None],
                n_valid=n_valid)
            conv, ssm = state or (None, None)
            held = {"conv": [], "ssm": ssm}
            keep = marked = None
            if snapshot_geometry(cfg) is not None:
                marks, *marked = _boundaries(table, start, n_valid, C, bs)
                keep = _Snapshots(state)

            def state_out(mi, new):
                held["conv"].append(new[0])
                held["ssm"] = new[1][0]

            # the rows' state as the step hands it over, and which row
            # is the chunk's
            x, load = hybrid.run_layers(
                cfg, params,
                hybrid.embed(cfg, params,
                             jnp.concatenate([tokens, chunk_tokens])[None]),
                jnp.append(active.astype(jnp.int32), n_valid),
                state_in=(keep.both_in(row, marks) if keep else
                          lambda mi: (conv[mi], (held["ssm"], mi), row)),
                state_out=keep.rows_out if keep else state_out,
                attend_for=attend_for, rows=b,
                positions=(jnp.concatenate([positions, pos])[None]
                           if cfg.rotary_full else None))
            last = b + jnp.maximum(n_valid, 1) - 1
            x = jnp.concatenate(
                [x[0, :b], jax.lax.dynamic_slice_in_dim(x[0], last, 1)])
            logits = hybrid.head(cfg, params, x)            # [b + 1, V]
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if keep:
                state = keep.done(_closed(bidx, off, bs), marked=marked)
            else:
                state = (jnp.stack(held["conv"]), held["ssm"]) if state \
                    else ()
            return (logits,
                    jnp.concatenate([load[0], greedy[:b], load[1],
                                     greedy[b:]]),
                    kv["pools"], state,
                    feed_chunk(feed_step(feed, active, greedy[:b]), row,
                               n_valid, greedy[b]))

        return step_chunk

    return _cached(("recurrent_step_chunk", bs, T, C), cfg, None, None,
                   build)
