"""KV-cache pools: the paged block pool and what lives beside it.

vLLM's insight (PagedAttention) is that serving memory must be bounded
by a PREALLOCATED pool handed out in fixed-size units and reclaimed on
sequence exit — never grown per request.

``BlockPool`` is that pool: fixed-size TOKEN BLOCKS (two arrays, K and
V — or ONE, for a model whose values are a view of its keys: latent
attention caches one vector a token — stored as ``PoolLayout`` defines,
once, for every program that reads or writes them), a per-request
BLOCK TABLE mapping sequence positions to blocks,
and per-block REFCOUNTS so blocks are shared across requests (prefix
reuse) with copy-on-write on a shared partially-filled tail.  The
decode step stays compiled-once because the table width and batch width
are static; the price is an indirection per step (the decode programs'
attention kernel walks each row's table and copies the blocks that hold
a key; the window programs gather their rows' tables), paid because
block granularity lets long and short sequences share one pool with
near-zero waste.  Block id 0 is a reserved SCRATCH block: masked rows and
out-of-range writes are redirected there so the compiled step never
needs a conditional scatter.

``RadixIndex`` is the prefix cache over the block pool: a trie keyed on
block-sized token chunks (plus partial-tail leaves), so a new request
whose prompt head matches a cached prefix ADOPTS those blocks by
refcount instead of re-running prefill (SGLang's RadixAttention shape).
Unreferenced cached prefixes are LRU-evicted under pool pressure.

``StatePool`` is what a model's recurrent layers keep a decode row.
Where that state is small enough to keep a BLOCK (``snapshot_geometry``:
a state that is nothing but a convolution's last inputs), the pool also
holds the state at each block's END, indexed by the block's id: a chain
of blocks then carries its state, and the index adopts across the
recurrent layers too, with no second structure.

Array updates go through jitted helpers (block write, block copy,
pool swap) so the engine loop never materializes a second full pool on
the host.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.gpt import GPTConfig
from ray_tpu.parallel.sharding import (DEFAULT_LLM_RULES, Rules,
                                       sharding_for, spec_for)


# logical axes of a paged pool array [L*(N+1), bs, W]: the WIDTH dim (a
# token's heads side by side) is the sharded one (Megatron-style tensor
# parallelism — every device holds ALL blocks with h/tp of each token's
# heads, so the host-side table/refcount/CoW logic is shard-oblivious).
# Layers fold into the leading dim, which is never sharded: the pool
# must not split over pp (every layer reads its own rows of it).
POOL_AXES = (None, None, "heads")

_LANES = 128        # the TPU tiles an array's minor dim in 128 lanes


def heads_shards(mesh, rules: Rules = DEFAULT_LLM_RULES) -> int:
    """Number of shards the pool's width dim is split into (1 when
    unmeshed) — the ``tp`` degree of the serving hot path."""
    if mesh is None:
        return 1
    spec = spec_for(POOL_AXES, rules, mesh)[-1]
    if spec is None:
        return 1
    return int(np.prod([mesh.shape[a] for a in
                        ((spec,) if isinstance(spec, str) else spec)]))


@dataclass(frozen=True)
class PoolLayout:
    """THE stored layout of a paged K/V pool, and the two operations
    every program performs on it.

    A pool is ONE array ``[n_layers * n_rows, block_size, width]``:
    layer ``l``'s block ``i`` is row ``l * n_rows + i`` (``n_rows`` =
    usable blocks + the scratch block, id 0), and a token's heads lie
    side by side in the minor dim.  ``width`` is ``n_heads * head_dim``
    rounded up to a multiple of 128 lanes PER SHARD (1600 -> 1664 for
    GPT-2 XL, 768 unpadded for 124M), so the TPU tiles the trailing
    ``(block_size, width)`` without padding and keeps the buffer in the
    plain row-major layout every program computes in: a program reads
    (``read``, or ops/attention.paged_decode_attention's copies of
    single blocks, each one contiguous ``[block_size, width]`` run at
    ``rows(layer, block)``) and writes (``commit``) the rows its tables
    name and never re-tiles or copies the pool.  With a mesh the width is split
    over the heads axis (``POOL_AXES``): each shard holds whole heads,
    then its own padding lanes.  Padding lanes are written as zeros
    and never read.

    ``value_lanes``: None where keys and values are two such pools.
    An int where there is ONE pool (the model's ``value_lanes``): a
    token is one head of ``head_dim`` lanes shared by every query head
    (a latent and a rotated key), and its values are its first
    ``value_lanes`` lanes — no second array exists.
    """
    n_layers: int
    n_rows: int             # blocks per layer, scratch block included
    block_size: int
    n_heads: int
    head_dim: int
    shards: int = 1         # heads_shards(mesh, rules)
    value_lanes: Optional[int] = None

    @classmethod
    def of(cls, cfg, pool: jax.Array, shards: int = 1,
           window: bool = False) -> "PoolLayout":
        """The layout of ``pool`` as a compiled program sees it, from
        the model's ``kv_geometry``: the layers that keep K/V (not every
        layer of a hybrid model does), the K/V heads (fewer than the
        query heads under grouped queries) and the head size.
        ``window``: ``pool`` belongs to the model's SECOND group of K/V
        layers, those that attend a window (``window_geometry``)."""
        if window:
            layers, heads, head_dim = cfg.window_geometry[:3]
            value_lanes = None
        else:
            layers, heads, head_dim = cfg.kv_geometry
            value_lanes = cfg.value_lanes
        lay = cls(layers, pool.shape[0] // layers, pool.shape[1], heads,
                  head_dim, shards, value_lanes)
        if lay.shape != pool.shape:
            raise ValueError(f"pool {pool.shape} is not a {lay.shape} "
                             f"pool of {layers} layers x "
                             f"{heads} heads of {head_dim}")
        return lay

    @property
    def lanes(self) -> int:
        """Lanes of one shard's heads, before padding."""
        return self.n_heads // self.shards * self.head_dim

    @property
    def width(self) -> int:
        return self.shards * -(-self.lanes // _LANES) * _LANES

    @property
    def shape(self) -> tuple:
        return (self.n_layers * self.n_rows, self.block_size, self.width)

    def rows(self, layer, blocks):
        """Leading-dim index of layer ``layer``'s blocks ``blocks``."""
        return layer * self.n_rows + blocks

    def pack(self, x: jax.Array) -> jax.Array:
        """[..., n_heads, head_dim] -> [..., width]."""
        lead, pad = x.shape[:-2], self.width // self.shards - self.lanes
        x = x.reshape(*lead, self.shards, self.lanes)
        if pad:
            x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
        return x.reshape(*lead, self.width)

    def unpack(self, x: jax.Array) -> jax.Array:
        """[..., width] -> [..., n_heads, head_dim]."""
        lead = x.shape[:-1]
        x = x.reshape(*lead, self.shards, -1)
        return x[..., :self.lanes].reshape(*lead, self.n_heads,
                                           self.head_dim)

    def read(self, pool: jax.Array, layer, tables: jax.Array) -> jax.Array:
        """Gather layer ``layer``'s blocks ``tables`` [..., T] as keys in
        position order, as stored: [..., T * block_size, width]
        (ops/attention.packed_attention attends them so; ``unpack``
        splits the heads out).  For a window of queries and for
        cache.py's own programs: a one-token pass reads through the
        kernel, which takes only the blocks a row holds."""
        g = pool[self.rows(layer, tables)]            # [..., T, bs, W]
        return g.reshape(*tables.shape[:-1], -1, self.width)

    def commit(self, pool: jax.Array, layer, blocks: jax.Array,
               offsets: Optional[jax.Array], new: jax.Array) -> jax.Array:
        """Write tokens' K/V ``new`` [..., n_heads, head_dim] at
        ``(blocks, offsets)`` [...] of layer ``layer`` — or, with
        ``offsets`` None, whole blocks ``new`` [..., block_size,
        n_heads, head_dim] at ``blocks`` [...].  Colliding writes (the
        scratch block) land in any order."""
        rows = self.rows(layer, blocks)
        new = self.pack(new.astype(pool.dtype))
        if offsets is None:
            return pool.at[rows].set(new)
        return pool.at[rows, offsets].set(new)

    # all layers of a block chain at once — cache.py's own programs
    def read_chain(self, pool: jax.Array, table: jax.Array) -> jax.Array:
        """Blocks ``table`` [T] of every layer in the interchange format
        [L, T, n_heads, block_size, head_dim]."""
        L, T = self.n_layers, table.shape[0]
        g = self.unpack(self.read(pool, jnp.arange(L)[:, None],
                                  table[None, :]))
        return g.reshape(L, T, self.block_size, self.n_heads,
                         self.head_dim).transpose(0, 1, 3, 2, 4)

    def commit_chain(self, pool: jax.Array, table: jax.Array,
                     new: jax.Array) -> jax.Array:
        """Inverse of ``read_chain``: ``new`` [L, T, n_heads,
        block_size, head_dim] lands at blocks ``table`` [T]."""
        return self.commit(pool, jnp.arange(self.n_layers)[:, None],
                           table[None, :], None,
                           new.transpose(0, 1, 3, 2, 4))


# the pool programs below are jitted per layout (static argument 0) and
# donate the pools: a block chain moves, never the pool


@partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def _copy_block(lay: PoolLayout, pools: tuple, src, dst):
    """Every pool <- block src at dst, every layer (copy-on-write)."""
    layers = jnp.arange(lay.n_layers)
    s, d = lay.rows(layers, src), lay.rows(layers, dst)
    return tuple(p.at[d].set(p[s]) for p in pools)


@partial(jax.jit, static_argnums=(0,), donate_argnums=(1, 2))
def _write_blocks(lay: PoolLayout, pool_k, pool_v, table, new_k, new_v):
    """Both pools <- a full prefill's K/V [L, h, T*bs, hd] scattered
    through table [T] (position p lands at (table[p//bs], p%bs)).
    Duplicate scratch entries collide harmlessly — their content is
    masked."""
    def chain(new):                       # -> [L, T, h, bs, hd]
        L, h, _, hd = new.shape
        return new.reshape(L, h, table.shape[0], lay.block_size,
                           hd).transpose(0, 2, 1, 3, 4)
    return (lay.commit_chain(pool_k, table, chain(new_k)),
            lay.commit_chain(pool_v, table, chain(new_v)))


@partial(jax.jit, static_argnums=(0,))
def _gather_blocks(lay: PoolLayout, pool_k, pool_v, table):
    """Both pools' block chains in ONE fused call — the eager two-step
    (k then v, each its own dispatch + device_get) dominated prefix
    extraction latency, not the bytes."""
    return lay.read_chain(pool_k, table), lay.read_chain(pool_v, table)


@partial(jax.jit, static_argnums=(0,), donate_argnums=(1, 2))
def _install_blocks(lay: PoolLayout, pool_k, pool_v, table, new_k, new_v):
    """Both pools <- new [L, T, h, bs, hd] at table [T]: the
    adopted-prefix scatter, landing both pools in ONE dispatch.  The
    caller owns ``table``'s ids exclusively (refcount 1, freshly
    alloc'd), so no CoW is needed."""
    return (lay.commit_chain(pool_k, table, new_k),
            lay.commit_chain(pool_v, table, new_v))


class BlockPool:
    """Refcounted fixed-size token-block pool (the paged KV cache).

    The K and V arrays are stored as ``PoolLayout`` says
    (``self.layout``; ``[n_layers * (n_blocks + 1), block_size,
    width]`` each; ``self.pools`` is the tuple of them that a program is
    handed: ``(k, v)``, or ``(k,)`` alone where the layout's
    ``value_lanes`` says the values are the keys' first lanes, and
    ``self.v`` is then None) — block id 0 is the reserved scratch block
    (never allocated; inactive/out-of-range writes in the compiled step
    are redirected there), usable blocks are ids ``1..n_blocks``.
    ``read_blocks`` / ``write_blocks_at`` speak the interchange format
    ``[L, T, n_heads, block_size, head_dim]`` and convert at the
    boundary.

    Reference rules: ``alloc()`` returns a block with refcount 1;
    every additional holder (a sharing request, the prefix trie)
    ``incref``s; ``decref`` frees the block back to the pool when the
    count reaches 0.  A holder about to WRITE a block must own it
    exclusively (refcount 1) — otherwise copy-on-write first
    (``copy_block`` into a fresh block, drop the shared reference).

    Thread contract: alloc/incref/decref/array
    swaps happen on the engine loop thread; ``stats()`` may be read
    from any thread (the lock only guards the free list + refcounts).

    With a ``mesh``, the pool arrays are sharded over the heads in
    their width dim (POOL_AXES — Megatron-style tensor parallelism):
    every device holds all ``n_blocks + 1`` blocks with ``n_heads / tp``
    of each token's heads, so block ids, tables, refcounts, the radix trie
    and copy-on-write are shard-oblivious and ``n_blocks`` is both the
    global admission budget AND the per-device block count (per-device
    bytes are ``bytes_total() / tp``).

    TWO KINDS OF K/V STATE.  A model some of whose attention layers
    attend their last ``window`` keys only (``cfg.window_geometry``)
    gets a second pool for those layers, ``self.window``: a ``BlockPool``
    of its own — own arrays, own layout (the window layers folded into
    its leading dim), own free list and refcounts — whose blocks a row
    holds through a SECOND table and gives back once every query that
    could read them has passed (the engine's ``_window_cover``), so a
    row's share of it is bounded by ``window_span`` tokens whatever its
    context.  The pool of the full layers (this object) grows with the
    context as before.  ``pools`` hands a program both groups' arrays,
    the full layers' first; ``swap`` takes them back in that order.
    """

    def __init__(self, cfg, n_blocks: int, block_size: int,
                 max_seq: Optional[int] = None, dtype=None, mesh=None,
                 rules: Rules = DEFAULT_LLM_RULES, state_rows: int = 0,
                 n_window_blocks: Optional[int] = None,
                 window_span: Optional[int] = None, _window: bool = False):
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.cfg = cfg
        self.mesh = mesh
        self.rules = rules
        self.block_size = int(block_size)
        self.max_seq = int(max_seq or cfg.max_seq)
        if self.max_seq > cfg.max_seq:
            raise ValueError(
                f"cache max_seq {self.max_seq} exceeds model max_seq "
                f"{cfg.max_seq} (wpe table bound)")
        # block-table width: enough blocks to cover one max_seq sequence
        self.blocks_per_seq = -(-self.max_seq // self.block_size)
        wg = getattr(cfg, "window_geometry", None)
        # the most blocks ONE row may hold: its whole context, or (the
        # window layers' pool) a window, a chunk and a block's rounding
        self.blocks_per_row = self.blocks_per_seq
        if _window:
            span = int(window_span or wg[3] + self.block_size)
            self.blocks_per_row = min(
                self.blocks_per_seq, -(-span // self.block_size) + 1)
        if n_blocks < self.blocks_per_row:
            raise ValueError(
                f"n_blocks {n_blocks} cannot hold one max_seq={self.max_seq} "
                f"sequence ({self.blocks_per_row} blocks of {block_size})")
        self.n_blocks = int(n_blocks)             # usable (excludes scratch)
        self.dtype = dtype or cfg.dtype
        shards = self.heads_shards
        kv_layers, kv_heads, head_dim = wg[:3] if _window \
            else cfg.kv_geometry
        if kv_heads % shards:
            raise ValueError(
                f"n_heads {kv_heads} is not divisible by the heads "
                f"(tp) shard count {shards} of mesh "
                f"{dict(zip(mesh.axis_names, mesh.devices.shape))} — "
                f"the pool shards the heads dim evenly per device")
        self.layout = PoolLayout(kv_layers, self.n_blocks + 1,
                                 self.block_size, kv_heads, head_dim,
                                 shards, None if _window else cfg.value_lanes)
        self.k = self._zeros()
        self.v = self._zeros() if self.layout.value_lanes is None else None
        # the second kind of state: what a model's recurrent layers keep
        # per decode row, beside the row's blocks (None for a model whose
        # whole past is K/V)
        self.state = (StatePool(cfg, state_rows, self.n_blocks)
                      if cfg.state_geometry is not None and not _window
                      else None)
        # ... and the second kind of K/V state: the window layers' pool
        self.window: Optional[BlockPool] = None
        if wg is not None and not _window:
            if n_window_blocks is None:
                raise ValueError("a model with window layers needs "
                                 "n_window_blocks")
            self.window = BlockPool(
                cfg, n_window_blocks, block_size, max_seq=self.max_seq,
                dtype=dtype, mesh=mesh, rules=rules,
                window_span=window_span, _window=True)
        self._lock = threading.Lock()
        # pop() -> block 1 first; id 0 (scratch) is never in the list
        self._free = list(range(self.n_blocks, 0, -1))
        self._rc = [0] * (self.n_blocks + 1)
        # bumped by every reset(): block ids published before a reset
        # (e.g. to the cluster prefix directory) are fenced by this —
        # a recovered pool's old ids must never be served remotely
        self.generation = 0

    @property
    def heads_shards(self) -> int:
        """Number of shards the pool's heads are split into (1 when
        unmeshed) — the ``tp`` degree of the serving hot path."""
        return heads_shards(self.mesh, self.rules)

    def _zeros(self) -> jax.Array:
        """Allocate one zeroed pool array — heads-sharded across the
        mesh when there is one (allocated shard-local via out_shardings,
        never materialized unsharded), plain jnp.zeros otherwise.  Used
        by __init__ AND reset() so donated-pool recovery reallocates
        every device's shard, not just the addressable default."""
        if self.mesh is None:
            return jnp.zeros(self.layout.shape, self.dtype)
        sh = sharding_for(POOL_AXES, self.rules, self.mesh)
        return jax.jit(partial(jnp.zeros, self.layout.shape, self.dtype),
                       out_shardings=sh)()

    # ------------------------------------------------------------- blocks

    def alloc(self) -> Optional[int]:
        """Hand out a block (refcount 1), or None when the pool is dry
        (caller evicts cached prefixes, preempts, or queues)."""
        with self._lock:
            if not self._free:
                return None
            bid = self._free.pop()
            self._rc[bid] = 1
            return bid

    def incref(self, bid: int) -> None:
        with self._lock:
            if self._rc[bid] < 1:
                raise ValueError(f"block {bid} is not allocated")
            self._rc[bid] += 1

    def decref(self, bid: int) -> int:
        """Drop one reference; frees the block at zero.  Returns the
        remaining count."""
        with self._lock:
            if self._rc[bid] < 1:
                raise ValueError(f"block {bid} is not allocated "
                                 "(double free or never alloc'd)")
            self._rc[bid] -= 1
            rc = self._rc[bid]
            if rc == 0:
                self._free.append(bid)
            return rc

    def refcount(self, bid: int) -> int:
        with self._lock:
            return self._rc[bid]

    def release_tail(self, blocks: list, keep: int) -> int:
        """Multi-token ROLLBACK (speculative decode): drop and decref
        the chain's blocks past the first ``keep`` — the refund of a
        block charge taken up front for drafted tokens the verify pass
        rejected.  ``blocks`` is truncated in place (the caller's
        row-chain list stays the single source of truth, so a
        preemption racing in later still releases exactly what the row
        holds).  Rolled-back blocks may contain rejected lanes' K/V —
        garbage beyond the row's committed length, masked everywhere
        and freed here, never leaked.  Returns the number released."""
        keep = max(int(keep), 0)
        dropped = 0
        while len(blocks) > keep:
            self.decref(blocks.pop())
            dropped += 1
        return dropped

    @property
    def n_free(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def n_used(self) -> int:
        with self._lock:
            return self.n_blocks - len(self._free)

    # ------------------------------------------------------------- arrays

    @property
    def pools(self) -> tuple:
        """The pool arrays a program is handed: ``(k, v)``, or ``(k,)``
        where the values are a view of the keys; then the window
        layers' ``(k, v)`` where the model has such layers."""
        own = self._own
        return own if self.window is None else own + self.window.pools

    @property
    def _own(self) -> tuple:
        return (self.k,) if self.v is None else (self.k, self.v)

    def copy_block(self, src: int, dst: int) -> None:
        """Copy-on-write: duplicate src's K/V into dst (every pool).
        (A block's state snapshot does not ride along: only a FULL block
        has one, and a full block is never written again — where a
        cache keeps snapshots the index shares full blocks only.)"""
        self.swap(*_copy_block(self.layout, self._own, jnp.int32(src),
                               jnp.int32(dst)))

    def read_blocks(self, ids) -> tuple:
        """Gather a block chain's K/V to host arrays — the EXPORT side
        of replica→replica prefix transfer.  Returns ``(k, v)`` of shape
        ``[L, T, h, bs, hd]`` each (T = len(ids)), fully replicated
        host-side so the bytes can ride the object plane regardless of
        the holder's mesh layout."""
        self._two_pools("read_blocks")
        t = jnp.asarray(list(ids), jnp.int32)
        k, v = jax.device_get(_gather_blocks(self.layout, self.k,
                                             self.v, t))
        return np.asarray(k), np.asarray(v)

    def write_blocks_at(self, ids, k_new, v_new) -> None:
        """Scatter fetched block K/V (``read_blocks`` layout,
        ``[L, T, h, bs, hd]``) into freshly-allocated local blocks —
        the INSTALL side of prefix adoption.  The caller owns ``ids``
        exclusively (refcount 1, just alloc'd), so no CoW is needed;
        with a mesh the ``.at[].set`` lands sharded through the pool's
        own sharding."""
        self._two_pools("write_blocks_at")
        t = jnp.asarray(list(ids), jnp.int32)
        lay = self.layout
        chain = (lay.n_layers, t.shape[0], lay.n_heads, lay.block_size,
                 lay.head_dim)
        k_new = jnp.asarray(k_new, self.dtype).reshape(chain)
        v_new = jnp.asarray(v_new, self.dtype).reshape(chain)
        self.k, self.v = _install_blocks(lay, self.k, self.v, t,
                                         k_new, v_new)

    def write_prefill(self, table, k_new: jax.Array,
                      v_new: jax.Array) -> None:
        """Seed a request's blocks from a FULL prefill ([L, h, S, hd]
        each — the r10 training-forward prefill): the whole padded
        sequence scatters through the block table in one jitted call.
        S may be shorter than the table span (zero-padded right);
        unowned table entries point at the scratch block, whose garbage
        the kv-length masks hide."""
        self._two_pools("write_prefill")
        span = self.blocks_per_seq * self.block_size
        s = k_new.shape[2]
        if s < span:
            pad = [(0, 0), (0, 0), (0, span - s), (0, 0)]
            k_new = jnp.pad(k_new, pad)
            v_new = jnp.pad(v_new, pad)
        self.k, self.v = _write_blocks(self.layout, self.k, self.v,
                                       jnp.asarray(table, jnp.int32),
                                       k_new, v_new)

    def _two_pools(self, what: str) -> None:
        """The interchange format ``[L, T, h, bs, hd]`` is a K and a V
        of head lanes: a pool of latents has none yet, nor has a chain
        that carries state snapshots."""
        if self.v is None:
            raise NotImplementedError(
                f"{what}: no interchange format for a pool whose values "
                f"are a view of its keys")
        if self.snapshots:
            raise NotImplementedError(
                f"{what}: no interchange format for blocks that carry a "
                f"state snapshot")

    @property
    def snapshots(self) -> bool:
        """Whether every full block carries the recurrent state at its
        end (``StatePool.snap``, indexed by block id)."""
        return self.state is not None and self.state.snap is not None

    def swap(self, k: jax.Array, v: Optional[jax.Array] = None,
             *window) -> None:
        """Install the compiled step's updated pool arrays
        (``*self.pools`` as a program returned them)."""
        self.k, self.v = k, v
        if window:
            self.window.swap(*window)

    def reset(self) -> None:
        """Reallocate the pool and drop every reference.  Needed after a
        FAILED compiled step: chunk-prefill and decode both donate the
        pool buffers, so an exception mid-step can leave self.k/v
        pointing at invalidated storage.  The caller fails all in-flight
        requests AND clears the prefix index (cached prefixes would
        otherwise point at zeroed blocks — silently wrong KV).  With a
        mesh, _zeros reallocates the pool SHARDED, every device's shard
        included — recovery must restore the same layout the compiled
        steps donate-commit into."""
        self.k = self._zeros()
        self.v = self._zeros() if self.layout.value_lanes is None else None
        if self.state is not None:
            self.state.reset()
        if self.window is not None:
            self.window.reset()
        with self._lock:
            self._free = list(range(self.n_blocks, 0, -1))
            self._rc = [0] * (self.n_blocks + 1)
            self.generation += 1

    # ------------------------------------------------------------- stats

    def bytes_total(self) -> int:
        """Bytes of the pools as stored, padding lanes included, and of
        the recurrent-state pool where there is one."""
        itemsize = np.dtype(jnp.zeros((), self.dtype).dtype).itemsize
        return (len(self._own) * int(np.prod(self.layout.shape)) * itemsize
                + self.state_bytes()
                + (self.window.bytes_total() if self.window is not None
                   else 0))

    def state_bytes(self) -> int:
        return self.state.bytes_total() if self.state is not None else 0

    @property
    def state_rows_in_use(self) -> int:
        return self.state.rows_in_use if self.state is not None else 0

    def stats(self) -> dict:
        with self._lock:
            free = len(self._free)
        shards = self.heads_shards
        return {
            "state_bytes": self.state_bytes(),
            "state_rows_in_use": self.state_rows_in_use,
            "state_snapshot_bytes": (self.state.snapshot_bytes()
                                     if self.state is not None else 0),
            "block_size": self.block_size,
            # blocks are replicated in COUNT across tp shards (heads are
            # what's split), so blocks_total is simultaneously the
            # global admission budget and the per-device block count —
            # both reported so no consumer has to guess which one a
            # gauge means
            "blocks_total": self.n_blocks,
            "blocks_per_device": self.n_blocks,
            "blocks_free": free,
            "blocks_used": self.n_blocks - free,
            "max_seq": self.max_seq,
            "bytes_total": self.bytes_total(),
            "bytes_per_device": self.bytes_total() // shards,
            "tp_shards": shards,
            "generation": self.generation,
            # the window layers' pool (0 / 0 where the model has none)
            "window_blocks_total": (self.window.n_blocks
                                    if self.window is not None else 0),
            "window_blocks_held": (self.window.n_used
                                   if self.window is not None else 0),
        }


# ---------------------------------------------------------------------------
# recurrent state


@partial(jax.jit, donate_argnums=(0, 1))
def _zero_row(conv, ssm, row):
    """Both state arrays <- zeros at decode row ``row``, every layer."""
    return (conv.at[:, row].set(jnp.zeros((), conv.dtype)),
            ssm.at[:, row].set(jnp.zeros((), ssm.dtype)))


@partial(jax.jit, donate_argnums=(0,))
def _restore_row(conv, snap, row, block):
    """Decode row ``row`` <- the state at the END of block ``block``,
    every layer; block 0 (no block adopted): zeros.  ONE program for a
    cold admission and an adoption alike, in ``_zero_row``'s place."""
    kept = snap[block].reshape(conv.shape[0], *conv.shape[2:])
    return conv.at[:, row].set(jnp.where(block > 0, kept, 0))


def snapshot_geometry(cfg):
    """(layers, lanes a layer) of the state snapshot a block carries, or None
    where the model's state has no snapshot form.  DERIVED from
    ``cfg.state_geometry``, no option: a state that is nothing but a
    convolution's last ``L - 1`` inputs (no recurrent entry) is a few
    tokens' activations a layer — under one block of the same model's
    K/V at any block size past a few tokens — and the state after ANY
    token of a window is a slice of that window's own inputs, so a
    program keeps it at every block boundary it crosses with no second
    pass.  An SSM or matrix state is neither small (2-4 MB a layer a
    row) nor to be had mid-window without stopping the scan there.
    Stored ``[blocks, layers * (L - 1) * d]``: the block's id leads, as
    it leads a K/V pool's rows, so that the scatter of the blocks a pass
    closes writes whole rows in place (indexed behind a layers dim the
    compiler re-laid the whole array out around every scatter, 604 MB
    each way in the described-chip compile), and everything behind it is
    folded into the minor dim: a trailing ``(L - 1, d)`` would be tiled
    ``(16, 128)`` and take eight times its bytes."""
    geometry = getattr(cfg, "state_geometry", None)
    if geometry is None or geometry[2] is not None:
        return None
    layers, conv, _ = geometry
    return layers, int(np.prod(conv))


class StatePool:
    """Per-row recurrent state of a model's state-space,
    linear-attention or short-convolution layers: the second kind of
    serving state, owned by the ``BlockPool`` whose blocks hold the
    attention layers' K/V.

    Preallocated arrays ``[recurrent layers, rows, ...]``, what the
    model's ``cfg.state_geometry`` says and no more — ``conv`` (the
    causal convolution's last inputs, in the activation dtype) and,
    where the geometry has a recurrent entry, ``ssm`` (float32:
    Mamba-2's selective-scan state ``[heads * head width, state]``, or
    the delta rule's matrix state ``[keys, heads * values]``; None for
    the short convolution, whose inputs are its whole state) — indexed
    by DECODE ROW: a row's state is the fixed-size summary of everything
    the row has read, so there is nothing to page.  Like the K/V pools
    they are donated to every compiled program and updated in place
    (``arrays``, in the order a program takes and returns them).  A
    program leaves the state of a row it does not advance exactly as it
    was (ops/ssm.py: ``n_valid`` 0), so no scratch row is needed.

    SNAPSHOTS.  Where ``snapshot_geometry`` gives the state a snapshot
    form, ``snap`` ``[n_blocks + 1, layers * lanes]`` holds the state at
    the END of each full block, indexed by the BLOCK's id (0: the
    scratch block, where a program's idle writes land): written by the
    program that writes the block's last token, it is allocated,
    refcounted, evicted and freed WITH the block — it has no life of
    its own — and a chain of blocks in the ``RadixIndex`` carries the
    state after its last token.  ``snap`` is None for the SSM and matrix
    states: their rows cannot be kept for a prefix, and a preempted
    request of such a model re-prefills from zero.

    Lifecycle, with the row's blocks: ``admit(row, block)`` sets the
    row's state to the snapshot of the last ADOPTED block (zeros with
    none: a prompt starts from nothing), ``release(row)`` drops it — at
    natural exit and at preemption alike.
    """

    def __init__(self, cfg, n_rows: int, n_blocks: int = 0):
        if n_rows < 1:
            raise ValueError(f"a state pool needs >= 1 row, got {n_rows}")
        layers, conv, ssm = cfg.state_geometry
        self._shapes = [((layers, n_rows, *conv), cfg.dtype)]
        if ssm is not None:
            self._shapes.append(((layers, n_rows, *ssm), jnp.float32))
        snap = snapshot_geometry(cfg)
        self._snap_shape = None if snap is None else (
            (n_blocks + 1, snap[0] * snap[1]), cfg.dtype)
        self._rows: set = set()
        self.reset()

    def reset(self) -> None:
        """(Re)allocate zeroed arrays and forget every row (also the
        recovery after a failed program invalidated the donated ones)."""
        zeros = [jnp.zeros(shape, dt) for shape, dt in self._shapes]
        self.conv = zeros[0]
        self.ssm = zeros[1] if len(zeros) > 1 else None
        self.snap = (None if self._snap_shape is None
                     else jnp.zeros(*self._snap_shape))
        self._rows.clear()

    @property
    def arrays(self) -> tuple:
        """What a program is handed, and hands back: ``(conv, ssm)``, or
        ``(conv, snap)`` of a state with a snapshot form."""
        return tuple(a for a in (self.conv, self.ssm, self.snap)
                     if a is not None)

    def admit(self, row: int, block: int = 0) -> None:
        """``block``: the last block of the chain the row adopted."""
        if self.snap is not None:
            self.conv = _restore_row(self.conv, self.snap, np.int32(row),
                                     np.int32(block))
        else:
            self.conv, self.ssm = _zero_row(self.conv, self.ssm,
                                            np.int32(row))
        self._rows.add(row)

    def release(self, row: int) -> None:
        self._rows.discard(row)

    def swap(self, *arrays) -> None:
        """Install a compiled program's updated state arrays
        (``arrays``' order)."""
        if self.snap is not None:
            self.conv, self.snap = arrays
        else:
            self.conv, self.ssm = arrays

    @property
    def rows_in_use(self) -> int:
        return len(self._rows)

    def snapshot_bytes(self) -> int:
        if self._snap_shape is None:
            return 0
        shape, dt = self._snap_shape
        return int(np.prod(shape)) * np.dtype(dt).itemsize

    def bytes_total(self) -> int:
        """The rows' state and the blocks' snapshots."""
        return sum(int(np.prod(shape)) * np.dtype(dt).itemsize
                   for shape, dt in self._shapes) + self.snapshot_bytes()


# ---------------------------------------------------------------------------
# radix prefix index


class _TrieNode:
    __slots__ = ("key", "block", "n_valid", "children", "parent", "lru")

    def __init__(self, key, block, n_valid, parent):
        self.key = key            # tuple of tokens (len == block_size for
        #                           interior/full nodes, < for tail leaves)
        self.block = block        # pool block id holding these tokens' KV
        self.n_valid = n_valid    # valid token count in the block
        self.children: dict = {}
        self.parent = parent
        self.lru = 0


class RadixIndex:
    """Trie over cached prompt prefixes, keyed on block-sized token
    chunks; holds one pool reference per cached block.

    * ``insert(tokens, block_ids)`` — cache a finished/preempted
      request's prefix chain: full blocks become interior nodes, a
      partial tail becomes a leaf (matched only when its whole content
      is a prefix of a later prompt — the shared-prompt-head case).
      Already-cached chunks dedupe to the existing node (the caller's
      duplicate block is simply not retained).
    * ``match(prompt)`` — longest cached chain that is a prefix of the
      prompt, CAPPED at ``len(prompt) - 1`` tokens so at least one
      prompt token always runs prefill (its logits produce the first
      sampled token).  Matched blocks are increfed for the caller.
    * ``evict(n)`` — LRU eviction of UNREFERENCED leaves (pool refcount
      1, i.e. only the trie holds the block); interior nodes become
      evictable once their subtree is gone.

    Over a pool whose blocks carry state snapshots (``pool.snapshots``)
    a chain is its blocks AND the state after its last token, which
    only a FULL block has: partial tails are then neither inserted nor
    matched, and every match ends on a block boundary.

    Single-threaded by design: called only from the engine loop thread
    (stats excepted, guarded by the pool's lock via refcounts).
    """

    def __init__(self, pool: BlockPool):
        self.pool = pool
        self.bs = pool.block_size
        self.tails = not pool.snapshots
        self.root = _TrieNode((), 0, 0, None)
        self._clock = 0
        self._nodes = 0
        # cumulative token counters (engine folds into stats)
        self.hit_tokens = 0
        self.lookup_tokens = 0
        self.evicted_blocks = 0

    def _touch(self, node: _TrieNode) -> None:
        self._clock += 1
        while node is not None and node is not self.root:
            node.lru = self._clock
            node = node.parent

    @property
    def cached_blocks(self) -> int:
        return self._nodes

    # -------------------------------------------------------------- match

    def match(self, prompt: np.ndarray) -> tuple:
        """(block_ids, n_tokens): the adopted chain, blocks increfed.
        Caller must decref each id when done (release or CoW)."""
        bs = self.bs
        n = len(prompt)
        self.lookup_tokens += n
        node, ids, matched = self.root, [], 0
        while matched + bs < n:        # full block AND >= 1 token left over
            key = tuple(int(t) for t in prompt[matched:matched + bs])
            child = node.children.get(key)
            if child is None or child.n_valid != bs:
                break
            ids.append(child.block)
            matched += bs
            node = child
        # partial tail leaves: longest one whose WHOLE content prefixes
        # the remaining prompt (still leaving >= 1 token for prefill)
        best = None
        for key, child in node.children.items() if self.tails else ():
            m = len(key)
            if m >= bs or m >= n - matched:
                continue
            if tuple(int(t) for t in prompt[matched:matched + m]) != key:
                continue
            if best is None or m > len(best.key):
                best = child
        if best is not None:
            ids.append(best.block)
            matched += len(best.key)
            node = best
        for bid in ids:
            self.pool.incref(bid)
        if node is not self.root:
            self._touch(node)
        self.hit_tokens += matched
        return ids, matched

    # ------------------------------------------------------------- insert

    def insert(self, tokens: np.ndarray, block_ids: list) -> None:
        """Cache the chain for ``tokens`` (the request's clean KV prefix)
        backed by ``block_ids`` (the request's table, in order).  Kept
        blocks gain a trie reference; chunks already cached dedupe to
        the existing node and the caller's copy is not retained."""
        bs = self.bs
        n = len(tokens)
        node = self.root
        for i in range(n // bs):
            key = tuple(int(t) for t in tokens[i * bs:(i + 1) * bs])
            child = node.children.get(key)
            if child is None:
                bid = block_ids[i]
                child = _TrieNode(key, bid, bs, node)
                node.children[key] = child
                self.pool.incref(bid)
                self._nodes += 1
            node = child
        j = n % bs if self.tails else 0
        if j:
            key = tuple(int(t) for t in tokens[n - j:])
            if key not in node.children:
                bid = block_ids[n // bs]
                leaf = _TrieNode(key, bid, j, node)
                node.children[key] = leaf
                self.pool.incref(bid)
                self._nodes += 1
                node = leaf
        self._touch(node)

    # ------------------------------------------------------------ evict

    def _leaves(self) -> list:
        out, stack = [], [self.root]
        while stack:
            node = stack.pop()
            kids = list(node.children.values())
            if not kids and node is not self.root:
                out.append(node)
            stack.extend(kids)
        return out

    def evict(self, n: int) -> int:
        """Free up to ``n`` blocks by dropping unreferenced cached
        prefixes, LRU-first, leaves-up.  Returns blocks actually freed
        (may be < n when everything left is referenced by a request).

        ONE trie walk per call seeds an LRU heap of evictable leaves;
        evicting a leaf pushes its parent when that exposes it — so a
        multi-block eviction is O(nodes + freed·log) instead of one
        full walk (plus a refcount lock round-trip per node) per freed
        block, which mattered: admission/growth pressure calls this
        from the decode hot path."""
        import heapq
        freed = 0
        heap = [(leaf.lru, id(leaf), leaf) for leaf in self._leaves()
                if self.pool.refcount(leaf.block) == 1]
        heapq.heapify(heap)
        while heap and freed < n:
            _, _, node = heapq.heappop(heap)
            # a heap entry may be stale (re-referenced since the walk)
            if (node.children
                    or node.parent.children.get(node.key) is not node
                    or self.pool.refcount(node.block) != 1):
                continue
            del node.parent.children[node.key]
            self.pool.decref(node.block)
            self._nodes -= 1
            freed += 1
            self.evicted_blocks += 1
            p = node.parent
            if (p is not self.root and not p.children
                    and self.pool.refcount(p.block) == 1):
                heapq.heappush(heap, (p.lru, id(p), p))
        return freed

    def clear(self) -> None:
        """Drop the whole index WITHOUT touching pool refcounts — used
        only after BlockPool.reset() (which already zeroed them)."""
        self.root = _TrieNode((), 0, 0, None)
        self._nodes = 0
