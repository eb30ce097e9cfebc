"""Collective communication, two planes.

Reference analogue: ray.util.collective (python/ray/util/collective/
collective.py:120-655 — init_collective_group, allreduce:258, barrier:298,
reduce:311, broadcast:373, allgather:423, reducescatter:472, send/recv)
with NCCL/Gloo backends.

TPU-native split (SURVEY.md §5 "distributed communication backend"):
  * **Compiled plane** — collectives inside jit/shard_map lower to XLA
    ICI collectives (psum/all_gather/ppermute/reduce_scatter).  This is
    the replacement for NCCL: zero Python in the loop, fused with compute.
  * **Host plane** — out-of-band CPU collectives between *actors* through
    the object store (the Gloo analogue), for control data and CPU-only
    workers.  Rendezvous is a named actor, mirroring the reference's
    named-actor NCCL-uniqueid exchange (collective_group/util.py).
"""

from __future__ import annotations

import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec

# ---------------------------------------------------------------------------
# compiled plane — use inside shard_map'd / pjit'd functions

REDUCE_OPS = ("sum", "mean", "max", "min", "prod")


def allreduce(x, axis_name: str, op: str = "sum"):
    """In-program allreduce (reference: collective.py:258 allreduce)."""
    if op == "sum":
        return jax.lax.psum(x, axis_name)
    if op == "mean":
        return jax.lax.pmean(x, axis_name)
    if op == "max":
        return jax.lax.pmax(x, axis_name)
    if op == "min":
        return jax.lax.pmin(x, axis_name)
    if op == "prod":
        return jnp.exp(jax.lax.psum(jnp.log(x), axis_name))
    raise ValueError(f"op must be one of {REDUCE_OPS}")


def allgather(x, axis_name: str, axis: int = 0, tiled: bool = True):
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reducescatter(x, axis_name: str, axis: int = 0):
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=axis,
                                tiled=True)


def broadcast(x, axis_name: str, root: int = 0):
    """Every shard gets root's value."""
    idx = jax.lax.axis_index(axis_name)
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    return jax.lax.psum(masked, axis_name)


def permute(x, axis_name: str, perm: list[tuple[int, int]]):
    """Point-to-point ring shift (reference: send/recv collective.py:531,594
    — on TPU p2p is a compiled ppermute over ICI)."""
    return jax.lax.ppermute(x, axis_name, perm)


def ring_perm(n: int, shift: int = 1) -> list[tuple[int, int]]:
    return [(i, (i + shift) % n) for i in range(n)]


def axis_index(axis_name: str):
    return jax.lax.axis_index(axis_name)


def shard_fn(mesh: Mesh, in_specs, out_specs, fn=None, check_vma: bool = False):
    """Decorator sugar over shard_map for writing collective code."""
    def wrap(f):
        return shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
    return wrap(fn) if fn is not None else wrap


# ---------------------------------------------------------------------------
# host plane — out-of-band collectives between actors


class _Rendezvous:
    """Named-actor blackboard for a collective group (reference analogue:
    rendezvous via named actor storing the NCCL unique id,
    python/ray/util/collective/collective_group/util.py)."""

    def __init__(self, world_size: int):
        self.world_size = world_size
        self.epochs: dict[tuple[str, int], dict[int, Any]] = {}

    def put(self, key: str, epoch: int, rank: int, value) -> int:
        slot = self.epochs.setdefault((key, epoch), {"vals": {}, "seen": set()})
        slot["vals"][rank] = value
        return len(slot["vals"])

    def collect(self, key: str, epoch: int, ranks: list[int], rank: int):
        slot = self.epochs.get((key, epoch))
        if slot is None or any(r not in slot["vals"] for r in ranks):
            return None
        out = {r: slot["vals"][r] for r in ranks}
        # server-side gc once every participant has collected — no client
        # can race a deletion it hasn't consumed yet
        slot["seen"].add(rank)
        if slot["seen"] >= set(ranks):
            del self.epochs[(key, epoch)]
        return out


def create_collective_group(name: str, world_size: int):
    """Create the group's rendezvous actor (call once, any process).
    Reference: collective.py:151 create_collective_group."""
    import ray_tpu
    from ray_tpu.core.actor import ActorClass
    cls = ActorClass(_Rendezvous, name=f"rt_collective::{name}",
                     get_if_exists=True)
    return cls.remote(world_size)


class CollectiveGroup:
    """Per-process handle; rank is explicit (reference:
    init_collective_group collective.py:120)."""

    def __init__(self, name: str, world_size: int, rank: int,
                 poll_interval: float = 0.002):
        import ray_tpu
        self.name = name
        self.world_size = world_size
        self.rank = rank
        self._poll = poll_interval
        # per-key epochs: ranks doing the same sequence of ops on a key
        # stay aligned even when other keys are used by subsets (p2p)
        self._epochs: dict[str, int] = {}
        try:
            self._board = ray_tpu.get_actor(f"rt_collective::{name}")
        except Exception:
            self._board = create_collective_group(name, world_size)

    # -- internals --------------------------------------------------------

    def _exchange(self, key: str, value, ranks: Optional[list[int]] = None):
        import ray_tpu
        ranks = ranks if ranks is not None else list(range(self.world_size))
        epoch = self._epochs.get(key, 0)
        self._epochs[key] = epoch + 1
        ray_tpu.get(self._board.put.remote(key, epoch, self.rank, value))
        deadline = time.time() + 120
        while True:
            vals = ray_tpu.get(self._board.collect.remote(key, epoch, ranks,
                                                          self.rank))
            if vals is not None:
                return vals
            if time.time() > deadline:
                raise TimeoutError(
                    f"collective '{key}' timed out at rank {self.rank}")
            time.sleep(self._poll)

    # -- API (mirrors collective.py surface) ------------------------------

    def barrier(self) -> None:
        self._exchange("barrier", None)

    def allreduce(self, x: np.ndarray, op: str = "sum") -> np.ndarray:
        vals = self._exchange("allreduce", np.asarray(x))
        stack = np.stack([vals[r] for r in sorted(vals)])
        if op == "sum":
            return stack.sum(0)
        if op == "mean":
            return stack.mean(0)
        if op == "max":
            return stack.max(0)
        if op == "min":
            return stack.min(0)
        raise ValueError(f"op must be one of {REDUCE_OPS}")

    def allgather(self, x: np.ndarray) -> list[np.ndarray]:
        vals = self._exchange("allgather", np.asarray(x))
        return [np.asarray(vals[r]) for r in sorted(vals)]

    def broadcast(self, x: Optional[np.ndarray], root: int = 0) -> np.ndarray:
        vals = self._exchange("broadcast",
                              np.asarray(x) if self.rank == root else None)
        return np.asarray(vals[root])

    def reduce(self, x: np.ndarray, root: int = 0,
               op: str = "sum") -> Optional[np.ndarray]:
        out = self.allreduce(x, op=op)
        return out if self.rank == root else None

    def reducescatter(self, x: np.ndarray, op: str = "sum") -> np.ndarray:
        full = self.allreduce(x, op=op)
        chunks = np.array_split(full, self.world_size, axis=0)
        return chunks[self.rank]

    def send(self, x: np.ndarray, dst: int) -> None:
        self._exchange(f"p2p:{self.rank}->{dst}", np.asarray(x),
                       ranks=[self.rank, dst] if dst != self.rank
                       else [self.rank])

    def recv(self, src: int) -> np.ndarray:
        vals = self._exchange(f"p2p:{src}->{self.rank}", None,
                              ranks=[src, self.rank] if src != self.rank
                              else [self.rank])
        return np.asarray(vals[src])
