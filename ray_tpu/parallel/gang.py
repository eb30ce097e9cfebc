"""Gang: slice-aware SPMD worker group.

The TPU-defining layer (SURVEY.md §7 M3).  Replaces the reference's
WorkerGroup + out-of-band NCCL rendezvous (reference:
train/_internal/worker_group.py:92 + train/torch/config.py:69
_setup_torch_process_group) with slice-native formation:

  * single host (this round's fast path): ONE in-process member owns all
    local chips — jax is single-controller per host, so the driver itself
    drives the mesh; no process hop, no serialization of arrays.
  * multi host: one member process per TPU host, co-initialized with
    ``jax.distributed.initialize`` (coordinator = rank-0 member), each
    running the same compiled program (SPMD).  Members are actors with
    ``num_tpus`` resources so the scheduler places them on TPU hosts.
    Members are WORKER processes, and the node service starts every
    worker with ``JAX_PLATFORMS=cpu`` (one process holds a host's
    chips: the driver) — so ``num_tpus_per_member`` reserves chips for
    placement but a member computes on CPU devices.  Several chips on
    ONE host are the single-host path above: one process, one mesh
    over all local chips.  There is deliberately no second path.

The gang is the unit of fault tolerance: a member death breaks the ICI
mesh, so recovery = rebuild the gang and restore from checkpoint
(reference restart-based analogue: backend_executor.py:571 _restart).
"""

from __future__ import annotations

import socket
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
from jax.sharding import Mesh

from ray_tpu.core import fault_injection as _fi
from ray_tpu.parallel.mesh import batch_sharding, create_mesh, mesh_shape


class GangMemberDied(RuntimeError):
    """A member actor died (or its call failed) during a collective
    gang operation.  Carries the rank so elastic recovery can name
    survivors without parsing error strings."""

    def __init__(self, rank: int, message: str):
        self.rank = rank
        super().__init__(message)


def _gather(refs: list, timeout: Optional[float], what: str) -> list:
    """Collective get with PER-MEMBER completion watching: the first
    member failure surfaces immediately as GangMemberDied naming the
    rank, instead of blocking until the stragglers a dead/failed peer
    has wedged (e.g. the rest of a formation barrier) time out."""
    import ray_tpu
    from ray_tpu.core.client import GetTimeoutError
    deadline = None if timeout is None else time.monotonic() + timeout
    pending = {ref: i for i, ref in enumerate(refs)}
    out: list = [None] * len(refs)
    while pending:
        ready, _ = ray_tpu.wait(list(pending), num_returns=len(pending),
                                timeout=1.0)
        for ref in ready:
            i = pending.pop(ref)
            try:
                out[i] = ray_tpu.get([ref])[0]
            except Exception as e:
                raise GangMemberDied(
                    i, f"gang member rank {i}/{len(refs)} failed during "
                       f"{what}: {e}") from e
        if deadline is not None and time.monotonic() > deadline and pending:
            raise GetTimeoutError(
                f"gang {what} timed out; ranks still pending: "
                f"{sorted(pending.values())}")
    return out


@dataclass
class GangConfig:
    mesh_axes: dict[str, int] = field(default_factory=lambda: {"dp": -1})
    num_hosts: int = 1
    use_cpu_devices: bool = False  # tests: virtual CPU mesh


class TpuGang:
    """Handle to a formed gang.  `run(fn, *args)` executes `fn` inside the
    mesh context on every member (single-host: inline)."""

    def __init__(self, config: Optional[GangConfig] = None,
                 devices: Optional[list] = None):
        self.config = config or GangConfig()
        if devices is None:
            devices = (jax.devices("cpu") if self.config.use_cpu_devices
                       else jax.devices())
        self.devices = devices
        self.mesh: Mesh = create_mesh(self.config.mesh_axes, devices=devices)
        self.num_hosts = self.config.num_hosts

    # -- info -------------------------------------------------------------

    @property
    def axis_sizes(self) -> dict[str, int]:
        return mesh_shape(self.mesh)

    @property
    def num_devices(self) -> int:
        return len(self.devices)

    # -- execution ---------------------------------------------------------

    def run(self, fn: Callable, *args, **kwargs) -> Any:
        """Execute fn with the gang mesh active (single-host inline)."""
        with self.mesh:
            return fn(*args, **kwargs)

    def put_batch(self, batch: Any) -> Any:
        """Host batch pytree -> sharded jax.Arrays over the data axes."""
        sh = batch_sharding(self.mesh)
        return jax.tree.map(lambda x: jax.device_put(x, sh), batch)

    def shutdown(self) -> None:
        pass


def form_gang(mesh_axes: Optional[dict[str, int]] = None,
              use_cpu_devices: bool = False) -> TpuGang:
    return TpuGang(GangConfig(mesh_axes=mesh_axes or {"dp": -1},
                              use_cpu_devices=use_cpu_devices))


# ---------------------------------------------------------------------------
# multi-host formation (skeleton — exercised via dryrun in round 1)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _routable_ip() -> str:
    """This host's address as seen by peers (UDP-connect trick; falls
    back to loopback on isolated machines)."""
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        s.connect(("8.8.8.8", 80))
        return s.getsockname()[0]
    except OSError:
        return "127.0.0.1"
    finally:
        s.close()


class GangMember:
    """Actor body for one host's member process (multi-host path).

    Placed with ``num_tpus=<chips per host>`` so the scheduler reserves a
    whole host's chips; rank 0's address is the jax.distributed
    coordinator (the analogue of the reference's TCP-store rendezvous on
    the rank-0 train worker, train/torch/config.py:69).  With
    ``cpu_backend`` the member pins jax to N virtual CPU devices before
    backend init — the multi-host test shape (collectives ride Gloo).
    """

    def __init__(self, rank: int, world: int,
                 cpu_backend: bool = False, local_device_count: int = 0):
        self.rank = rank
        self.world = world
        self.cpu_backend = cpu_backend
        self.local_device_count = local_device_count
        self._initialized = False
        self._busy = False

    def choose_coordinator(self) -> str:
        """Rank 0 picks the rendezvous address ON ITS OWN HOST (the
        driver's loopback would be unreachable from other nodes)."""
        ip = _routable_ip()
        return f"{ip}:{_free_port()}"

    def _pin_backend(self) -> None:
        import jax as _jax
        if self.cpu_backend:
            # must land before first backend touch in this fresh process
            _jax.config.update("jax_platforms", "cpu")
            # real cross-process CPU collectives (the multi-host test
            # shape); also before backend init
            _jax.config.update("jax_cpu_collectives_implementation",
                               "gloo")
            if self.local_device_count:
                _jax.config.update("jax_num_cpu_devices",
                                   self.local_device_count)

    def _info(self) -> dict:
        import jax as _jax
        return {"rank": self.rank,
                "global_devices": len(_jax.devices()),
                "local_devices": len(_jax.local_devices()),
                "pid": __import__("os").getpid()}

    def setup(self, coordinator: str) -> dict:
        from ray_tpu.parallel.jax_compat import distributed_initialize
        self._pin_backend()
        if self.world > 1 and not self._initialized:
            # resilient client: a PEER's death must surface as a
            # collective error here, not terminate this process — the
            # property the elastic gang is built on (jax_compat)
            distributed_initialize(coordinator, self.world, self.rank)
            self._initialized = True
        return self._info()

    def reinit(self, coordinator: str, world: int, rank: int) -> dict:
        """Leave the current (possibly poisoned) distributed world IN
        PLACE — same process, same pid — and join a new one at the new
        world size/rank.  The elastic re-gang step: abandon (no
        collective barrier), drop cached backends so the global device
        view shrinks/grows, re-initialize."""
        from jax.extend.backend import clear_backends

        from ray_tpu.parallel.jax_compat import (distributed_abandon,
                                                 distributed_initialize)
        self._await_idle()
        if self._initialized:
            distributed_abandon()
            self._initialized = False
        clear_backends()
        self.rank = rank
        self.world = world
        if world > 1:
            distributed_initialize(coordinator, world, rank)
            self._initialized = True
        return self._info()

    def run(self, pickled_fn: bytes, *args):
        import cloudpickle
        fn = cloudpickle.loads(pickled_fn)
        self._busy = True
        try:
            return fn(self.rank, *args)
        finally:
            self._busy = False

    def _await_idle(self, timeout: float = 45.0) -> None:
        """A reform may land while this member's run() thread is still
        wedged in a collective its dead peer poisoned; tearing the
        backend down under a live computation is undefined.  Gloo
        surfaces peer death as an error within seconds, so wait for the
        attempt to unwind before abandoning the world."""
        deadline = time.monotonic() + timeout
        while getattr(self, "_busy", False) and time.monotonic() < deadline:
            time.sleep(0.05)

    def ping(self) -> dict:
        """Liveness probe; dispatched concurrently with run() (the gang
        creates members with max_concurrency>1), so a member wedged in
        a broken collective still answers."""
        import os
        return {"rank": self.rank, "pid": os.getpid()}

    def pid(self) -> int:
        import os
        return os.getpid()


class MultiHostGang:
    """A formed multi-host gang: one GangMember actor per host, jointly
    initialized through jax.distributed (SPMD across processes).

    The reference analogue is the worker-group half of BackendExecutor
    (reference: train/_internal/backend_executor.py:94 start +
    worker_group.py:92); formation here is one collective
    jax.distributed.initialize instead of a framework process-group
    bootstrap.

    The gang is ELASTIC: a member death no longer forces a full restart.
    ``reform(survivors)`` re-forms the gang at reduced world size from
    the SURVIVING member actors — same processes, same pids, fresh
    coordinator, fresh jax.distributed world, dp axis resharded to the
    new world — and ``readmit()`` grows it back toward the target size
    with replacement actors at the next re-gang boundary.  Full teardown
    + re-formation (reference: backend_executor.py:571 restart) remains
    the fallback when no member survives or reform itself fails.
    """

    def __init__(self, num_members: int, *, num_tpus_per_member: float = 0,
                 cpu_backend: bool = False, devices_per_member: int = 0,
                 resources_per_member: Optional[dict] = None,
                 setup_timeout: float = 120.0,
                 member_cls: Optional[type] = None):
        import ray_tpu

        self.num_members = num_members
        self.target_members = num_members
        self.setup_timeout = setup_timeout
        self._cpu_backend = cpu_backend
        self._devices_per_member = devices_per_member
        opts: dict = {"max_concurrency": 4}   # ping/reinit beside run
        if num_tpus_per_member:
            opts["num_tpus"] = num_tpus_per_member
        if resources_per_member:
            opts["resources"] = resources_per_member
        self._actor_cls = ray_tpu.remote(member_cls or GangMember) \
            .options(**opts)
        self.members = [
            self._actor_cls.remote(rank=i, world=num_members,
                                   cpu_backend=cpu_backend,
                                   local_device_count=devices_per_member)
            for i in range(num_members)]
        try:
            # rank 0 picks the rendezvous address on ITS host (it may be
            # scheduled on any node), then setup is a collective barrier:
            # all members must be in flight together.  _gather surfaces
            # the FIRST failed setup promptly — the others are wedged in
            # a barrier that can no longer complete.
            self.coordinator = ray_tpu.get(
                self.members[0].choose_coordinator.remote(),
                timeout=setup_timeout)
            self.infos = _gather(
                [m.setup.remote(self.coordinator) for m in self.members],
                setup_timeout, "formation setup")
        except BaseException:
            # partial formation must not leak the members that DID come
            # up: one failed/timed-out setup used to leave world-1
            # actors alive (and holding TPU reservations) forever
            self.shutdown()
            raise
        self.global_devices = self.infos[0]["global_devices"]

    # ----------------------------------------------------------- execution

    def run(self, fn: Callable, *args,
            timeout: Optional[float] = None) -> list:
        """Run ``fn(rank, *args)`` on every member; returns per-rank
        results (SPMD: all ranks execute the same program).  No default
        timeout: a member-side attempt may legitimately run for hours.

        Completion is watched PER MEMBER: the first failure — actor
        death or member exception — surfaces immediately as
        ``GangMemberDied`` naming the rank, instead of blocking on
        stragglers a dead peer has wedged in a broken collective."""
        import cloudpickle
        payload = cloudpickle.dumps(fn)
        return _gather([m.run.remote(payload, *args)
                        for m in self.members], timeout, "run")

    def member_pids(self) -> list[int]:
        import ray_tpu
        return ray_tpu.get([m.pid.remote() for m in self.members],
                           timeout=60)

    # ------------------------------------------------------------ elasticity

    def alive_ranks(self, timeout: float = 15.0) -> list[int]:
        """Probe every member concurrently; returns the ranks that still
        answer.  One shared deadline over ALL probes — a handful of
        wedged members must cost one window, not one window each.  Death
        errors surface promptly (event-driven actor-death sealing), so
        the common case costs one round-trip."""
        import ray_tpu
        probes = [(i, m.ping.remote()) for i, m in enumerate(self.members)]
        ready, _ = ray_tpu.wait([r for _, r in probes],
                                num_returns=len(probes), timeout=timeout)
        ready_set = set(ready)
        out = []
        for i, ref in probes:
            if ref not in ready_set:
                continue   # unresponsive within the window: not alive
            try:
                ray_tpu.get([ref], timeout=5)
                out.append(i)
            except Exception:
                pass       # sealed as an actor-death error: dead
        return out

    def reform(self, survivors: list[int]) -> None:
        """Re-form the gang from the surviving member actors at world
        size ``len(survivors)`` — their PROCESSES are kept (same pids);
        only the jax.distributed world is torn down and rebuilt, with
        the dp axis implicitly resharded to the new global device set.
        Dead members' actor handles are reaped."""
        import ray_tpu
        if not survivors:
            raise ValueError("reform needs at least one survivor")
        survivors = sorted(survivors)
        dead = [m for i, m in enumerate(self.members) if i not in survivors]
        keep = [self.members[i] for i in survivors]
        world = len(keep)
        # new rank 0 picks a FRESH coordinator on its host (the old
        # coordinator may have died with rank 0, and a stale service
        # must never adopt the new world)
        self.coordinator = ray_tpu.get(
            keep[0].choose_coordinator.remote(), timeout=self.setup_timeout)
        refs = [m.reinit.remote(self.coordinator, world, i)
                for i, m in enumerate(keep)]
        self.infos = _gather(refs, self.setup_timeout, "reform")
        self.members = keep
        self.num_members = world
        self.global_devices = self.infos[0]["global_devices"]
        for m in dead:
            try:
                ray_tpu.kill(m)
            except Exception:
                pass

    def _chaos(self, point: str, **ctx) -> None:
        """Chaos-plane trigger at gang-membership boundaries
        (hotpath_registry contract: disarmed = one global load +
        is-None branch).  Runs driver-side, so scripted schedules fire
        deterministically in-process."""
        fi = _fi._active
        if fi is None:
            return
        ctx.setdefault("world", self.num_members)
        fi.on_gang(point, ctx)

    def readmit(self, count: Optional[int] = None) -> int:
        """Grow the gang back toward ``target_members`` with REPLACEMENT
        member actors (fresh processes), re-initializing the whole world
        at the larger size.  Survivor processes are still kept — this is
        the "re-admit a replacement host at the next re-gang boundary"
        step.  Returns the new world size."""
        import ray_tpu
        want = self.target_members - self.num_members \
            if count is None else count
        if want <= 0:
            return self.num_members
        self._chaos("gang_readmit", target=self.target_members,
                    want=want)
        world = self.num_members + want
        fresh = [
            self._actor_cls.remote(rank=self.num_members + j, world=world,
                                   cpu_backend=self._cpu_backend,
                                   local_device_count=self._devices_per_member)
            for j in range(want)]
        try:
            self.coordinator = ray_tpu.get(
                self.members[0].choose_coordinator.remote(),
                timeout=self.setup_timeout)
            refs = [m.reinit.remote(self.coordinator, world, i)
                    for i, m in enumerate(self.members)]
            refs += [m.setup.remote(self.coordinator) for m in fresh]
            self.infos = _gather(refs, self.setup_timeout, "readmit")
        except BaseException:
            for m in fresh:   # don't leak half-admitted replacements
                try:
                    ray_tpu.kill(m)
                except Exception:
                    pass
            raise
        self.members = self.members + fresh
        self.num_members = world
        self.global_devices = self.infos[0]["global_devices"]
        return world

    def shutdown(self) -> None:
        import ray_tpu
        for m in self.members:
            try:
                ray_tpu.kill(m)
            except Exception:
                pass
