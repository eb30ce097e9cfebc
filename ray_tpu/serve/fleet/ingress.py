"""The fleet layer: admission → occupancy routing → replica call, with
resume-on-replica-death for streams and a full ingress event trail.

``Fleet`` is installed on a DeploymentState by ``serve.fleet.enable``;
``DeploymentHandle.remote`` detects it and routes ``__call__`` traffic
through here instead of the round-robin ``assign_replica`` path.  One
request's life:

  1. **admit** — ``AdmissionController.acquire`` (token bucket +
     bounded priority queue).  Refusal raises ``ShedError``; the HTTP
     ingress maps it to ``429`` + ``Retry-After``.  Every admitted or
     shed request is counted — nothing exits this layer unaccounted.
  2. **route** — ``OccupancyRouter.assign``: power-of-two-choices on
     the engine gauges, preferring replicas that already hold the
     requested model variant.
  3. **call** — in-process bodies run on the calling thread (the
     proxy's executor); actor replicas go through the core runtime.
  4. **resume** — a replica that dies mid-request (typed
     ``EngineStoppedError``) is marked dead and the request is retried
     on another replica.  Streams resume EXACTLY: generation is
     deterministic from the request (same params/seed on every
     replica), so the retry replays and the wrapper skips the
     already-delivered prefix by token index.  A request that cannot be
     placed fails promptly with a clean error — never a silent hang.

Chaos/observability hooks follow the house gate discipline: when the
fault plane / flight recorder is disarmed each hook site costs one
module-global load + ``is None`` branch (enforced by ``ray_tpu lint``
via analysis/hotpath_registry.py).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Iterator, Optional

from ray_tpu.core import fault_injection as _fi
from ray_tpu.core import flight_recorder as _fr
from ray_tpu.serve import engine_stats
from ray_tpu.serve.fleet.admission import (AdmissionController, ShedError,
                                           parse_priority)
from ray_tpu.serve.fleet.router import NoReplicaError, OccupancyRouter
from ray_tpu.serve.qos import (PRIORITY_BATCH, EngineDrainingError,
                               ReplicaDeadError)

# the engines' rows a fleet reports (``Fleet.fleet_snapshot``: the blocks
# are the REAL memory signal behind the row counts), each reduced over
# the live replicas' probes by its rule in ``engine_stats``, and the
# snapshot's own names for three of them
_SNAPSHOT_ROWS = (
    "max_slots", "active_slots", "waiting_requests", "blocks_total",
    "block_utilization", "mesh_devices", "tp_shards", "prefix_hit_rate",
    "spec_drafted_tokens", "spec_accepted_tokens", "spec_accept_rate",
)
_SNAPSHOT_NAMES = {"max_slots": "total_slots",
                   "waiting_requests": "engine_waiting",
                   "blocks_total": "total_blocks"}


def _is_replica_death(e: BaseException, replica) -> bool:
    """Classify an exception as this-replica-died (retriable: the
    request had no observable side effects).  In-process engines raise
    the typed ReplicaDeadError subclass; a killed ACTOR replica's
    pending calls fail with the core runtime's actor-death errors
    instead, which carry no shared base class."""
    if isinstance(e, ReplicaDeadError):
        return True
    if replica is not None and replica.is_actor:
        try:
            from ray_tpu.core.client import ActorDiedError
        except ImportError:                      # pragma: no cover
            ActorDiedError = ()
        if isinstance(e, ActorDiedError):
            return True
        return isinstance(e, RuntimeError) and "Actor died" in str(e)
    return False


def _resume_kind(e: BaseException, replica) -> str:
    """Classify a replica-death re-route: planned removal (drain race /
    drain-timeout kill / explicit scale_to kill — the replica's
    lifecycle already left "active", or the typed draining error) vs a
    genuine failure.  Splitting the counter is what makes the r13
    masking bug impossible to reintroduce silently: a scale-down that
    eats resumes now shows up under ``resumed_scale_down``, and
    ``resumed_failure`` staying 0 without chaos is an assertable
    invariant."""
    if isinstance(e, EngineDrainingError):
        return "resumed_scale_down"
    if replica is not None \
            and getattr(replica, "lifecycle", "active") != "active":
        return "resumed_scale_down"
    return "resumed_failure"


@dataclass
class FleetConfig:
    """Ingress knobs for one deployment's fleet layer."""
    rate: float = 200.0                  # admission tokens/s
    burst: float = 64.0                  # bucket depth (absorbed burst)
    max_queue_depth: int = 64            # parked requests before shedding
    max_queue_wait_s: Any = None         # float or {priority: seconds}
    interactive_wait_s: float = 2.0      # used when max_queue_wait_s is None
    batch_wait_s: float = 10.0
    retry_on_replica_failure: bool = True
    max_resume_attempts: int = 2         # re-routes after a replica death
    drain_deadline_s: float = 30.0       # DRAINING -> forced kill+resume
    seed: int = 0                        # router's p2c rng
    keep_events: int = 8192
    # cluster prefix plane (serve/fleet/prefix_directory.py): directory
    # + prefix-affinity routing + replica→replica block adoption.  OFF
    # by default — with it off, the fleet path is byte-identical to the
    # plane not existing (every hook gates on ``fleet.prefix is None``)
    cluster_prefix: bool = False
    prefix_directory_capacity: int = 4096
    prefix_fetch_timeout_s: float = 5.0  # extract/install per-hop cap


@dataclass
class FleetCounters:
    """Request accounting.  Identity (asserted in tests): every admitted
    request ends in exactly one of completed/errored/cancelled, and
    every replica-death re-route is classified — there is deliberately
    NO aggregate ``resumed`` field, so a new death path MUST pick a
    class (``fleet_snapshot`` derives the sum for compatibility)."""
    admitted: int = 0
    shed: int = 0
    rejected: int = 0                    # malformed envelope (client bug)
    completed: int = 0
    errored: int = 0
    cancelled: int = 0                   # consumer abandoned the stream
    resumed_failure: int = 0             # re-route after a CRASH
    resumed_scale_down: int = 0          # re-route off a planned removal
    drained: int = 0                     # replicas retired empty
    drain_timeout: int = 0               # drains that fell back to kill
    replayed_tokens: int = 0             # resume-replay cost (skipped)


class Fleet:
    """Per-deployment fleet layer (admission + router + event trail)."""

    def __init__(self, state, config: Optional[FleetConfig] = None):
        self.state = state
        self.cfg = config or FleetConfig()
        self.name = state.deployment.name
        waits = self.cfg.max_queue_wait_s
        if waits is None:
            from ray_tpu.inference.engine import PRIORITY_INTERACTIVE
            waits = {PRIORITY_INTERACTIVE: self.cfg.interactive_wait_s,
                     PRIORITY_BATCH: self.cfg.batch_wait_s}
        self.admission = AdmissionController(
            rate=self.cfg.rate, burst=self.cfg.burst,
            max_queue_depth=self.cfg.max_queue_depth,
            max_queue_wait_s=waits)
        self.router = OccupancyRouter(state, seed=self.cfg.seed)
        self.counters = FleetCounters()
        self._clock = threading.Lock()
        self._events: deque = deque(maxlen=self.cfg.keep_events)
        self.prefix = None
        if self.cfg.cluster_prefix:
            from ray_tpu.serve.fleet.prefix_directory import PrefixPlane
            self.prefix = PrefixPlane(
                self, capacity=self.cfg.prefix_directory_capacity,
                fetch_timeout_s=self.cfg.prefix_fetch_timeout_s)

    # ----------------------------------------------------------- event trail

    def note(self, kind: str, **fields) -> None:
        """Ingress event: local bounded ring always; a timestamped copy
        into the flight recorder when one is armed so `ray_tpu
        timeline` shows admission/shed/route next to task stages."""
        ev = {"t": time.time(), "kind": kind, "deployment": self.name}
        ev.update(fields)
        self._events.append(ev)
        rec = _fr._active
        if rec is None:
            return
        rec.note_ingress(ev)

    def _chaos(self, point: str, **ctx) -> None:
        """Fault-plane hook (serve_route / serve_stream): zero-overhead
        gate when no plan is installed."""
        fi = _fi._active
        if fi is None:
            return
        ctx["fleet"] = self
        fi.on_serve(point, ctx)

    def events(self) -> list[dict]:
        return list(self._events)

    def dump_events(self, path: str) -> str:
        import json
        with open(path, "w") as f:
            json.dump(self.events(), f)
        return path

    def _count(self, field_name: str, n: int = 1) -> None:
        with self._clock:
            setattr(self.counters, field_name,
                    getattr(self.counters, field_name) + n)

    # ------------------------------------------------------------- signals

    def total_load(self) -> float:
        """Deployment-wide demand for the autoscaler: engine-held slots
        + engine queues + requests parked at the ingress."""
        total = float(self.admission.queue_depth())
        for r in self.router.live_replicas():
            try:
                st = self.router.probe(r)
            except Exception:
                continue
            if st is None:
                total += r.ongoing
            elif not st.get("stopped"):
                total += (float(st.get("active_slots", 0))
                          + float(st.get("waiting_requests", 0)))
        return total

    def fleet_snapshot(self) -> dict:
        """Point-in-time fleet state: the live replicas' probes, each
        key reduced by its rule in ``serve/engine_stats.py``, and the
        ingress's own counters."""
        reps = self.router.live_replicas()
        probed = []
        for r in reps:
            try:
                st = self.router.probe(r)
            except Exception:
                continue
            if st and not st.get("stopped"):
                probed.append(st)
        snap = {_SNAPSHOT_NAMES.get(key, key): value for key, value
                in engine_stats.reduce(probed, _SNAPSHOT_ROWS).items()}
        slots = snap["total_slots"]
        with self._clock:
            counters = dict(self.counters.__dict__)
        # compatibility aggregate (the split fields are authoritative)
        counters["resumed"] = (counters["resumed_failure"]
                               + counters["resumed_scale_down"])
        if self.prefix is not None:
            # cluster prefix plane: remote hits / fetch failures /
            # fallback recomputes + live directory size (all zero-less
            # ABSENT when the plane is off, so plane-less snapshots
            # stay byte-identical to previous rounds)
            counters.update(self.prefix.counters())
        return {
            "replicas": len(reps),
            **snap,
            "ingress_queued": self.admission.queue_depth(),
            "occupancy": (snap["active_slots"] / slots) if slots else 0.0,
            **counters,
        }

    # ------------------------------------------------------------- serving

    def remote(self, args: tuple, kwargs: dict) -> "_FleetResponse":
        """Admission happens HERE (synchronously — backpressure is the
        point); routing/calling happen in ``result()``."""
        req = args[0] if args and isinstance(args[0], dict) else None
        priority = PRIORITY_BATCH
        model = None
        if req is not None:
            try:
                priority = parse_priority(req.get("priority"))
            except ValueError:
                # malformed envelope: a CLIENT error, accounted (the
                # complete-accounting invariant covers every request:
                # offered == admitted + shed + rejected)
                self._count("rejected")
                self.note("rejected", reason="bad priority",
                          value=repr(req.get("priority")))
                raise
            model = req.get("model")
        try:
            waited = self.admission.acquire(priority)
        except ShedError as e:
            self._count("shed")
            self.note("shed", reason=e.reason,
                      retry_after_s=round(e.retry_after_s, 3),
                      priority=priority)
            raise
        self._count("admitted")
        self.note("admit", queued_s=round(waited, 6), priority=priority,
                  model=model)
        return _FleetResponse(self, args, kwargs, model, priority)

    def _call(self, replica, args: tuple, kwargs: dict,
              timeout: Optional[float] = None):
        if self.prefix is not None:
            # cluster prefix adoption runs before EVERY replica call
            # (first route and resume re-routes alike): if the
            # directory knows a peer holding this prompt's prefix,
            # fetch + install it here so the engine's admission match
            # adopts it.  before_call NEVER raises — any failure is a
            # counted, silent downgrade to local recompute
            self.prefix.before_call(replica, args)
        if replica.is_actor:
            import ray_tpu
            ref = replica.impl.handle_request.remote("__call__", args,
                                                     kwargs)
            return ray_tpu.get(ref, timeout=timeout)
        return replica.impl.handle_request("__call__", args, kwargs)

    # --------------------------------------------------------------- chaos

    def kill_replica(self, replica) -> None:
        """Chaos helper: kill a replica's body in place (engines shut
        down, pending requests fail with EngineStoppedError) WITHOUT
        removing it from the membership — exactly what a crash looks
        like to the router.  The controller's self-heal tick replaces
        it."""
        self.note("chaos_kill", replica=replica.tag)
        if self.prefix is not None:
            self.prefix.invalidate_holder(replica.tag)
        try:
            if replica.is_actor:
                import ray_tpu
                ray_tpu.kill(replica.impl)
            else:
                replica.impl.close()
        except Exception:
            pass


class _FleetResponse:
    """Future-like over the fleet path (same ``result()`` surface as
    ServeResponse).  Routing + the replica call + the resume loop start
    EAGERLY on the fleet pool at construction — ``remote()`` fires the
    request like the plain handle path does; ``result()`` just waits —
    so submit-then-collect clients overlap and the engines see the real
    offered load."""

    _pool = None
    _pool_lock = threading.Lock()

    @classmethod
    def _ensure_pool(cls):
        from concurrent.futures import ThreadPoolExecutor
        with cls._pool_lock:
            if cls._pool is None:
                cls._pool = ThreadPoolExecutor(
                    max_workers=256, thread_name_prefix="raytpu-fleet")
        return cls._pool

    def __init__(self, fleet: Fleet, args, kwargs, model, priority):
        self._fleet = fleet
        self._args = args
        self._kwargs = kwargs
        self._model = model
        self._priority = priority
        self._fut = self._ensure_pool().submit(self._run)

    def result(self, timeout: Optional[float] = None):
        return self._fut.result(timeout)

    def _run(self):
        fleet = self._fleet
        state = fleet.state
        t0 = time.perf_counter()
        exclude: list = []
        attempts = fleet.cfg.max_resume_attempts \
            if fleet.cfg.retry_on_replica_failure else 0
        try:
            for attempt in range(attempts + 1):
                prefer = (fleet.prefix.route_hint(self._args)
                          if fleet.prefix is not None else None)
                replica = fleet.router.assign(self._model,
                                              exclude=tuple(exclude),
                                              prefer=prefer)
                fleet.note("route", replica=replica.tag,
                           model=self._model, attempt=attempt,
                           priority=self._priority)
                fleet._chaos("serve_route", replica=replica,
                             model=self._model, attempt=attempt)
                try:
                    out = fleet._call(replica, self._args, self._kwargs)
                except BaseException as e:
                    fleet.router.release(replica)
                    if not _is_replica_death(e, replica):
                        raise
                    # replica died before/while handling: mark, re-route
                    fleet.router.mark_dead(replica)
                    if fleet.prefix is not None:
                        fleet.prefix.invalidate_holder(replica.tag)
                    exclude.append(replica.tag)
                    if attempt >= attempts:
                        raise
                    kind = _resume_kind(e, replica)
                    fleet._count(kind)
                    fleet.note("resume", from_replica=replica.tag,
                               resume_kind=kind, attempt=attempt + 1)
                    continue
                if hasattr(out, "__next__"):
                    # stream: the wrapper owns release + resume +
                    # completion accounting from here on.  _FleetStream
                    # guards the closed-before-first-next() case — a
                    # closed UNSTARTED generator never runs its body,
                    # so the generator's own finally cannot be the only
                    # holder of the release
                    gen = fleet_stream(fleet, out, replica, self._args,
                                       self._kwargs, self._model,
                                       exclude, t0, state)

                    def never_started(fleet=fleet, out=out,
                                      replica=replica):
                        try:
                            out.close()   # cancel the engine request
                        except Exception:
                            pass
                        fleet.router.release(replica)
                        fleet._count("cancelled")
                    return _FleetStream(gen, never_started)
                fleet.router.release(replica)
                if fleet.prefix is not None:
                    # advertise what this replica's engines published to
                    # their local tries while serving (best-effort)
                    fleet.prefix.publish_from(replica)
                self._account(False, t0, state)
                return out
            raise ReplicaDeadError(      # pragma: no cover (loop exits)
                "no attempt succeeded")
        except BaseException:
            self._account(True, t0, state)
            raise

    def _account(self, error: bool, t0: float, state) -> None:
        self._fleet._count("errored" if error else "completed")
        if state is not None:
            try:
                state.record_request(time.perf_counter() - t0, error)
            except Exception:
                pass


class _FleetStream:
    """Iterator shim over the fleet_stream generator.  Its single job:
    a consumer that abandons the stream BEFORE the first ``next()``
    (client disconnect during response-start) closes an UNSTARTED
    generator — whose body, including the finally that releases the
    replica and cancels the engine request, never runs.  The shim
    tracks whether iteration started and runs that cleanup itself."""

    def __init__(self, gen, on_never_started):
        self._gen = gen
        self._on_never_started = on_never_started
        self._started = False
        self._closed = False

    def __iter__(self):
        return self

    def __next__(self):
        self._started = True
        return next(self._gen)

    def close(self):
        if self._closed:
            return
        self._closed = True
        started = self._started
        self._gen.close()
        if not started:
            self._on_never_started()

    def __del__(self):   # belt-and-braces: dropped without close()
        try:
            self.close()
        except Exception:
            pass


def fleet_stream(fleet: Fleet, gen: Iterator, replica, args, kwargs,
                 model, exclude: list, t0: float, state) -> Iterator:
    """Resume-capable stream wrapper.  Yields the inner chunks; when
    the serving replica dies mid-stream (EngineStoppedError out of the
    generator) the request is re-routed and REPLAYED — deterministic
    generation means the retry produces the same tokens, and chunks
    whose ``index`` precedes what was already delivered are skipped, so
    the consumer sees one seamless stream."""
    emitted = 0          # token chunks already delivered downstream
    attempts_left = (fleet.cfg.max_resume_attempts
                     if fleet.cfg.retry_on_replica_failure else 0)
    held = replica       # the replica whose ongoing count we hold
    finished = False
    try:
        while True:
            try:
                for chunk in gen:
                    if isinstance(chunk, dict):
                        idx = chunk.get("index")
                        if idx is not None and idx < emitted:
                            # resume replay: already sent — counted, so
                            # the replay COST of every resume path is a
                            # visible number, not free-looking work
                            fleet._count("replayed_tokens")
                            continue
                    fleet._chaos("serve_stream", replica=held,
                                 index=emitted)
                    yield chunk
                    if isinstance(chunk, dict) and "token" in chunk:
                        emitted += 1
                finished = True
                fleet._count("completed")
                if fleet.prefix is not None and held is not None:
                    fleet.prefix.publish_from(held)
                if state is not None:
                    state.record_request(time.perf_counter() - t0, False)
                return
            except BaseException as e:
                if held is None or not _is_replica_death(e, held):
                    raise
                dead_tag = held.tag
                kind = _resume_kind(e, held)
                fleet.router.mark_dead(held)
                if fleet.prefix is not None:
                    fleet.prefix.invalidate_holder(dead_tag)
                fleet.router.release(held)
                held = None
                exclude.append(dead_tag)
                while True:
                    if attempts_left <= 0:
                        raise
                    attempts_left -= 1
                    fleet._count(kind)
                    fleet.note("resume", from_replica=dead_tag,
                               resume_kind=kind, mid_stream=True,
                               emitted=emitted)
                    # re-route (NoReplicaError here fails the request
                    # promptly — a clean error, never a hang), replay
                    held = fleet.router.assign(model,
                                               exclude=tuple(exclude))
                    fleet.note("route", replica=held.tag, model=model,
                               resumed_at=emitted)
                    try:
                        out = fleet._call(held, args, kwargs)
                        break
                    except BaseException as e2:
                        # the REPLAY target may be dead too (cascading
                        # chaos): burn another attempt on the next
                        # replica instead of failing with spares left
                        if not _is_replica_death(e2, held):
                            raise
                        dead_tag = held.tag
                        kind = _resume_kind(e2, held)
                        fleet.router.mark_dead(held)
                        if fleet.prefix is not None:
                            fleet.prefix.invalidate_holder(dead_tag)
                        fleet.router.release(held)
                        held = None
                        exclude.append(dead_tag)
                if not hasattr(out, "__next__"):
                    raise ReplicaDeadError(
                        "resume produced a non-stream result")
                gen = out
    except BaseException as e:
        if not finished:
            if isinstance(e, GeneratorExit):
                # consumer abandonment (client disconnect), not a
                # server fault: account it as cancelled so error-rate
                # metrics don't rise on hung-up clients
                fleet._count("cancelled")
            else:
                fleet._count("errored")
                if state is not None:
                    try:
                        state.record_request(time.perf_counter() - t0,
                                             True)
                    except Exception:
                        pass
        raise
    finally:
        if held is not None:
            fleet.router.release(held)
        close = getattr(gen, "close", None)
        if close is not None:
            close()     # propagate consumer abandonment to the engine
