"""DeploymentHandle: the client side of a deployment.

Reference capability: serve handles (python/ray/serve/handle.py
RayServeHandle.remote → router → replica).  ``handle.remote(...)``
returns a future-like; ``.result()`` blocks.  Actor replicas return
ObjectRefs (query runs in the replica process); in-process replicas run
on a worker thread pool so concurrent queries still overlap.
"""

from __future__ import annotations

import threading
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Optional

from ray_tpu.serve.controller import DeploymentState, ReplicaHandle
from ray_tpu.util import tracing


def _is_timeout(e: BaseException) -> bool:
    from concurrent.futures import TimeoutError as FutTimeout
    try:
        from ray_tpu.core.client import GetTimeoutError
    except ImportError:  # pragma: no cover
        GetTimeoutError = ()
    return isinstance(e, (FutTimeout, TimeoutError, GetTimeoutError))


class ServeResponse:
    """Future-like wrapper (reference: DeploymentResponse)."""

    def __init__(self, resolve, cancel_release):
        self._resolve = resolve
        self._release = cancel_release
        self._done = False
        self._value = None
        self._error: Optional[BaseException] = None

    def result(self, timeout: Optional[float] = None):
        if not self._done:
            try:
                self._value = self._resolve(timeout)
            except BaseException as e:
                if _is_timeout(e):
                    # request is still executing on the replica — keep
                    # its concurrency slot held and let the caller retry
                    raise
                self._error = e
            self._release()
            self._done = True
        if self._error is not None:
            raise self._error
        return self._value


class DeploymentHandle:
    _pool: Optional[ThreadPoolExecutor] = None
    _pool_lock = threading.Lock()

    def __init__(self, state: DeploymentState, method: str = "__call__"):
        self._state = state
        self._method = method

    @property
    def deployment_name(self) -> str:
        return self._state.deployment.name

    def options(self, *, method_name: str) -> "DeploymentHandle":
        return DeploymentHandle(self._state, method_name)

    def __getattr__(self, name: str) -> "DeploymentHandle":
        if name.startswith("_"):
            raise AttributeError(name)
        return DeploymentHandle(self._state, name)

    @classmethod
    def _ensure_pool(cls) -> ThreadPoolExecutor:
        with cls._pool_lock:
            if cls._pool is None:
                cls._pool = ThreadPoolExecutor(max_workers=32)
        return cls._pool

    def _current_state(self) -> DeploymentState:
        """Re-resolve by name: a redeploy replaces the DeploymentState,
        and a handle bound to the dead one would spin on zero replicas
        forever."""
        try:
            from ray_tpu import serve as _serve
            ctrl = _serve._controller
            if ctrl is not None:
                st = ctrl.deployments.get(self._state.deployment.name)
                if st is not None and st is not self._state:
                    self._state = st
        except Exception:
            pass
        return self._state

    def remote(self, *args, **kwargs):
        import time as _time
        state, method = self._current_state(), self._method
        fleet = getattr(state, "fleet", None)
        if fleet is not None and method == "__call__":
            # fleet-enabled deployment: admission (may raise ShedError
            # — backpressure is synchronous by design) + occupancy
            # routing + resume-on-replica-death, instead of the
            # round-robin assign below
            return fleet.remote(args, kwargs)
        replica = state.assign_replica()
        t0 = _time.perf_counter()
        if replica.is_actor:
            ref = replica.impl.handle_request.remote(method, args, kwargs)

            def resolve_inner(timeout):
                import ray_tpu
                # timeout=None means block until done (matches the
                # in-process Future path) — do not invent a deadline
                return ray_tpu.get(ref, timeout=timeout)
        else:
            # the pool's thread does not carry contextvars: the
            # caller's span context (the serve front's) goes explicitly
            fut: Future = self._ensure_pool().submit(
                tracing.call_in_context, tracing.inject_context(),
                replica.impl.handle_request, method, args, kwargs)

            def resolve_inner(timeout):
                return fut.result(timeout)

        def resolve(timeout):
            try:
                out = resolve_inner(timeout)
            except BaseException as e:
                if not _is_timeout(e):   # timeouts retry; don't count
                    state.record_request(_time.perf_counter() - t0, True)
                raise
            state.record_request(_time.perf_counter() - t0, False)
            return out

        return ServeResponse(resolve, lambda: state.release(replica))

    def __reduce__(self):
        # a handle crossing a process boundary (deployment-graph child
        # injected into a replica's constructor) becomes a
        # RemoteDeploymentHandle that routes via the KV-mirrored replica
        # membership — the controller object cannot travel
        return (RemoteDeploymentHandle,
                (self.deployment_name, self._method))


class RemoteDeploymentHandle:
    """Process-portable deployment handle (the router half the reference
    ships inside every replica: _private/router.py + long-poll replica
    membership).  Replica actor handles come from the KV mirror the
    controller maintains; the snapshot refreshes on a short TTL or on
    call failure, so scaling/restarts propagate without a central hop
    per request."""

    REFRESH_S = 1.0

    def __init__(self, name: str, method: str = "__call__"):
        self._name = name
        self._method = method
        self._replicas: list = []
        self._maxq = 8
        self._fetched_at = 0.0
        self._rr = 0
        self._ongoing: dict[int, int] = {}   # replica index -> in-flight
        self._lock = threading.Lock()

    def options(self, *, method_name: str) -> "RemoteDeploymentHandle":
        return RemoteDeploymentHandle(self._name, method_name)

    def __getattr__(self, name: str) -> "RemoteDeploymentHandle":
        if name.startswith("_"):
            raise AttributeError(name)
        return RemoteDeploymentHandle(self._name, name)

    def __reduce__(self):
        return (RemoteDeploymentHandle, (self._name, self._method))

    def _refresh(self, force: bool = False) -> None:
        import time as _time
        now = _time.monotonic()
        with self._lock:
            if (not force and self._replicas
                    and now - self._fetched_at < self.REFRESH_S):
                return
        import cloudpickle
        import ray_tpu
        raw = ray_tpu.get_runtime().client.kv_get(
            f"serve:replicas:{self._name}".encode())
        if raw is None:
            raise RuntimeError(
                f"no replica membership for deployment {self._name!r} "
                "(not deployed with actor replicas?)")
        snap = cloudpickle.loads(raw)
        with self._lock:
            self._replicas = snap["replicas"]
            self._maxq = snap["max_concurrent_queries"]
            self._fetched_at = now
            # counts are keyed by actor id, so a refresh with unchanged
            # membership preserves in-flight bookkeeping and a reorder
            # can't misattribute load; drop counts for departed replicas
            live = {self._replica_key(r) for r in self._replicas}
            self._ongoing = {k: v for k, v in self._ongoing.items()
                             if k in live}

    @staticmethod
    def _replica_key(replica) -> str:
        try:
            return replica._actor_id.hex()
        except AttributeError:
            return str(id(replica))

    def _assign(self, timeout: float = 60.0):
        """Round-robin with per-handle max_concurrent_queries
        backpressure — the remote path must honor the same concurrency
        bound the local router enforces (router.py:221)."""
        import time as _time
        deadline = _time.monotonic() + timeout
        while True:
            self._refresh()
            with self._lock:
                n = len(self._replicas)
                if n == 0:
                    raise RuntimeError(f"deployment {self._name!r} has "
                                       "no actor replicas")
                for _ in range(n):
                    self._rr += 1
                    r = self._replicas[self._rr % n]
                    key = self._replica_key(r)
                    if self._ongoing.get(key, 0) < self._maxq:
                        self._ongoing[key] = self._ongoing.get(key, 0) + 1
                        return key, r
            if _time.monotonic() > deadline:
                raise RuntimeError(
                    f"deployment {self._name!r}: all replicas saturated "
                    f"for {timeout}s")
            _time.sleep(0.001)

    def _release(self, key: str) -> None:
        with self._lock:
            if self._ongoing.get(key, 0) > 0:
                self._ongoing[key] -= 1

    def remote(self, *args, **kwargs) -> ServeResponse:
        key, replica = self._assign()
        ref = replica.handle_request.remote(self._method, args, kwargs)

        def resolve(timeout):
            import ray_tpu
            try:
                return ray_tpu.get(ref, timeout=timeout)
            except Exception:
                # stale membership (replica died): refresh for the next
                # call, but never let the refresh mask the real failure
                try:
                    self._refresh(force=True)
                except Exception:
                    pass
                raise
        return ServeResponse(resolve, lambda: self._release(key))
