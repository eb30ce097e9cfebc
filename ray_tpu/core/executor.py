"""Task/actor execution engine.

The analogue of the reference's executor half of CoreWorker (reference:
src/ray/core_worker/core_worker.cc:2528 task_execution_callback →
python/ray/_raylet.pyx:701 execute_task): fetch the function by id,
resolve arguments, run user code, store returns (inline vs shm by size),
report completion.  Used by worker processes (ray_tpu.core.worker) and by
the driver's in-process TPU executor thread (single-host fast path — the
driver keeps jax device ownership, SURVEY.md §7 design delta 1).
"""

from __future__ import annotations

import contextlib
import inspect
import queue
import threading
import time
import traceback
from typing import Any, Optional

import cloudpickle

from ray_tpu.core.client import NodeClient, TaskError
from ray_tpu.core.ids import ActorID, ObjectID, TaskID
from ray_tpu.core.object_ref import ObjectRef, ObjectRefGenerator
from ray_tpu.core.serialization import SerializedObject, get_context


# reusable span stand-in for the no-tracing hot path (nullcontext is
# stateless, so one instance serves every task)
_NULL_SPAN = contextlib.nullcontext()


def _task_span(name: str, spec: dict):
    from ray_tpu.util.tracing import span, tracing_enabled
    if not tracing_enabled():
        return _NULL_SPAN
    return span(name, kind="server", parent=spec.get("trace_ctx"))


class _ArgSlot:
    """Marker for a top-level ObjectRef argument resolved before execution."""

    __slots__ = ("index",)

    def __init__(self, index: int):
        self.index = index


def make_message_queue() -> "queue.SimpleQueue":
    """Create the executor inbox BEFORE connecting the client, so pushes
    that arrive during registration are never dropped."""
    return queue.SimpleQueue()


def queue_push_handler(q: "queue.SimpleQueue",
                       client_cell: Optional[dict] = None):
    """Route pushes into the executor inbox.  With ``client_cell``
    (filled with {"client": NodeClient} after connect), "profile"
    requests are served straight off the RECEIVE thread — a worker
    busy inside a long task is exactly the one worth profiling, and
    its inbox won't drain until the task ends."""
    def push(msg: dict) -> None:
        if (msg.get("t") == "profile" and client_cell
                and client_cell.get("client") is not None):
            _serve_profile(client_cell["client"], msg)
            return
        q.put(msg)
    return push


def _serve_profile(client, msg: dict) -> None:
    def run():
        from ray_tpu.util.profiling import sample_folded
        try:
            folded = sample_folded(
                duration=float(msg.get("duration", 2.0)),
                hz=float(msg.get("hz", 99.0)))
            client.send({"t": "profile_result",
                         "prof_id": msg["prof_id"], "folded": folded})
        except Exception as e:
            client.send({"t": "profile_result",
                         "prof_id": msg["prof_id"], "error": str(e)})
    threading.Thread(target=run, daemon=True,
                     name="raytpu-sampler").start()


class _ActorAsyncState:
    """Long-lived event loop for ONE async actor: every in-flight call
    runs as a coroutine on this loop, so calls interleave at awaits and
    share asyncio primitives (reference: fiber-based async actors,
    core_worker/transport/fiber.h — vs. a fresh asyncio.run per call,
    which isolates each call on its own loop)."""

    def __init__(self, name: str = "raytpu-actor-loop"):
        import asyncio
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self._run, daemon=True,
                                       name=name)
        self.thread.start()
        self._sems: dict[str, Any] = {}   # concurrency group -> Semaphore
        self._sems_lock = threading.Lock()

    def _run(self) -> None:
        import asyncio
        asyncio.set_event_loop(self.loop)
        self.loop.run_forever()

    def group_sem(self, group: str, limit: int):
        import asyncio
        with self._sems_lock:
            sem = self._sems.get(group)
            if sem is None:
                sem = self._sems[group] = asyncio.Semaphore(limit)
            return sem

    def stop(self) -> None:
        self.loop.call_soon_threadsafe(self.loop.stop)


class Executor:
    def __init__(self, client: NodeClient,
                 msg_queue: Optional["queue.SimpleQueue"] = None,
                 threaded_actors: bool = False):
        self.client = client
        self._functions: dict[str, Any] = {}
        self._actors: dict[bytes, Any] = {}
        self._actor_envs: dict[bytes, dict] = {}
        self._actor_lock = threading.Lock()
        # async-actor loops + concurrency-group state (reference:
        # concurrency_group_manager.cc named groups with own executors)
        self._actor_loops: dict[bytes, _ActorAsyncState] = {}
        self._actor_groups: dict[bytes, dict] = {}     # aid -> {name: limit}
        self._sync_sems: dict[tuple, Any] = {}         # (aid, group) -> sem
        self._serde = get_context()
        self._queue = msg_queue if msg_queue is not None else queue.SimpleQueue()
        self._shutdown = threading.Event()
        # threaded_actors: dedicated CPU workers honor max_concurrency>1
        # by running each dispatched actor call on its own thread.  The
        # SHARED in-process TPU executor must stay single-threaded — all
        # TPU actors and tasks share the driver's jax device, and
        # concurrent dispatch from multiple threads would break the
        # driver-owns-device invariant.
        self._threaded_actors = threaded_actors

    # -- message pump ------------------------------------------------------

    def push_handler(self, msg: dict) -> None:
        """Called on the client's receive thread."""
        self._queue.put(msg)

    def run_loop(self) -> None:
        """Blocking execution loop (reference:
        CoreWorkerProcess::RunTaskExecutionLoop, core_worker_process.h:100)."""
        while not self._shutdown.is_set():
            msg = self._queue.get()
            t = msg.get("t")
            if t in ("stop", "shutdown", "exit"):
                self._shutdown.set()
                break
            if t == "execute":
                self.execute_task(msg["spec"])
            elif t == "execute_actor":
                # the node dispatches up to the actor's max_concurrency
                # in-flight calls; a dedicated worker honors that with
                # one thread per dispatched call (no pool cap: a bounded
                # pool could deadlock waiter-pattern actors whose
                # unblocking call queues behind blocked threads).  With
                # max_concurrency=1 the node sends one call at a time,
                # so ordering is preserved.  Reference: concurrency
                # groups, core_worker task_execution_service
                if self._threaded_actors:
                    threading.Thread(
                        target=self.execute_actor_task,
                        args=(msg["spec"],), daemon=True,
                        name="raytpu-actor-task").start()
                else:
                    self.execute_actor_task(msg["spec"])
            elif t == "create_actor_exec":
                self.create_actor(msg["spec"])
            elif t == "profile":
                # normally served on the receive thread
                # (queue_push_handler); kept here for executors fed by
                # other transports
                _serve_profile(self.client, msg)
            elif t == "destroy_actor":
                with self._actor_lock:
                    aid = msg["actor_id"]
                    self._actors.pop(aid, None)
                    self._actor_envs.pop(aid, None)
                    self._actor_groups.pop(aid, None)
                    self._sync_sems = {k: v for k, v in
                                       self._sync_sems.items()
                                       if k[0] != aid}
                    st = self._actor_loops.pop(aid, None)
                if st is not None:
                    st.stop()

    # -- function store ----------------------------------------------------

    def _get_function(self, function_id: str):
        fn = self._functions.get(function_id)
        if fn is None:
            reply = self.client.request({"t": "fetch_function",
                                         "function_id": function_id})
            fn = cloudpickle.loads(reply["pickled"])
            self._functions[function_id] = fn
        return fn

    # -- argument resolution ----------------------------------------------

    def _load_args(self, spec: dict):
        blob_id = spec.get("arg_blob")
        if blob_id is not None:
            args, kwargs = self.client.get_objects([ObjectID(blob_id)])[0]
        else:
            so = SerializedObject.from_buffer(spec["args"])
            args, kwargs = self._serde.deserialize(so)
        ref_ids = [ObjectID(b) for b in spec.get("arg_ids", [])
                   if b != blob_id]
        if ref_ids:
            values = self.client.get_objects(ref_ids)
            args = [values[a.index] if isinstance(a, _ArgSlot) else a
                    for a in args]
            kwargs = {k: (values[v.index] if isinstance(v, _ArgSlot) else v)
                      for k, v in kwargs.items()}
        return list(args), dict(kwargs)

    # -- return storage ----------------------------------------------------

    def _store_returns(self, spec: dict, result: Any) -> None:
        return_ids = [ObjectID(b) for b in spec["return_ids"]]
        num_returns = spec.get("num_returns", 1)
        # returns are OWNED by the submitter (spec["owner"]), not this
        # executor — its release_refs must be able to reclaim them
        owner = spec.get("owner") or self.client.worker_id
        if num_returns == "dynamic":
            refs = []
            task_id = TaskID(spec["task_id"])
            for i, item in enumerate(result):
                oid = ObjectID.for_task_return(task_id, i + 2)
                self.client.put_object(oid, item, owner=owner)
                refs.append(ObjectRef(oid, owner=owner))
            self.client.put_object(return_ids[0], ObjectRefGenerator(refs),
                                   owner=owner)
            return
        if num_returns == 0:
            return
        if num_returns == 1:
            outs = [result]
        else:
            outs = list(result)
            if len(outs) != num_returns:
                raise ValueError(
                    f"Task declared num_returns={num_returns} but returned "
                    f"{len(outs)} values")
        for oid, val in zip(return_ids, outs):
            self.client.put_object(oid, val, owner=owner)

    def _store_error(self, spec: dict, exc: BaseException, tb: str) -> None:
        err = TaskError(exc, tb) if not isinstance(exc, TaskError) else exc
        for b in spec["return_ids"]:
            try:
                self.client.put_object(ObjectID(b), err, is_error=True)
            except Exception:
                # even the error failed to serialize — store a plain one
                self.client.put_object(
                    ObjectID(b),
                    TaskError(RuntimeError(
                        f"unserializable {type(exc).__name__}: {exc}"), tb),
                    is_error=True)

    # -- execution ---------------------------------------------------------

    def execute_task(self, spec: dict) -> None:
        from ray_tpu.core.runtime import task_context
        from ray_tpu.runtime_env import applied_env
        error = None
        # flight recorder: data-driven — stamp only when the submitter
        # started a lifecycle record (one dict.get when disabled), and
        # ship the stamps back inside task_done for the node to fold in
        fr = spec.get("fr")
        if fr is not None:
            fr.append(("worker_recv", time.monotonic()))
        try:
            fn = self._get_function(spec["function_id"])
            args, kwargs = self._load_args(spec)
            if fr is not None:
                fr.append(("exec_start", time.monotonic()))
            with task_context(TaskID(spec["task_id"])), \
                    applied_env(spec.get("runtime_env"), self.client), \
                    _task_span(f"task::{spec.get('name', '?')}.execute",
                               spec):
                result = fn(*args, **kwargs)
            if fr is not None:
                fr.append(("exec_end", time.monotonic()))
            # one syscall for inline result puts + completion (hot path:
            # per-task overhead, SURVEY hard part 6)
            with self.client.batched_sends():
                self._store_returns(spec, result)
                done = {"t": "task_done", "task_id": spec["task_id"],
                        "error": None}
                if fr is not None:
                    fr.append(("result_store", time.monotonic()))
                    done["fr"] = fr
                self.client.send(done)
            return
        except BaseException as e:  # noqa: BLE001 — report all task errors
            tb = traceback.format_exc()
            error = f"{type(e).__name__}: {e}"
            self._store_error(spec, e, tb)
        done = {"t": "task_done", "task_id": spec["task_id"],
                "error": error}
        if fr is not None:
            done["fr"] = fr
        self.client.send(done)

    def create_actor(self, spec: dict) -> None:
        error = None
        try:
            cls = self._get_function(spec["function_id"])
            args, kwargs = self._load_args(spec)
            from ray_tpu.core.runtime import task_context
            from ray_tpu.runtime_env import applied_env
            env = spec.get("runtime_env")
            if env and self._threaded_actors:
                # dedicated worker: the env spans the actor's LIFETIME
                # (applied once, never popped)
                applied_env(env, self.client).__enter__()
                env = None
            elif env:
                # SHARED executor (in-process TPU): the env must never
                # leak into the driver/other actors — scope it around
                # construction and around every method call instead
                self._actor_envs[spec["actor_id"]] = env
            with task_context(TaskID(spec["task_id"])), \
                    applied_env(env, self.client):
                instance = cls(*args, **kwargs)
            with self._actor_lock:
                self._actors[spec["actor_id"]] = instance
                groups = dict(spec.get("concurrency_groups") or {})
                if groups:
                    # "" = the default group, bounded by max_concurrency
                    groups[""] = int(spec.get("max_concurrency", 1))
                self._actor_groups[spec["actor_id"]] = groups
        except BaseException as e:  # noqa: BLE001
            error = (f"{type(e).__name__}: {e}\n{traceback.format_exc()}")
        self.client.send({"t": "actor_created", "actor_id": spec["actor_id"],
                          "error": error})

    def _actor_loop_state(self, aid: bytes) -> _ActorAsyncState:
        with self._actor_lock:
            st = self._actor_loops.get(aid)
            if st is None:
                st = self._actor_loops[aid] = _ActorAsyncState()
            return st

    def _sync_group_sem(self, aid: bytes, group: str, limit: int):
        with self._actor_lock:
            sem = self._sync_sems.get((aid, group))
            if sem is None:
                sem = self._sync_sems[(aid, group)] = \
                    threading.BoundedSemaphore(limit)
            return sem

    def _group_limit(self, spec: dict) -> Optional[int]:
        groups = self._actor_groups.get(spec["actor_id"]) or {}
        if not groups:
            # no named groups declared: the node's max_concurrency
            # admission cap alone governs
            return None
        # the node raises its dispatch cap to default+sum(groups), so
        # the DEFAULT group ("" key, = max_concurrency) must be enforced
        # here too — otherwise declaring any named group would unbound
        # the default group's concurrency
        group = spec.get("concurrency_group") or ""
        limit = groups.get(group)
        if limit is None:
            raise ValueError(
                f"Unknown concurrency group {group!r}; declared groups: "
                f"{sorted(g for g in groups if g)}")
        return int(limit)

    def _finish_actor_task(self, spec: dict, result: Any,
                           exc: Optional[BaseException],
                           tb: str = "") -> None:
        fr = spec.get("fr")
        if exc is None:
            try:
                with self.client.batched_sends():
                    self._store_returns(spec, result)
                    done = {"t": "task_done", "task_id": spec["task_id"],
                            "error": None}
                    if fr is not None:
                        fr.append(("result_store", time.monotonic()))
                        done["fr"] = fr
                    self.client.send(done)
                return
            except BaseException as e:  # noqa: BLE001
                exc, tb = e, traceback.format_exc()
        error = f"{type(exc).__name__}: {exc}"
        self._store_error(spec, exc, tb)
        done = {"t": "task_done", "task_id": spec["task_id"],
                "error": error}
        if fr is not None:
            done["fr"] = fr
        self.client.send(done)

    def execute_actor_task(self, spec: dict) -> None:
        from ray_tpu.core.runtime import task_context
        from ray_tpu.runtime_env import applied_env
        fr = spec.get("fr")
        if fr is not None:
            fr.append(("worker_recv", time.monotonic()))
        try:
            instance = self._actors.get(spec["actor_id"])
            if instance is None:
                raise RuntimeError("actor instance not found in this worker")
            method = getattr(instance, spec["method"])
            args, kwargs = self._load_args(spec)
            limit = self._group_limit(spec)
            if fr is not None:
                fr.append(("exec_start", time.monotonic()))
            if inspect.iscoroutinefunction(method) or \
                    inspect.iscoroutinefunction(
                        getattr(method, "__func__", method)):
                self._run_async_actor_task(spec, method, args, kwargs, limit)
                return
            sem = (self._sync_group_sem(spec["actor_id"],
                                        spec.get("concurrency_group") or "",
                                        limit)
                   if limit is not None else None)
            with task_context(TaskID(spec["task_id"])), \
                    applied_env(self._actor_envs.get(spec["actor_id"]),
                                self.client), \
                    _task_span(f"actor::{spec.get('name', '?')}.execute",
                               spec):
                if sem is not None:
                    with sem:
                        result = method(*args, **kwargs)
                else:
                    result = method(*args, **kwargs)
                if inspect.iscoroutine(result):
                    # async value from a non-coroutine callable (rare):
                    # still run it on the shared actor loop
                    self._run_async_actor_task(
                        spec, lambda: result, (), {}, limit)
                    return
        except BaseException as e:  # noqa: BLE001
            self._finish_actor_task(spec, None, e, traceback.format_exc())
            return
        if fr is not None:
            fr.append(("exec_end", time.monotonic()))
        self._finish_actor_task(spec, result, None)

    def _run_async_actor_task(self, spec: dict, method, args, kwargs,
                              limit: Optional[int]) -> None:
        """Schedule the call on the actor's long-lived loop and return —
        completion is reported from the loop.  All in-flight calls
        interleave at awaits and share asyncio primitives."""
        import asyncio
        from ray_tpu.core.runtime import task_context
        from ray_tpu.runtime_env import applied_env
        st = self._actor_loop_state(spec["actor_id"])

        async def runner():
            from ray_tpu.util.tracing import span
            with task_context(TaskID(spec["task_id"])), \
                    applied_env(self._actor_envs.get(spec["actor_id"]),
                                self.client), \
                    span(f"actor::{spec.get('name', '?')}.execute",
                         kind="server", parent=spec.get("trace_ctx")):
                if limit is not None:
                    sem = st.group_sem(
                        spec.get("concurrency_group") or "", limit)
                    async with sem:
                        return await method(*args, **kwargs)
                return await method(*args, **kwargs)

        def schedule():
            task = st.loop.create_task(runner())

            def done(t):
                fr = spec.get("fr")
                if fr is not None:
                    # async path returns before execute_actor_task's
                    # sync-side exec_end stamp — stamp here instead so
                    # coroutine runtime isn't folded into result_store
                    fr.append(("exec_end", time.monotonic()))
                exc = t.exception()
                if exc is not None:
                    tb = "".join(traceback.format_exception(
                        type(exc), exc, exc.__traceback__))
                    self._finish_actor_task(spec, None, exc, tb)
                else:
                    self._finish_actor_task(spec, t.result(), None)
            task.add_done_callback(done)

        st.loop.call_soon_threadsafe(schedule)

    def get_actor_instance(self, actor_id: bytes) -> Optional[Any]:
        return self._actors.get(actor_id)
