"""Process-wide runtime: submission side of the core API.

The analogue of the reference CoreWorker's submission half + worker.py
globals (reference: python/ray/_private/worker.py global_worker,
core_worker.cc SubmitTask:1815, CreateActor, SubmitActorTask) — holds the
node-client connection, generates deterministic task/object ids, exports
functions once, and owns the driver-side helper threads (node service,
in-process TPU executor, log monitor).
"""

from __future__ import annotations

import atexit
import concurrent.futures
import contextlib
import hashlib
import os
import threading
import time
import contextvars
import uuid
from typing import Any, Optional, Sequence

import cloudpickle

from ray_tpu._config import RayTpuConfig, set_config
from ray_tpu.core import flight_recorder as _fr
from ray_tpu.core.client import NodeClient, TaskError  # noqa: F401
from ray_tpu.core.executor import Executor, _ArgSlot
from ray_tpu.core.ids import (ActorID, JobID, ObjectID, TaskID, _Counter)
from ray_tpu.core.object_ref import ObjectRef, ObjectRefGenerator
from ray_tpu.core.serialization import get_context

# --------------------------------------------------------------------------
# per-task execution context.  Contextvars, not threading.local: async
# actors interleave many in-flight calls as coroutines on ONE event-loop
# thread (reference: fiber.h async actors), and each asyncio.Task carries
# its own Context copy — thread-locals would make interleaved calls stomp
# each other's task ids and put counters.  Plain threads still get
# per-thread isolation (each thread has its own context).


class _TaskContext:
    task_id = contextvars.ContextVar("raytpu_task_id", default=None)
    put_counter = contextvars.ContextVar("raytpu_put_counter", default=0)
    task_counter = contextvars.ContextVar("raytpu_task_counter", default=0)


_ctx = _TaskContext()


@contextlib.contextmanager
def task_context(task_id: TaskID):
    t1 = _TaskContext.task_id.set(task_id)
    t2 = _TaskContext.put_counter.set(0)
    t3 = _TaskContext.task_counter.set(0)
    try:
        yield
    finally:
        _TaskContext.task_id.reset(t1)
        _TaskContext.put_counter.reset(t2)
        _TaskContext.task_counter.reset(t3)


def current_task_id() -> TaskID:
    tid = _TaskContext.task_id.get()
    if tid is None:
        # thread outside any task: derive a stable per-thread driver task id
        tid = TaskID(hashlib.sha1(
            f"thread-{threading.get_ident()}-{uuid.uuid4().hex}".encode()
        ).digest()[:20] + JobID.from_int(0).binary())
        _TaskContext.task_id.set(tid)
    return tid


# --------------------------------------------------------------------------


class Runtime:
    def __init__(self, client: NodeClient, mode: str,
                 executor: Optional[Executor] = None,
                 namespace: str = "default"):
        self.client = client
        self.mode = mode  # "driver" | "worker"
        self.executor = executor
        self.namespace = namespace or "default"
        self.job_id = JobID.from_int(1)
        self._exported: set[str] = set()
        self._export_lock = threading.Lock()
        self._actor_counter = _Counter()
        self._serde = get_context()
        # prepared runtime envs memoized per canonical input: re-zipping
        # / re-checking the KV on EVERY submission would dominate the
        # task hot path for working_dir users
        self._env_cache: dict[str, tuple] = {}
        self._env_cache_lock = threading.Lock()
        self._futures_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=4, thread_name_prefix="raytpu-future")
        # distributed refcount: when this process's last ref to an object
        # dies, tell the node so the owner's storage can be reclaimed
        # (reference: reference_count.h local-count half)
        from ray_tpu.core.object_ref import get_tracker
        get_tracker().set_sink(self._release_refs)
        # driver-owned helpers (populated by init())
        self.node_service = None
        self.tpu_executor_client: Optional[NodeClient] = None
        self.tpu_executor_thread: Optional[threading.Thread] = None
        self.session_dir: str = ""

    # ---------------------------------------------------------- functions

    def export_function(self, fn: Any) -> str:
        pickled = cloudpickle.dumps(fn)
        fid = hashlib.sha1(pickled).hexdigest()
        with self._export_lock:
            if fid not in self._exported:
                self.client.request({"t": "register_function",
                                     "function_id": fid, "pickled": pickled})
                self._exported.add(fid)
        return fid

    # ---------------------------------------------------------- task spec

    def _prepare_args(self, args: Sequence, kwargs: dict, spec: dict) -> None:
        """Top-level ObjectRefs become resolved-by-executor slots; nested
        refs travel as refs (reference: LocalDependencyResolver,
        transport/dependency_resolver.cc)."""
        ref_ids: list[bytes] = []

        def slot(v):
            if isinstance(v, ObjectRef):
                ref_ids.append(v.binary())
                return _ArgSlot(len(ref_ids) - 1)
            return v

        new_args = [slot(a) for a in args]
        new_kwargs = {k: slot(v) for k, v in kwargs.items()}
        so = self._serde.serialize((new_args, new_kwargs))
        data = so.to_bytes()
        inline_limit = self.client.config_dict["max_direct_call_object_size"]
        if len(data) > inline_limit:
            blob_id = ObjectID.for_put(current_task_id(),
                                       self._next_put_index())
            self.client.put_serialized(blob_id, so)
            spec["arg_blob"] = blob_id.binary()
            spec["args"] = b""
            ref_ids.append(blob_id.binary())
        else:
            spec["args"] = data
        spec["arg_ids"] = ref_ids

    def _prepare_env(self, runtime_env: dict) -> tuple:
        """validate + prepare + hash, memoized on the raw input (same
        env dict on every .remote() must not re-zip working_dir)."""
        import json as _json

        from ray_tpu.runtime_env import env_hash, prepare, validate
        try:
            key = _json.dumps(runtime_env, sort_keys=True, default=str)
        except TypeError:
            key = repr(sorted(runtime_env.items()))
        with self._env_cache_lock:
            hit = self._env_cache.get(key)
        if hit is not None:
            return hit
        prepared = prepare(validate(dict(runtime_env)), self.client)
        out = (prepared, env_hash(prepared))
        with self._env_cache_lock:
            self._env_cache[key] = out
        return out

    def _next_put_index(self) -> int:
        n = _TaskContext.put_counter.get() + 1
        _TaskContext.put_counter.set(n)
        return n

    def _next_task_id(self) -> TaskID:
        n = _TaskContext.task_counter.get() + 1
        _TaskContext.task_counter.set(n)
        return TaskID.of(current_task_id(), n)

    # ------------------------------------------------------------- submit

    def make_task_template(self, function_id: str, *,
                           name: str = "", num_returns=1,
                           resources: Optional[dict] = None,
                           num_tpus: float = 0, max_retries: int = 0,
                           placement_group=None, runtime_env=None) -> dict:
        """Static spec fields resolved ONCE per RemoteFunction: env
        preparation/hashing, resource map, descriptor — the per-call
        path only stamps ids and args (reference: the task spec
        builder caches the serialized function descriptor,
        _raylet.pyx TaskSpecification reuse)."""
        env_h = ""
        if runtime_env:
            runtime_env, env_h = self._prepare_env(runtime_env)
        return {
            "task_id": b"",
            "kind": "task",
            "name": name,
            "function_id": function_id,
            "num_returns": num_returns,
            "return_ids": (),
            "resources": resources or {},
            "num_tpus": num_tpus,
            "max_retries": max_retries,
            "placement_group": placement_group,
            "runtime_env": runtime_env,
            "env_hash": env_h,
            # the SUBMITTER owns the returns (reference: ownership model,
            # core_worker.h — the caller, not the executor, owns results)
            "owner": self.client.worker_id,
        }

    def submit_task_template(self, template: dict, args, kwargs):
        task_id = self._next_task_id()
        num_returns = template["num_returns"]
        n_ret = 1 if num_returns == "dynamic" else max(num_returns, 0)
        returns = [ObjectID.for_task_return(task_id, i + 1)
                   for i in range(max(n_ret, 1))]
        spec = dict(template)
        spec["task_id"] = task_id.binary()
        spec["return_ids"] = [o.binary() for o in returns]
        if _fr._active is not None:
            # flight recorder: open the lifecycle record; "encode" below
            # isolates client-side arg serialization from the wire hop
            _fr._active.start(spec)
        from ray_tpu.util.tracing import tracing_enabled
        if tracing_enabled():
            from ray_tpu.util.tracing import span
            # the submit span is the PARENT of the worker's execute span
            # (reference: tracing_helper injects the client span's
            # context), so its context — not the ambient one — goes
            # into the spec
            with span(f"task::{spec['name']}.remote", kind="client",
                      task_id=task_id.hex()) as sp:
                if sp:
                    spec["trace_ctx"] = sp.context()
                self._prepare_args(args, kwargs, spec)
                if _fr._active is not None:
                    _fr._active.stamp(spec, "encode")
                self.client.send_soon({"t": "submit_task", "spec": spec})
        else:
            self._prepare_args(args, kwargs, spec)
            if _fr._active is not None:
                _fr._active.stamp(spec, "encode")
            self.client.send_soon({"t": "submit_task", "spec": spec})
        owner = self.client.worker_id
        refs = [ObjectRef(o, owner=owner) for o in returns]
        if num_returns == "dynamic" or num_returns == 1:
            return refs[0]
        if num_returns == 0:
            return None
        return refs

    def submit_task(self, function_id: str, args, kwargs, *,
                    name: str = "", num_returns=1,
                    resources: Optional[dict] = None,
                    num_tpus: float = 0, max_retries: int = 0,
                    placement_group=None, runtime_env=None):
        template = self.make_task_template(
            function_id, name=name, num_returns=num_returns,
            resources=resources, num_tpus=num_tpus, max_retries=max_retries,
            placement_group=placement_group, runtime_env=runtime_env)
        return self.submit_task_template(template, args, kwargs)

    # ------------------------------------------------------------- actors

    def create_actor(self, function_id: str, args, kwargs, *,
                     class_name: str, methods: list[str],
                     name: str = "", namespace: str = "",
                     get_if_exists: bool = False,
                     resources: Optional[dict] = None, num_tpus: float = 0,
                     max_restarts: int = 0, max_concurrency: int = 1,
                     concurrency_groups: Optional[dict] = None,
                     placement_group=None, runtime_env=None) -> ActorID:
        if runtime_env:
            runtime_env, _ = self._prepare_env(runtime_env)
        actor_id = ActorID.of(self.job_id, current_task_id(),
                              self._actor_counter.next())
        task_id = self._next_task_id()
        spec = {
            "task_id": task_id.binary(),
            "kind": "actor_create",
            "actor_id": actor_id.binary(),
            "name": name,
            "namespace": namespace,
            "get_if_exists": get_if_exists,
            "class_name": class_name,
            "methods": methods,
            "function_id": function_id,
            "num_returns": 0,
            "return_ids": [],
            "resources": resources or {},
            "num_tpus": num_tpus,
            "max_restarts": max_restarts,
            "max_concurrency": max_concurrency,
            "concurrency_groups": dict(concurrency_groups or {}),
            "placement_group": placement_group,
            "runtime_env": runtime_env,
        }
        self._prepare_args(args, kwargs, spec)
        reply = self.client.request({"t": "create_actor", "spec": spec})
        return ActorID(reply["actor_id"])

    def submit_actor_task(self, actor_id: ActorID, caller_nonce: bytes,
                          seq: int, method: str,
                          args, kwargs, *, num_returns=1, name: str = "",
                          concurrency_group: str = ""):
        task_id = TaskID.for_actor_task(actor_id, caller_nonce, seq)
        n_ret = 1 if num_returns == "dynamic" else max(num_returns, 0)
        return_ids = [ObjectID.for_task_return(task_id, i + 1)
                      for i in range(max(n_ret, 1))]
        spec = {
            "task_id": task_id.binary(),
            "kind": "actor_task",
            "actor_id": actor_id.binary(),
            "method": method,
            "name": name or method,
            "seq": seq,
            "num_returns": num_returns,
            "return_ids": [o.binary() for o in return_ids],
            "owner": self.client.worker_id,
        }
        if concurrency_group:
            spec["concurrency_group"] = concurrency_group
        from ray_tpu.util import tracing
        # only while tracing is on: the serve front's span is always on
        tctx = tracing.inject_context() if tracing.active() else None
        if tctx is not None:
            spec["trace_ctx"] = tctx
        if _fr._active is not None:
            _fr._active.start(spec)
        self._prepare_args(args, kwargs, spec)
        if _fr._active is not None:
            _fr._active.stamp(spec, "encode")
        self.client.send_soon({"t": "submit_actor_task", "spec": spec})
        refs = [ObjectRef(o, owner=self.client.worker_id) for o in return_ids]
        if num_returns == "dynamic" or num_returns == 1:
            return refs[0]
        if num_returns == 0:
            return None
        return refs

    def kill_actor(self, actor_id: ActorID, no_restart: bool = True) -> None:
        self.client.request({"t": "kill_actor", "actor_id": actor_id.binary(),
                             "no_restart": no_restart})

    # ------------------------------------------------------------ objects

    def put(self, value: Any) -> ObjectRef:
        oid = ObjectID.for_put(current_task_id(), self._next_put_index())
        # explicit puts keep jax.Array leaves device-resident (HBM
        # objects, core/device_objects.py) — no host bounce until a
        # different process actually asks for the value
        self.client.put_object(oid, value, allow_device=True)
        return ObjectRef(oid, owner=self.client.worker_id)

    def get(self, refs: Sequence[ObjectRef],
            timeout: Optional[float] = None) -> list[Any]:
        if _fr._active is None:
            return self.client.get_objects([r.id for r in refs],
                                           timeout=timeout)
        # flight recorder: the caller-visible tail of the lifecycle
        # (result_store → get return) lands in its own histogram.
        # Success only — a timeout would fold the caller's timeout
        # SETTING into the latency histogram as if it were a roundtrip
        t0 = time.monotonic()
        out = self.client.get_objects([r.id for r in refs],
                                      timeout=timeout)
        rec = _fr._active
        if rec is not None:
            rec.observe("get_roundtrip", time.monotonic() - t0)
        return out

    def wait(self, refs: Sequence[ObjectRef], num_returns: int = 1,
             timeout: Optional[float] = None):
        ready_bins = set(self.client.wait([r.id for r in refs], num_returns,
                                          timeout))
        ready, not_ready = [], []
        for r in refs:
            (ready if r.binary() in ready_bins else not_ready).append(r)
        return ready, not_ready

    def free(self, refs: Sequence[ObjectRef]) -> None:
        self.client.free([r.id for r in refs])

    def as_future(self, ref: ObjectRef) -> concurrent.futures.Future:
        return self._futures_pool.submit(
            lambda: self.client.get_objects([ref.id])[0])

    def _release_refs(self, object_ids: list) -> None:
        if not self.client.closed:
            self.client.send({"t": "release_refs",
                              "object_ids": object_ids})

    # ----------------------------------------------------------- shutdown

    def shutdown(self) -> None:
        from ray_tpu.core.object_ref import get_tracker
        try:
            get_tracker().flush()
        except Exception:
            pass
        get_tracker().set_sink(None)
        try:
            self._futures_pool.shutdown(wait=False)
        except Exception:
            pass
        if self.tpu_executor_client is not None:
            try:
                self.tpu_executor_client.close()
            except Exception:
                pass
        try:
            self.client.close()
        except Exception:
            pass
        if self.node_service is not None:
            self.node_service.stop()


# --------------------------------------------------------------------------
# globals

_runtime: Optional[Runtime] = None
_runtime_lock = threading.Lock()


def get_runtime() -> Runtime:
    if _runtime is None:
        raise RuntimeError("ray_tpu is not initialized — call ray_tpu.init()")
    return _runtime


def is_initialized() -> bool:
    return _runtime is not None


def attach_worker_runtime(client: NodeClient, executor: Executor) -> Runtime:
    global _runtime
    # Adopt the node's resolved config (received at registration) so
    # system_config overrides reach worker-side get_config() readers —
    # the reference distributes _system_config cluster-wide the same way
    # (ray_config.cc:29).  Worker-local RAY_TPU_* env still wins.
    from ray_tpu._config import RayTpuConfig, set_config
    set_config(RayTpuConfig(client.config_dict))
    with _runtime_lock:
        _runtime = Runtime(client, mode="worker", executor=executor)
    return _runtime


def _detect_tpu_chips() -> int:
    """Count local accelerator chips.  This process becomes their owner
    (one process per chip), so a backend that fails to initialize
    raises: answering 0 would silently leave ``num_tpus`` work with
    nowhere to run."""
    import jax
    return sum(1 for d in jax.local_devices() if d.platform != "cpu")


def init(*, num_cpus: Optional[float] = None, num_tpus: Optional[float] = None,
         resources: Optional[dict] = None, address: Optional[str] = None,
         object_store_memory: Optional[int] = None,
         system_config: Optional[dict] = None,
         namespace: str = "default") -> Runtime:
    """Start (or connect to) a node and attach this process as the driver.

    Reference analogue: ray.init (python/ray/_private/worker.py:1043) —
    starts the control plane + worker pool, connects the driver, and (TPU
    design delta) registers an in-process TPU executor so compiled jax work
    runs in the driver where device ownership lives.
    """
    global _runtime
    with _runtime_lock:
        if _runtime is not None:
            return _runtime

        if address is None:
            # job drivers join their cluster via the env the supervisor
            # sets (reference: RAY_ADDRESS)
            address = os.environ.get("RAY_TPU_ADDRESS") or None

        if address and address.startswith("ray://"):
            # thin-client mode (reference: ray.init("ray://...") routes
            # through util/client — python/ray/_private/worker.py:1043)
            from ray_tpu.util.client import ClientRuntime
            rt = ClientRuntime(address, namespace=namespace)
            _runtime = rt
            atexit.register(shutdown)
            return rt

        cfg_overrides = dict(system_config or {})
        if object_store_memory is not None:
            cfg_overrides["object_store_memory"] = object_store_memory
        config = RayTpuConfig(cfg_overrides)
        set_config(config)

        session = uuid.uuid4().hex
        session_dir = os.path.join("/tmp/ray_tpu", f"session_{session[:8]}")
        os.makedirs(session_dir, exist_ok=True)

        if address is None:
            from ray_tpu.core.node import NodeService
            if num_tpus is None:
                num_tpus = _detect_tpu_chips()
            svc = NodeService(config, session, session_dir,
                              num_cpus=num_cpus, num_tpus=num_tpus,
                              resources=resources)
            svc.start_thread()
            address = svc.address
        else:
            svc = None

        client = NodeClient(address, kind="driver")
        rt = Runtime(client, mode="driver", namespace=namespace)
        rt.node_service = svc
        rt.session_dir = session_dir

        # In-process TPU executor (single-host fast path): tasks/actors with
        # num_tpus>0 execute on this thread, inside the driver process.
        n_tpu = num_tpus if num_tpus is not None else 0
        if svc is not None and n_tpu and config.tpu_gang_in_process:
            from ray_tpu.core.executor import (make_message_queue,
                                               queue_push_handler)
            inbox = make_message_queue()
            cell: dict = {}
            ex_client = NodeClient(address, kind="tpu_executor", tpu=True,
                                   push_handler=queue_push_handler(inbox,
                                                                   cell))
            cell["client"] = ex_client
            ex = Executor(ex_client, msg_queue=inbox)
            t = threading.Thread(target=ex.run_loop, daemon=True,
                                 name="raytpu-tpu-executor")
            t.start()
            rt.tpu_executor_client = ex_client
            rt.tpu_executor_thread = t

        _runtime = rt
        atexit.register(shutdown)
        return rt


def shutdown() -> None:
    global _runtime
    with _runtime_lock:
        if _runtime is None:
            return
        rt = _runtime
        _runtime = None
    rt.shutdown()
    # give worker procs a moment to exit before the session dir vanishes
    time.sleep(0.05)
