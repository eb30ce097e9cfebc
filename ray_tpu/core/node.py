"""Node service: the per-node daemon (raylet analogue).

The node was ONE ~4,000-line module through round 10; it is now split
along its three planes, with this file left as the service shell —
composition, lifecycle, and the head channel:

  * ``node_workers.py`` — worker pool / prefork / liveness / OOM
    (reference: worker_pool.h, memory_monitor.h)
  * ``node_transfer.py`` — object directory + transfer + relay + shm
    bookkeeping + ownership/lineage recovery (reference:
    object_manager.h, plasma store.h, object_recovery_manager.h)
  * ``node_sched.py`` — task/actor/placement-group scheduling, parking,
    spillover + rebalance (reference: local_task_manager.h,
    cluster_task_manager.h)

State stays SINGLE-OWNER: every attribute is created in
``NodeService.__init__`` here, and the mixins are stateless method
bundles over that state (the event loop remains one thread, so no new
synchronization appears with the split).  ``ray_tpu lint`` resolves
cross-mixin ``self`` calls through this composed class — the protocol /
blocking / hotpath / locks invariants that made the split safe keep
gating all four modules.

Cluster half (active when ``head_address`` is set): head channel
(register / heartbeat / view sync, reference: ray_syncer.h:30), task
spillover routing, cluster-scope request proxying, and node-death
recovery hooks.  Without a head this service runs standalone: the
single-node control plane fused into one loop.  Runs as a thread inside
the driver (default, ``ray_tpu.init()``) or standalone
(``python -m ray_tpu.core.node``).
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading
import time
import traceback
from collections import deque
from typing import Any, Optional

from ray_tpu._config import RayTpuConfig
from ray_tpu.core import fault_injection as _fi
from ray_tpu.core import flight_recorder as _fr
from ray_tpu.core import protocol
from ray_tpu.core.ids import ActorID, NodeID, ObjectID
from ray_tpu.core.object_store import (NativeObjectStoreCore,
                                       make_object_store_core)
from ray_tpu.core.service import (ClientRec, ClusterStoreMixin,
                                  EventLoopService)
from ray_tpu.core.node_workers import (NodeWorkersMixin, _ForkedProc,
                                       _PendingLaunch)
from ray_tpu.core.node_transfer import (NodeTransferMixin, ObjInfo,
                                        OwnedRec, _LOCAL_NODES_BY_HEX,
                                        _gil_free_copy, _wire_spec)
from ray_tpu.core.node_sched import (NodeSchedMixin, ActorRec, PGRec,
                                     TaskRec)

__all__ = [
    "NodeService", "ObjInfo", "OwnedRec", "TaskRec", "ActorRec",
    "PGRec", "_ForkedProc", "_PendingLaunch", "_LOCAL_NODES_BY_HEX",
    "_gil_free_copy", "_wire_spec",
]


class NodeService(NodeWorkersMixin, NodeTransferMixin, NodeSchedMixin,
                  ClusterStoreMixin, EventLoopService):
    name = "node"

    def __init__(self, config: RayTpuConfig, session: str,
                 session_dir: str, listen_host: str = "127.0.0.1",
                 port: int = 0, num_cpus: Optional[float] = None,
                 num_tpus: Optional[float] = None,
                 resources: Optional[dict] = None,
                 head_address: Optional[str] = None,
                 stop_on_driver_exit: bool = True,
                 labels: Optional[dict] = None):
        super().__init__(listen_host, port)
        _fi.autoinstall_from_env()   # chaos plane in spawned node daemons
        self.config = config
        self.session = session
        self.session_dir = session_dir
        self.node_id = NodeID.from_random()
        _LOCAL_NODES_BY_HEX[self.node_id.hex()] = self
        self.stop_on_driver_exit = stop_on_driver_exit
        os.makedirs(os.path.join(session_dir, "logs"), exist_ok=True)
        # same-host workers connect over a unix socket (cheaper per
        # message than TCP loopback); falls back to the TCP address
        self.worker_address = self.address
        try:
            port = self.address.rsplit(":", 1)[1]
            self.worker_address = self.add_unix_listener(
                os.path.join(session_dir, f"node-{port}.sock"))
        except OSError:
            pass

        ncpu = num_cpus if num_cpus is not None else float(os.cpu_count() or 1)
        self.total_resources: dict[str, float] = {"CPU": ncpu}
        if num_tpus:
            self.total_resources["TPU"] = float(num_tpus)
            # advertise the generation so accelerator_type constraints
            # can pin placement (util/accelerators.accelerator_resource)
            from ray_tpu.util.accelerators import (accelerator_resource,
                                                   detect_tpu_type)
            tpu_type = detect_tpu_type()
            if tpu_type:
                self.total_resources[
                    accelerator_resource(tpu_type)] = float(num_tpus)
        if resources:
            self.total_resources.update(resources)
        self.available = dict(self.total_resources)

        spill_dir = config.object_spilling_dir or os.path.join(session_dir, "spill")
        self.store = make_object_store_core(session,
                                            config.object_store_memory,
                                            spill_dir,
                                            spill_uri=config.object_spilling_uri)

        self.objects: dict[ObjectID, ObjInfo] = {}
        self.tasks: dict[bytes, TaskRec] = {}
        # Two-queue dispatch (reference: local_task_manager.h waiting →
        # dispatch queues): tasks wait on deps, then join a runnable FIFO
        # per executor class.
        self.runnable_cpu: deque[dict] = deque()
        self.runnable_zero: deque[dict] = deque()   # zero-demand specs
        self.runnable_tpu: deque[dict] = deque()
        # incremental aggregates over the runnable queues: admission and
        # spawn decisions run PER EVENT, so recomputing by iterating a
        # deep queue would be O(backlog) per task -> O(n^2) per burst
        self._queued_demand: dict[str, float] = {}
        self._queued_pg = 0
        self.dep_waiting: dict[ObjectID, list] = {}  # oid -> waiting specs
        self.actors: dict[ActorID, ActorRec] = {}
        self.named_actors: dict[tuple[str, str], ActorID] = {}
        self._actors_wanting_worker: deque = deque()
        self._init_stores()   # kv / pubsub / function store (mixin)
        self.pgs: dict[PlacementGroupID, PGRec] = {}
        self.pg_available: dict[tuple[bytes, int], dict] = {}  # (pg,bundle)->free
        self.task_events: deque = deque(maxlen=config.task_events_buffer_size)
        # bounded retention of finished TaskRecs: the state API wants
        # recent history, but an unbounded dict makes every scan over
        # self.tasks O(everything ever run)
        self._done_order: deque = deque()
        self._spawning = 0
        self._worker_procs: list = []   # Popen | _ForkedProc
        self._worker_log_by_pid: dict[int, tuple] = {}  # pid -> (out, err)
        # fork-server template (reference: worker_pool.h:352
        # PrestartWorkers amortization; here startup cost is paid once
        # in the template and workers fork in ~ms — core/prefork.py)
        self._prefork_proc: Optional[subprocess.Popen] = None
        self._prefork_conn = None       # control socket to the template
        self._prefork_buf = b""
        self._prefork_path = ""
        if config.prefork_workers:
            self._start_prefork_template()
        # containerized-worker spawns in flight: image -> Popen.  One
        # at a time per image (a container cold-start is seconds; a
        # burst would stampede podman), re-armed when the worker
        # registers or its launcher process dies.
        self._container_spawning: dict[str, Any] = {}
        # Batched-get bookkeeping: (conn_id, reqid) -> {ids, remaining}.
        self._multigets: dict[tuple, dict] = {}
        self._mg_by_oid: dict[ObjectID, set] = {}

        # ---- cluster plane state (dormant when head_address is None) ----
        self.head_address = head_address
        self.labels = dict(labels or {})
        self._owner_driver: Optional[int] = None
        self.head_conn: Optional[protocol.Connection] = None
        self.cluster_view: dict[str, dict] = {}
        self._head_seq = 0
        self._head_pending: dict[int, Any] = {}
        self._head_subs: set[str] = set()
        self._hb_inflight = False
        self._peer_conns: dict[str, protocol.Connection] = {}
        self._peer_connecting: dict[str, list] = {}   # node_hex -> [cb]
        # actor_id(bytes) -> ("alive", node_hex, address)
        self.actor_cache: dict[bytes, tuple] = {}
        self._awaiting_actor: dict[bytes, list] = {}   # aid -> queued specs
        # aid -> when its locate was orphaned by a head failover
        self._actor_wait_parked: dict[bytes, float] = {}
        self._pulls: dict[bytes, dict] = {}            # oid bytes -> state
        self._pull_attempts: dict[bytes, int] = {}
        self._out_transfers: dict[tuple, dict] = {}    # (conn_id, oid) -> st
        self._bcast_tail: dict[bytes, tuple] = {}      # ob -> (hex, addr)
        self._watched: set[bytes] = set()              # locate sent for oid
        self._fwd_tasks: dict[bytes, dict] = {}        # task_id -> fwd info
        self._fwd_by_oid: dict[bytes, bytes] = {}      # return oid -> task_id
        self._pg_prepared: dict[tuple, dict] = {}      # (pg,idx) -> bundle
        self._pg_bundles: dict[tuple, dict] = {}       # committed originals
        self._pending_local_pgs: dict[bytes, dict] = {}  # single-node queue
        self._device_pending_pulls: dict[bytes, list] = {}  # ob -> [(conn,m)]
        self._released_wait: set[ObjectID] = set()     # owner-released oids
        self._nested_count: dict[bytes, int] = {}      # id -> container holds
        # ---- ownership + lineage (reference: reference_count.h /
        # object_recovery_manager.h / ownership_based_object_directory.cc)
        self.owned: dict[bytes, OwnedRec] = {}         # oid -> directory rec
        self.lineage: dict[bytes, dict] = {}           # tid -> {spec,cost,live,recons}
        self._lineage_bytes = 0
        self._lineage_order: deque[bytes] = deque()
        self._owner_watch: dict[bytes, str] = {}       # oid -> owner hex asked

        # OOM protection (reference: memory_monitor.h + worker killing
        # policy; N15 MemoryMonitor slice)
        self.memory_monitor = None
        if config.memory_monitor_refresh_ms > 0:
            from ray_tpu.core.memory_monitor import MemoryMonitor
            self.memory_monitor = MemoryMonitor(
                config.memory_usage_threshold,
                config.memory_monitor_refresh_ms)
        self._oom_kills: dict[bytes, str] = {}     # task_id -> detail
        self.oom_kill_count = 0

        # per-iteration coalescing for head/peer channels: handlers emit
        # several small messages per task (location reports, owner
        # pushes, forwards); one batched send per loop pass replaces a
        # send (syscall or lane post + peer wakeup) per message
        self._head_out: list = []
        self._peer_out: dict[int, tuple] = {}   # id(conn) -> (conn, [msgs])

        # ---- graceful decommission (ACTIVE -> DRAINING -> TERMINATED):
        # armed by the head's node_drain push.  While draining: no new
        # work is queued here (specs forward to the head unless the head
        # explicitly routed them back), running tasks finish under the
        # deadline, then owned objects / ownership records hand off to a
        # survivor and the node exits via drain_done.
        self._draining = False
        self._drain_deadline = 0.0
        self._drain_state = ""           # "" | waiting | handoff | done
        self._drain_timed_out = False
        self._drain_acks_pending: set[str] = set()   # survivor node hexes

        self._last_hb = 0.0
        self._hb_period = config.heartbeat_period_ms / 1000.0
        # ticks must run at least as often as heartbeats are due
        self.tick_interval = min(self.tick_interval, self._hb_period)

        # flight recorder (core/flight_recorder.py): armed per process
        # by config/env; workers stamp data-driven off the spec instead
        if config.flight_recorder and _fr._active is None:
            _fr.enable()

        self.metrics_exporter = None
        if config.metrics_export_port:
            from ray_tpu.metrics import MetricsExporter, node_metrics_snapshot
            self.metrics_exporter = MetricsExporter(
                lambda: node_metrics_snapshot(self),
                port=config.metrics_export_port)

        if head_address:
            self._connect_head()

    # ------------------------------------------------------------------ run

    def on_tick(self) -> None:
        # periodic re-dispatch: recovers from missed wakeups and
        # re-evaluates worker-pool health (dead spawns etc.)
        self._audit_worker_pool()
        self._schedule()
        self._rebalance()
        self._expire_stale_pins()
        self._sweep_released()
        self._memory_check()
        self._expire_parked_actor_waits()
        if self._draining:
            self._drain_check()
        self._heartbeat()

    def _cleanup(self) -> None:
        from ray_tpu.core import local_lane
        local_lane.unregister_service(self)
        _LOCAL_NODES_BY_HEX.pop(self.node_id.hex(), None)
        for rec in list(self.clients.values()):
            try:
                self._push(rec, {"t": "shutdown"})
                self._flush(rec)
            except Exception:
                pass
        # closing the control connection tells the template to exit
        if self._prefork_conn is not None:
            try:
                self._prefork_conn.close()
            except OSError:
                pass
            self._prefork_conn = None
        deadline = time.time() + 2.0
        for p in self._worker_procs:
            try:
                p.wait(timeout=max(0.0, deadline - time.time()))
            except subprocess.TimeoutExpired:
                p.kill()
        if self._prefork_proc is not None:
            try:
                self._prefork_proc.wait(timeout=max(0.0,
                                                    deadline - time.time()))
            except subprocess.TimeoutExpired:
                self._prefork_proc.kill()
        for rec in list(self.clients.values()):
            try:
                rec.sock.close()
            except OSError:
                pass
            if rec.lane is not None:
                rec.lane._mark_closed()
        self.listener.close()
        self._close_extra_listeners()
        self.sel.close()
        for conn in self._peer_conns.values():
            try:
                conn.close()
            except Exception:
                pass
        if self.head_conn is not None:
            try:
                self.head_conn.close()
            except Exception:
                pass
        if self.metrics_exporter is not None:
            self.metrics_exporter.stop()
        self.store.shutdown()

    # ------------------------------------------------------- head channel

    def _connect_head(self) -> None:
        conn = protocol.connect(
            self.head_address, remote=True,
            label=(f"node:{self.node_id.hex()[:8]}", "head"))
        conn.send({"t": "register_node", "reqid": 0,
                   "node_id": self.node_id.hex(), "address": self.address,
                   "resources": self.total_resources,
                   "available": dict(self.available),
                   "labels": self.labels})
        reply = conn.recv(timeout=30.0)
        if reply.get("error"):
            raise RuntimeError(f"head registration failed: {reply['error']}")
        self.cluster_view = reply.get("view", {})
        # the head's session differs from this node's DERIVED session
        # (per-node shm arenas) — replica validation uses the head's
        self.head_session = reply.get("session", "")
        self.head_conn = conn
        self._start_head_recv(conn)

    def _start_head_recv(self, conn) -> None:
        """Route head pushes onto the event loop.  A lane connection
        (same-process head) delivers straight from the head's loop —
        no dedicated recv thread, one wakeup fewer per message."""
        from ray_tpu.core.local_lane import LaneConnection
        if isinstance(conn, LaneConnection):
            conn.on_close = lambda: self.post(self._head_lost)
            conn.set_deliver(
                lambda m: self.post(lambda m=m: self._on_head_msg(m)))
            return
        t = threading.Thread(target=self._head_recv_loop, daemon=True,
                             name="raytpu-node-head")
        t.start()

    def _head_recv_loop(self) -> None:
        while not self._stop.is_set():
            try:
                msg = self.head_conn.recv()
            except protocol.ConnectionClosed:
                self.post(self._head_lost)
                return
            except Exception:
                continue
            self.post(lambda m=msg: self._on_head_msg(m))

    def _head_lost(self) -> None:
        # Head death orphans the cluster plane; keep serving local work
        # (reference: raylets survive transient GCS outages), fail
        # everything mid-flight through the head so callers see errors
        # instead of hanging forever, and keep trying to REJOIN — a
        # persistent head restarting on the same address picks the
        # cluster back up (reference: GCS-FT reconnection,
        # gcs_client reconnection loop).
        if self.head_conn is None:
            return
        sys.stderr.write("[node] lost connection to head service\n")
        self.head_conn = None
        self._hb_inflight = False
        pending = list(self._head_pending.values())
        self._head_pending.clear()
        for cb in pending:
            try:
                cb({"error": "head connection lost"})
            except Exception:
                sys.stderr.write("[node] head-lost callback failed:\n"
                                 + traceback.format_exc())
        # actor-bound tasks whose locate was cut off stay PARKED for the
        # failover grace window (config actor_locate_failover_grace_s):
        # failing them instantly turned every head failover into
        # client-visible actor errors.  _head_rejoined re-issues the
        # locates; on_tick expires the ones the grace ran out on.
        now = time.monotonic()
        for ab in self._awaiting_actor:
            self._actor_wait_parked.setdefault(ab, now)
        self.post_later(1.0, self._try_reconnect_head)

    def _try_reconnect_head(self) -> None:
        if self.head_conn is not None or self._stop.is_set():
            return

        def work():
            try:
                conn = protocol.connect(
                    self.head_address, timeout=3.0, remote=True,
                    label=(f"node:{self.node_id.hex()[:8]}", "head"))
                conn.send({"t": "register_node", "reqid": 0,
                           "node_id": self.node_id.hex(),
                           "address": self.address,
                           "resources": self.total_resources,
                           "available": dict(self.available),
                           "labels": self.labels})
                reply = conn.recv(timeout=10.0)
                if reply.get("error"):
                    raise RuntimeError(reply["error"])
            except Exception:
                self.post_later(2.0, self._try_reconnect_head)
                return
            self.post(lambda: self._head_rejoined(conn, reply))
        threading.Thread(target=work, daemon=True,
                         name="raytpu-head-reconnect").start()

    def _head_rejoined(self, conn: protocol.Connection,
                       reply: dict) -> None:
        if self.head_conn is not None:
            try:
                conn.close()
            except Exception:
                pass
            return
        sys.stderr.write("[node] rejoined head service\n")
        self.head_conn = conn
        self.cluster_view = reply.get("view", {})
        self.head_session = reply.get("session",
                                      getattr(self, "head_session", ""))
        self._start_head_recv(conn)
        try:
            # re-establish cluster-visible state: subscriptions, object
            # locations, actor liveness (a restarted head restored its
            # durable directory but not this live state)
            for ch in self._head_subs:
                conn.send({"t": "subscribe", "channel": ch})
            adds = []
            for oid, info in self.objects.items():
                if info.state in ("ready", "error"):
                    info.loc_reported = True
                    adds.append(oid.binary())
            if adds:
                conn.send({"t": "report_locations", "adds": adds})
            for ar in self.actors.values():
                if ar.state != "dead":
                    self._report_actor_state(ar)
            # re-ask for every actor whose locate the failover orphaned;
            # the parked specs resume the moment the new head answers
            for ab in list(self._awaiting_actor):
                self._head_rpc(
                    {"t": "locate_actor", "actor_id": ab},
                    lambda reply, ab=ab: self._on_actor_located(ab, reply))
        except protocol.ConnectionClosed:
            self._head_lost()

    def _head_send(self, msg: dict) -> None:
        """Queue a head-bound message; the loop flushes the batch once
        per iteration (_flush_corked).  Send failures surface there and
        run the normal head-loss path."""
        if self.head_conn is None:
            return
        self._head_out.append(msg)

    def _conn_send(self, conn, msg: dict) -> None:
        """Queue a peer-bound message for the per-iteration batch
        flush."""
        ent = self._peer_out.get(id(conn))
        if ent is None:
            self._peer_out[id(conn)] = (conn, [msg])
        else:
            ent[1].append(msg)

    def _flush_corked(self) -> None:
        if self._head_out:
            out, self._head_out = self._head_out, []
            conn = self.head_conn
            if conn is not None:
                try:
                    if len(out) == 1:
                        conn.send(out[0])
                    else:
                        conn.send_batch(out)
                except protocol.ConnectionClosed:
                    self._head_lost()
        if self._peer_out:
            batches, self._peer_out = self._peer_out, {}
            for conn, msgs in batches.values():
                try:
                    if len(msgs) == 1:
                        conn.send(msgs[0])
                    else:
                        conn.send_batch(msgs)
                except (protocol.ConnectionClosed, OSError):
                    pass   # peer drop is handled by its recv/on_close path
        super()._flush_corked()

    def _head_rpc(self, msg: dict, cb=None) -> None:
        if self.head_conn is None:
            if cb is not None:
                cb({"error": "no head connection"})
            return
        if cb is not None:
            self._head_seq += 1
            msg["reqid"] = self._head_seq
            self._head_pending[self._head_seq] = cb
        self._head_send(msg)

    def _on_head_msg(self, m: dict) -> None:
        if m.get("t") == "reply":
            cb = self._head_pending.pop(m.get("reqid"), None)
            if cb is not None:
                try:
                    cb(m)
                except Exception:
                    sys.stderr.write("[node] head rpc callback failed:\n"
                                     + traceback.format_exc())
            return
        handler = getattr(self, "_hh_" + m["t"], None)
        if handler is None:
            return
        try:
            handler(m)
        except Exception:
            sys.stderr.write(f"[node] head push {m['t']} failed:\n"
                             + traceback.format_exc())

    def _head_reply(self, reqid: int, **kw) -> None:
        kw["t"] = "reply"
        kw["reqid"] = reqid
        self._head_send(kw)

    def _heartbeat(self) -> None:
        if self.head_conn is None or self._hb_inflight:
            return
        now = time.monotonic()
        if now - self._last_hb < self._hb_period:
            return
        self._last_hb = now
        self._hb_inflight = True

        def cb(reply):
            self._hb_inflight = False
            if not reply.get("error"):
                self.cluster_view = reply.get("view", self.cluster_view)
        queued = {k: v for k, v in self._queued_demand.items()
                  if v > 1e-9}
        self._head_rpc({"t": "heartbeat",
                        "available": self._projected_available(),
                        "total": self.total_resources,
                        "queued": queued}, cb)

    # -------------------------------------------------------- registration

    def _h_register(self, rec, m):
        rec.kind = m["kind"]
        rec.worker_id = m.get("worker_id", "")
        rec.pid = m.get("pid", 0)
        rec.tpu = bool(m.get("tpu", False))
        rec.node_hex = m.get("node_hex", "")
        rec.container_image = m.get("container_image", "")
        if rec.kind == "driver" and self._owner_driver is None:
            # the FIRST driver owns this node's lifetime; later drivers
            # (job entrypoints, attached shells) come and go freely
            self._owner_driver = rec.conn_id
        if rec.kind in ("worker", "tpu_executor"):
            if rec.container_image:
                # container launches track per-image (_container_
                # spawning), never the host _spawning counter — a
                # decrement here would mark an unrelated in-flight host
                # spawn as done
                self._container_spawning.pop(rec.container_image, None)
            else:
                self._spawning = max(0, self._spawning - 1)
        self._reply(rec, m["reqid"], session=self.session,
                    node_id=self.node_id.hex(), address=self.address,
                    config=self.config.to_dict(),
                    native_store=isinstance(self.store,
                                            NativeObjectStoreCore))
        while self._actors_wanting_worker:
            ar = self._actors_wanting_worker.popleft()
            if ar.state in ("pending", "restarting") and ar.conn_id is None:
                self._place_actor(ar)
                break   # one new worker hosts one actor
        self._schedule()

    # -- functions

    def _h_register_function(self, rec, m):
        self._store_function(m["function_id"], m["pickled"])
        if self.head_conn is not None:
            # cluster-wide export so any node's workers can fetch it
            self._head_send({"t": "register_function",
                             "function_id": m["function_id"],
                             "pickled": m["pickled"]})
        if "reqid" in m:
            self._reply(rec, m["reqid"], ok=True)

    def _h_fetch_function(self, rec, m):
        fid = m["function_id"]
        if fid in self.functions:
            self._reply(rec, m["reqid"], pickled=self.functions[fid])
            return
        first = fid not in self._fn_waiters
        self._fn_waiters.setdefault(fid, []).append((rec.conn_id, m["reqid"]))
        if first and self.head_conn is not None:
            # the head parks the fetch until some node registers the
            # function (functions are exported once, cluster-wide)
            def cb(reply):
                if reply.get("pickled"):
                    self._store_function(fid, reply["pickled"])
                elif reply.get("error"):
                    # head gone: fail waiters instead of hanging workers
                    for conn_id, reqid in self._fn_waiters.pop(fid, []):
                        w = self.clients.get(conn_id)
                        if w is not None:
                            self._reply(w, reqid,
                                        error="function fetch failed: "
                                              f"{reply['error']}")
            self._head_rpc({"t": "fetch_function", "function_id": fid}, cb)

    # -- head proxying ------------------------------------------------------

    def _cluster_scope(self, rec: ClientRec, m: dict) -> bool:
        """Route a cluster-scope client request.  True = handled here
        (proxied to the head, or failed transiently); False = this node
        is STANDALONE and should serve it from its local stores.

        The distinction matters during a head failover: a cluster
        node with its head temporarily gone must NOT silently fall back
        to its (empty) local store — that's a split-brain read.  It
        answers with a transient, RetryPolicy-retryable error instead,
        so clients ride out the failover and then read the truth."""
        if self.head_address is None:
            return False
        if self.head_conn is None:
            if "reqid" in m:
                self._reply(rec, m["reqid"],
                            error="head connection lost (failover in "
                                  "progress)")
            return True
        self._proxy_to_head(rec, m)
        return True

    def _proxy_to_head(self, rec: ClientRec, m: dict) -> None:
        """Forward a cluster-scope client request to the head verbatim and
        relay the reply (errors included)."""
        reqid = m.get("reqid")
        fwd = {k: v for k, v in m.items() if k != "reqid"}
        if reqid is None:
            self._head_send(fwd)
            return

        def cb(reply):
            w = self.clients.get(rec.conn_id)
            if w is None:
                return
            out = {k: v for k, v in reply.items() if k not in ("t", "reqid")}
            self._reply(w, reqid, **out)
        self._head_rpc(fwd, cb)

    # 2PC participant handlers (pushed by the head over the head channel;
    # reference: gcs_placement_group_scheduler.h Prepare/Commit on raylets)

    def _hh_head_snapshot(self, m: dict) -> None:
        """Persist the head's replicated snapshot (the cluster-as-the-
        database head-FT store — see head.py _fan_out_replicas)."""
        if m.get("session") not in (None, getattr(self, "head_session",
                                                  "")):
            return   # a different cluster's state must never land here
        # seq fence per head incarnation: a slow async snapshot can fan
        # out AFTER a newer snapshot_now one — applying it would undo
        # the barrier's guarantee (and lose whatever the newer snapshot
        # captured on a later head-machine recovery)
        boot = m.get("boot")
        if boot != getattr(self, "_head_replica_boot", None):
            self._head_replica_boot = boot
            self._head_replica_seq = 0
        if m.get("seq", 0) < getattr(self, "_head_replica_seq", 0):
            return   # stale replica from an older snapshot
        path = os.path.join(self.session_dir, "head_replica.state")
        tmp = path + ".tmp"
        try:
            with open(tmp, "wb") as f:
                f.write(m["data"])
            os.replace(tmp, path)
            self._head_replica_seq = m.get("seq", 0)
        except OSError:
            pass  # a missed replica is refreshed by the next snapshot

    def _h_fetch_head_snapshot(self, rec, m):
        """A replacement head bootstraps from this node's replica; the
        reply carries this node's session so a head recovering against
        the wrong cluster rejects it."""
        path = os.path.join(self.session_dir, "head_replica.state")
        try:
            with open(path, "rb") as f:
                data = f.read()
        except OSError:
            self._reply(rec, m["reqid"],
                        session=getattr(self, "head_session", ""),
                        error="no head snapshot replica on this node")
            return
        self._reply(rec, m["reqid"], ok=True, data=data,
                    session=getattr(self, "head_session", ""),
                    seq=getattr(self, "_head_replica_seq", 0))

    # -- kv / pubsub

    def _h_kv_put(self, rec, m):
        if self._cluster_scope(rec, m):
            return
        super()._h_kv_put(rec, m)

    def _h_kv_get(self, rec, m):
        if self._cluster_scope(rec, m):
            return
        super()._h_kv_get(rec, m)

    def _h_kv_del(self, rec, m):
        if self._cluster_scope(rec, m):
            return
        super()._h_kv_del(rec, m)

    def _h_kv_keys(self, rec, m):
        if self._cluster_scope(rec, m):
            return
        super()._h_kv_keys(rec, m)

    # -- cluster prefix directory (the head hosts it; see core/head.py
    # _h_prefix_* and serve/fleet/prefix_directory.py).  Standalone
    # nodes answer with benign no-ops: a single-node session has
    # exactly one fleet process, whose in-proc directory already IS the
    # whole prefix plane — there is nothing cluster-scope to mirror.

    def _h_prefix_publish(self, rec, m):
        if self._cluster_scope(rec, m):
            return
        if "reqid" in m:
            self._reply(rec, m["reqid"], ok=True, published=0)

    def _h_prefix_lookup(self, rec, m):
        if self._cluster_scope(rec, m):
            return
        if "reqid" in m:
            self._reply(rec, m["reqid"], ok=True, hit=None)

    def _h_prefix_invalidate(self, rec, m):
        if self._cluster_scope(rec, m):
            return
        if "reqid" in m:
            self._reply(rec, m["reqid"], ok=True, invalidated=0)

    def _h_subscribe(self, rec, m):
        ch = m["channel"]
        if self.head_conn is not None and ch not in self._head_subs:
            # subscribe this NODE at the head once per channel; local
            # clients fan out from the node (reference: pubsub long-poll
            # through the raylet)
            self._head_subs.add(ch)
            self._head_send({"t": "subscribe", "channel": ch})
        super()._h_subscribe(rec, m)

    def _publish(self, channel: str, data: Any) -> None:
        if self.head_conn is not None:
            # cluster-wide: the head fans out to subscribed nodes
            # (including this one), which deliver locally on _hh_pub
            self._head_send({"t": "publish", "channel": channel,
                             "data": data})
            return
        self._publish_local(channel, data)

    def _hh_pub(self, m: dict) -> None:
        self._publish_local(m["channel"], m["data"])

    def _hh_view_update(self, m: dict) -> None:
        self.cluster_view = m["view"]

    def _h_flight_recorder(self, rec, m):
        """Observer query: completed lifecycle records + chaos events +
        serve-ingress events + the per-stage summary (the `ray_tpu
        timeline` source)."""
        fr = _fr._active
        if fr is None:
            self._reply(rec, m["reqid"], enabled=False, records=[],
                        faults=[], ingress=[], stages={})
            return
        self._reply(rec, m["reqid"], enabled=True,
                    records=fr.export_records(
                        limit=int(m.get("limit", 2000))),
                    faults=fr.export_faults(),
                    ingress=fr.export_ingress(),
                    stages=fr.stage_summary())

    def _h_state(self, rec, m):
        what = m["what"]
        if what in ("nodes", "resources", "cluster_actors") \
                and self.head_conn is not None:
            # cluster-scope views come from the head (ray.nodes() /
            # ray.cluster_resources() are cluster-wide in the reference)
            fwd = dict(m)
            fwd["what"] = {"cluster_actors": "actors"}.get(what, what)
            self._proxy_to_head(rec, fwd)
            return
        if what == "tasks":
            out = [{"task_id": tid.hex(), "name": tr.spec.get("name", ""),
                    "state": tr.state, "error": tr.error,
                    "submitted_at": tr.submitted_at,
                    "duration": (tr.finished_at - tr.started_at)
                    if tr.finished_at else None}
                   for tid, tr in self.tasks.items()]
        elif what == "actors":
            out = [{"actor_id": aid.hex(), "state": ar.state,
                    "name": ar.name, "namespace": ar.namespace,
                    "class_name": ar.spec.get("class_name", ""),
                    "pending_calls": len(ar.queue)}
                   for aid, ar in self.actors.items()]
        elif what == "objects":
            out = [{"object_id": oid.hex(), "state": info.state,
                    "loc": info.loc, "size": info.size}
                   for oid, info in self.objects.items()]
        elif what == "workers":
            out = [{"worker_id": c.worker_id, "kind": c.kind, "pid": c.pid,
                    "state": c.state, "tpu": c.tpu,
                    "log": os.path.basename(
                        self._worker_log_by_pid.get(c.pid, ("", ""))[0])
                    or None}
                   for c in self.clients.values()
                   if c.kind in ("worker", "tpu_executor")]
        elif what == "nodes":
            out = [{"node_id": self.node_id.hex(), "address": self.address,
                    "resources": self.total_resources,
                    "available": self.available, "alive": True}]
        elif what == "task_events":
            out = list(self.task_events)
        elif what == "resources":
            out = {"total": self.total_resources, "available": self.available}
        else:
            out = []
        self._reply(rec, m["reqid"], data=out)

    def _h_ping(self, rec, m):
        self._reply(rec, m["reqid"], ok=True, time=time.time())

    def _h_head_flush(self, rec, m):
        """Replication barrier: force the head to snapshot + fan out
        replicas, reply once THIS node's replica has landed (the
        head_snapshot push precedes the head's reply on this channel)."""
        if self.head_conn is None:
            self._reply(rec, m["reqid"], ok=True, replicated=False)
            return
        reqid = m["reqid"]

        def cb(reply):
            w = self.clients.get(rec.conn_id)
            if w is None:
                return
            if reply.get("error"):
                self._reply(w, reqid, error=reply["error"])
            else:
                self._reply(w, reqid, ok=True,
                            replicated=bool(reply.get("replicated")))
        self._head_rpc({"t": "snapshot_now"}, cb)

    # ------------------------------------------------- graceful drain

    def _h_drain_node(self, rec, m):
        """Client entry point for decommissioning a cluster node: the
        request proxies to the head (which owns membership and flips the
        target to DRAINING).  Standalone nodes have nowhere to drain
        to."""
        if self._cluster_scope(rec, m):
            return
        self._reply(rec, m["reqid"],
                    error="standalone node: nothing to drain to "
                          "(drain_node needs a cluster)")

    def _hh_node_drain(self, m: dict) -> None:
        """Head push: decommission this node gracefully.  From here on
        the lifecycle is DRAINING: queued specs re-park to the head,
        new local submissions forward, running tasks get ``deadline_s``
        to finish, then the owned-object handoff ships and the node
        exits via drain_done (node.py hosts the state machine; the
        handoff itself lives in node_transfer)."""
        if self._draining:
            return
        self._draining = True
        self._drain_state = "waiting"
        self._drain_deadline = (time.monotonic()
                                + float(m.get("deadline_s", 30.0)))
        sys.stderr.write("[node] draining for decommission "
                         f"(deadline {m.get('deadline_s', 30.0)}s)\n")
        fi = _fi._active
        if fi is not None:
            fi.on_drain("node_drain", {"node": self})
        self._repark_queued_to_head()
        self._drain_check()

    def _drain_busy(self) -> bool:
        """Work the drain must wait for — everything that will still
        EXECUTE here: tasks running on workers, actor method calls in
        flight OR queued (an actor can't move, so its queue drains
        here), specs still in the runnable queues (only PG-bound and
        head-routed-back specs remain there during a drain — both run
        here by design), and dep-waiting specs (they either forward on
        resolution or run here; either way exiting under them drops
        work).  Conservative signals are safe: the deadline caps the
        wait, and past it the EXPLICIT timeout path runs."""
        for rec in self.clients.values():
            if rec.current_task is not None:
                return True
        for ar in self.actors.values():
            # an actor whose CREATION is still in flight (worker
            # spawning) must reach alive before the drain can judge its
            # queue — exiting under it strands calls parked at their
            # submitters awaiting the locate
            if ar.state in ("pending", "restarting"):
                return True
            if ar.state != "dead" and (ar.running or ar.queue):
                return True
        if self.runnable_cpu or self.runnable_tpu or self.runnable_zero:
            return True
        if self.dep_waiting:
            return True
        return False

    def _drain_check(self) -> None:
        if self._drain_state != "waiting":
            return
        timed_out = time.monotonic() >= self._drain_deadline
        if self._drain_busy() and not timed_out:
            return
        self._drain_timed_out = timed_out and self._drain_busy()
        self._drain_state = "handoff"
        self._drain_handoff()

    def _drain_finish(self) -> None:
        """Handoff shipped (and acked, or the ack window closed): tell
        the head this removal is COMPLETE — never a surprise — then
        stop.  The head's node_dead fan-out still runs as the safety
        net for anything the handoff didn't cover."""
        if self._drain_state == "done":
            return
        self._drain_state = "done"
        self._head_rpc({"t": "drain_done",
                        "node_id": self.node_id.hex(),
                        "timed_out": self._drain_timed_out},
                       lambda reply: self._stop.set())
        # backstop: head unreachable / reply lost — exit anyway
        self.post_later(5.0, self._stop.set)

    def _h_stop_node(self, rec, m):
        """Hard-stop this node on request — the chaos-testing kill switch
        (reference: the NodeKiller in _private/test_utils.py:1337 and
        `ray kill-random-node`).  Workers die with the node; the head
        notices through the dropped connection / missed heartbeats."""
        if "reqid" in m:
            self._reply(rec, m["reqid"], ok=True)
        for p in self._worker_procs:
            if p.poll() is None:
                try:
                    p.kill()
                except OSError:
                    pass
        if self._prefork_proc is not None and self._prefork_proc.poll() is None:
            try:
                self._prefork_proc.kill()
            except OSError:
                pass
        self._stop.set()

    # -- disconnect handling

    def on_client_drop(self, rec: ClientRec) -> None:
        for oid, _ts in rec.held_pins:
            self.store.unpin(oid)
        rec.held_pins.clear()
        # device-resident entries die with their owner process
        for oid, info in list(self.objects.items()):
            if info.loc == "device" and info.owner_conn == rec.conn_id:
                self._device_owner_lost(oid, info)
        # drop any outbound transfers to this peer
        for key in [k for k in self._out_transfers if k[0] == rec.conn_id]:
            st = self._out_transfers.pop(key)
            if st.get("view") is not None:
                st["view"] = None
                if st.get("pinned", True):
                    self.store.unpin(st["oid"])
        # fail or retry the running task (reference: worker death →
        # owner retries, task_manager.h:406)
        if rec.current_task is not None:
            tr = self.tasks.get(rec.current_task)
            oom_detail = self._oom_kills.pop(rec.current_task, None)
            if tr is not None and tr.state == "running":
                if not tr.spec.get("_cpu_released"):
                    self._return_resources(tr.spec)
                tr.spec.pop("_cpu_released", None)
                if tr.retries_left > 0:
                    tr.retries_left -= 1
                    tr.state = "pending"
                    if _fr._active is not None:
                        # name the failed attempt + death-detection gap
                        # explicitly so it doesn't pollute the retry's
                        # enqueue interval in the stage histograms
                        _fr._active.stamp(tr.spec, "retry")
                    self._make_runnable(tr.spec)
                elif oom_detail is not None:
                    from ray_tpu.core.client import OutOfMemoryError
                    tr.state = "failed"
                    tr.error = oom_detail
                    tr.finished_at = time.time()
                    self._record_event(tr.spec, "FAILED")
                    for b in tr.spec["return_ids"]:
                        self._seal_error_object(
                            ObjectID(b), OutOfMemoryError(oom_detail))
                else:
                    self._fail_task(tr.spec,
                                    f"Worker died while running task "
                                    f"(pid={rec.pid})")
        conn_actors = [a for a in self.actors.values()
                       if a.conn_id == rec.conn_id and a.state != "dead"]
        for ar in conn_actors:
                self._return_resources(ar.spec)
                ar.conn_id = None
                # In-flight method calls die with the worker: fail them so
                # callers see an actor-death error instead of hanging
                # (reference: actor task fate on actor death,
                # direct_actor_task_submitter.h DisconnectActor).
                for spec in list(ar.running.values()):
                    self._fail_task(spec,
                                    f"Actor died while executing method "
                                    f"'{spec.get('method', '?')}' "
                                    f"(pid={rec.pid})")
                ar.running.clear()
                if ar.restarts_left != 0:
                    if ar.restarts_left > 0:
                        ar.restarts_left -= 1
                    ar.state = "restarting"
                    self._report_actor_state(ar)
                    self._place_actor(ar)
                else:
                    ar.state = "dead"
                    ar.death_cause = f"worker process died (pid={rec.pid})"
                    self._report_actor_state(ar)
                    self._fail_actor_queue(ar, ar.death_cause)
        if (rec.kind == "driver" and self.stop_on_driver_exit
                and rec.conn_id == self._owner_driver):
            # owning driver gone → shut down
            self._stop.set()
        self._schedule()

def main() -> None:
    import argparse
    parser = argparse.ArgumentParser(description="ray_tpu node service")
    parser.add_argument("--port", type=int, default=6379)
    parser.add_argument("--session", default=None)
    parser.add_argument("--session-dir", default=None)
    parser.add_argument("--num-cpus", type=float, default=None)
    parser.add_argument("--num-tpus", type=float, default=None)
    parser.add_argument("--head-address", default=None,
                        help="head service address; omit for standalone")
    parser.add_argument("--label", action="append", default=[],
                        help="k=v node label (repeatable); e.g. the "
                             "autoscaler's provider_node_id")
    args = parser.parse_args()
    labels = dict(kv.split("=", 1) for kv in args.label)
    import uuid
    session = args.session or uuid.uuid4().hex
    session_dir = args.session_dir or os.path.join(
        "/tmp/ray_tpu", f"session_{session[:8]}")
    svc = NodeService(RayTpuConfig(), session, session_dir, port=args.port,
                      num_cpus=args.num_cpus, num_tpus=args.num_tpus,
                      head_address=args.head_address,
                      stop_on_driver_exit=args.head_address is None,
                      labels=labels)
    print(f"ray_tpu node service listening on {svc.address} "
          f"(session {session})", flush=True)
    try:
        svc.run()
    except KeyboardInterrupt:
        svc.stop()


if __name__ == "__main__":
    main()
