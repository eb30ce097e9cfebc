"""Worker-pool half of the node service (split out of core/node.py).

Worker process lifecycle for one node: demand-driven pool growth with
capped startup concurrency, the fork-server fast path (core/prefork.py),
containerized worker launches (runtime_env.container), liveness auditing
moved off the per-event path, OOM victim selection, and the worker
observability handlers (logs / profiling / stack dumps).  Reference:
src/ray/raylet/worker_pool.h, memory_monitor.h.

``NodeWorkersMixin`` carries no state of its own — every attribute is
initialized by ``NodeService.__init__`` (core/node.py), which composes
this mixin with the transfer and scheduling halves.  Cross-mixin calls
go through ``self``; ``ray_tpu lint`` (analysis/) resolves them through
the composed class, so the loop-blocking and hotpath invariants keep
gating this module after the split.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import time
from typing import Optional

from ray_tpu.core import fault_injection as _fi


# ---------------------------------------------------------------------------
# fork-server worker handle


class _ForkedProc:
    """Popen-shaped handle for a worker forked by the prefork template
    (core/prefork.py).  The template reaps exits, so liveness is probed
    with signal 0 rather than waitpid."""

    def __init__(self, pid: int):
        self.pid = pid
        self._rc: Optional[int] = None

    def poll(self) -> Optional[int]:
        if self._rc is None:
            try:
                os.kill(self.pid, 0)
            except (ProcessLookupError, PermissionError):
                self._rc = 0
        return self._rc

    def wait(self, timeout: Optional[float] = None) -> int:
        deadline = None if timeout is None else time.time() + timeout
        while self.poll() is None:
            if deadline is not None and time.time() > deadline:
                raise subprocess.TimeoutExpired("forked-worker", timeout)
            time.sleep(0.02)
        return self._rc

    def _signal(self, sig: int) -> None:
        try:
            os.kill(self.pid, sig)
        except (ProcessLookupError, PermissionError):
            pass

    def terminate(self) -> None:
        self._signal(signal.SIGTERM)

    def kill(self) -> None:
        self._signal(signal.SIGKILL)


class _PendingLaunch:
    """Popen-shaped placeholder guarding a container launch that has
    been SCHEDULED but not yet exec'd (e.g. chaos slow-spawn).  poll()
    reads in-flight until the register window expires, then done —
    re-arming retries for a launch that silently died."""

    def __init__(self, ttl_s: float):
        self._deadline = time.monotonic() + ttl_s
        self.pid = 0

    def poll(self) -> Optional[int]:
        return None if time.monotonic() < self._deadline else 0

class NodeWorkersMixin:
    """Worker pool / prefork / liveness (mixed into NodeService)."""

    def _memory_check(self) -> None:
        """OOM protection: when node memory crosses the threshold, kill
        one running worker chosen by the group-by-owner policy; the task
        retries or fails with OutOfMemoryError (reference:
        memory_monitor.h:52, worker_killing_policy_group_by_owner.h:85)."""
        mm = self.memory_monitor
        if mm is None or not mm.due():
            return
        over = mm.over_threshold()
        if over is None:
            return
        used, total = over
        from ray_tpu.core.memory_monitor import pick_victim
        cands = []
        for rec in self.clients.values():
            if (rec.kind != "worker" or rec.dedicated_actor is not None
                    or rec.state != "busy" or rec.current_task is None
                    or not rec.pid):
                continue
            tr = self.tasks.get(rec.current_task)
            if tr is not None and tr.state == "running":
                cands.append((rec, tr))
        victim = pick_victim(cands)
        if victim is None:
            return
        rec, tr = victim
        detail = (f"task used node memory past the threshold "
                  f"({used / (1 << 20):.0f}MiB / {total / (1 << 20):.0f}"
                  f"MiB >= {mm.threshold:.2f}); worker pid={rec.pid} "
                  f"killed to protect the node")
        try:
            os.kill(rec.pid, signal.SIGKILL)
        except OSError:
            return   # already gone: no kill happened, record nothing
        self._oom_kills[rec.current_task] = detail
        self.oom_kill_count += 1
        self._record_event(tr.spec, "OOM_KILLED", worker=rec.conn_id)
        sys.stderr.write(f"[node] OOM: killing worker pid={rec.pid} "
                         f"(task {rec.current_task.hex()[:12]}, "
                         f"{used}/{total} bytes)\n")

    def _maybe_spawn_container_worker(self, container: dict) -> None:
        """Launch a worker exec'd inside the requested image
        (runtime_env.container — ROADMAP 5a).  One launch in flight per
        image: container cold-starts are seconds, and every _schedule
        pass would otherwise stampede podman.  A launcher that dies
        before its worker registers re-arms on the next pass."""
        image = container["image"]
        prev = self._container_spawning.get(image)
        if prev is not None and prev.poll() is None:
            return
        # arm the guard BEFORE the spawn call: a chaos-delayed spawn
        # returns without a Popen, and every _schedule pass until the
        # delay elapsed would otherwise queue another launch.  The
        # placeholder expires after the register window so a silently
        # failed launch re-arms; _do_spawn_worker overwrites it with
        # the real proc.
        self._container_spawning[image] = _PendingLaunch(
            self.config.worker_register_timeout_s)
        try:
            self._spawn_worker_proc(container=dict(container))
        except Exception as e:
            self._container_spawning.pop(image, None)
            # no container runtime / unlaunchable image: a spec that can
            # never dispatch must not wedge the queue head forever —
            # fail the demand with the real problem named
            self._fail_container_demand(
                image, f"containerized worker for image '{image}' "
                       f"cannot launch: {e}")

    def _fail_container_demand(self, image: str, error: str) -> None:
        for q in (self.runnable_cpu, self.runnable_tpu,
                  self.runnable_zero):
            doomed = [s for s in q
                      if (((s.get("runtime_env") or {}).get("container")
                           or {}).get("image")) == image]
            for spec in doomed:
                q.remove(spec)
                # mirror _queue_pop's aggregate accounting
                if spec.get("placement_group"):
                    self._queued_pg = max(0, self._queued_pg - 1)
                else:
                    for k, v in self._demand(spec).items():
                        self._queued_demand[k] = \
                            self._queued_demand.get(k, 0.0) - v
                self._fail_task(spec, error)
        if (not self.runnable_cpu and not self.runnable_tpu
                and not self.runnable_zero):
            self._queued_demand.clear()
            self._queued_pg = 0
        for ar in list(self.actors.values()):
            if (ar.state in ("pending", "restarting")
                    and ar.conn_id is None
                    and (((ar.spec.get("runtime_env") or {})
                          .get("container") or {}).get("image")) == image):
                self._mark_actor_dead(ar, error)

    def _audit_worker_pool(self) -> None:
        """Self-heal the in-flight spawn counter against crashed spawns
        and prune long-dead procs.  Runs on the periodic tick, NOT per
        event: each liveness probe is a waitpid/kill syscall per proc,
        and at thousands of events/s this scan alone was ~45% of the
        node loop (sampled; the 5 ms throttle still admitted it every
        few events)."""
        alive = [p for p in self._worker_procs if p.poll() is None]
        if len(self._worker_procs) - len(alive) > 32:
            self._worker_procs = alive
        registered = sum(1 for c in self.clients.values()
                         if c.kind == "worker" and not c.tpu)
        # on_tick runs _schedule() right after this, so just correct
        # the counter here
        self._spawning = max(0, len(alive) - registered)

    def _maybe_spawn_worker(self, tpu: bool = False) -> None:
        if tpu:
            return  # TPU executors are registered by the driver, not spawned
        # Throttle: this runs on EVERY submit/completion event.  Pool
        # sizing only needs to be right within a few ms; the periodic
        # tick re-audits (and self-heals `_spawning`) regardless.
        now = time.monotonic()
        if now - getattr(self, "_last_spawn_eval", 0.0) < 0.005:
            # re-arm so a lone skipped event still gets its evaluation
            # promptly instead of waiting for the next tick
            if not getattr(self, "_spawn_eval_armed", False):
                self._spawn_eval_armed = True

                def rearm():
                    self._spawn_eval_armed = False
                    self._schedule()
                self.post_later(0.006, rearm)
            return
        self._last_spawn_eval = now
        registered = sum(1 for c in self.clients.values()
                         if c.kind == "worker" and not c.tpu)
        # Demand-driven pool growth (reference: worker_pool.h capped startup
        # concurrency :192): one worker per waiting task/actor, capped.
        n_actors_waiting = sum(
            1 for a in self.actors.values()
            if a.state in ("pending", "restarting") and a.conn_id is None
            and not a.spec.get("num_tpus"))
        # containerized workers don't count as spare capacity here: they
        # can only take matching-image tasks, so an idle one must not
        # mask the need for a host worker
        idle = sum(1 for c in self.clients.values()
                   if c.kind == "worker" and not c.tpu and c.state == "idle"
                   and c.dedicated_actor is None and not c.container_image)
        # Tasks can only run while CPU is available, so a pool larger than
        # the free CPUs is waste; placement-group tasks draw on their
        # bundle reservation, zero-cpu tasks (e.g. PlacementGroup.ready()
        # pollers) run regardless of CPU pressure, and actors hold no CPU
        # — all three always need a process.  Concurrent startups are
        # capped (reference: worker_pool.h maximum_startup_concurrency
        # :192,717).
        n_pg = min(self._queued_pg, len(self.runnable_cpu))
        n_zero = len(self.runnable_zero)
        cpu_demand = min(len(self.runnable_cpu) - n_pg,
                         max(0, int(self.available.get("CPU", 0.0))))
        demand = cpu_demand + n_pg + n_zero + n_actors_waiting
        # cold spawns compete for CPU, so their concurrency is capped at
        # roughly core count; forks from the warm template cost ~ms and
        # can ramp much harder (reference: worker_pool.h:192,717)
        if self._prefork_conn is not None or self._prefork_ready():
            max_concurrent_startup = 16
        else:
            max_concurrent_startup = max(2, os.cpu_count() or 1)
        want = min(demand - idle - self._spawning,
                   self.config.max_workers - registered - self._spawning,
                   max_concurrent_startup - self._spawning)
        for _ in range(max(0, want)):
            self._spawning += 1
            self._spawn_worker_proc()

    def _spawn_worker_proc(self, container: Optional[dict] = None) -> None:
        if _fi._active is not None:
            # chaos plane: slow-spawn (the fork lands late) or a spawn
            # that silently dies; _audit_worker_pool self-heals the
            # in-flight counter either way, exactly as for a real
            # crashed spawn
            v = _fi._active.spawn_verdict(self)
            if v == "fail":
                return
            if type(v) is tuple:
                self.post_later(
                    v[1], lambda: self._do_spawn_worker(container))
                return
        self._do_spawn_worker(container)

    def _do_spawn_worker(self, container: Optional[dict] = None) -> None:
        logdir = os.path.join(self.session_dir, "logs")
        # monotone counter, NOT len(): pruning dead procs shrinks the
        # list and len() would hand a live worker's log index to a new
        # one (interleaved logs, wrong dashboard attribution)
        self._worker_seq = getattr(self, "_worker_seq", 0) + 1
        idx = self._worker_seq
        outp = os.path.join(logdir, f"worker-{idx}.out")
        errp = os.path.join(logdir, f"worker-{idx}.err")
        # containerized workers (runtime_env.container) always bypass
        # the prefork template: the child must be exec'd INSIDE the
        # image, and a fork of this host's pre-imported interpreter is
        # by definition not that (reference:
        # _private/runtime_env/container.py worker command wrapping)
        proc = None if container else self._fork_worker(outp, errp)
        if proc is None:
            env = self._worker_env()
            worker_cmd = [sys.executable, "-m", "ray_tpu.core.worker",
                          "--address", self.worker_address,
                          "--session", self.session]
            if container:
                from ray_tpu.runtime_env import container_command
                worker_cmd = container_command(container, worker_cmd,
                                               self.session_dir)
            out = open(outp, "ab", buffering=0)
            err = open(errp, "ab", buffering=0)
            proc = subprocess.Popen(
                worker_cmd,
                env=env, stdout=out, stderr=err, start_new_session=True)
            if container:
                self._container_spawning[container["image"]] = proc
        self._worker_procs.append(proc)
        # stack dumps / the dashboard log view need pid -> log mapping
        self._worker_log_by_pid[proc.pid] = (outp, errp)

    def _worker_env(self) -> dict:
        env = dict(os.environ)
        # Workers must not steal the TPU from the driver (one process
        # holds the chip): force CPU jax.
        env["JAX_PLATFORMS"] = "cpu"
        env.setdefault("XLA_FLAGS", "")
        env["RAY_TPU_SESSION"] = self.session
        # Propagate the driver's import path so functions/classes pickled
        # by reference (module-level defs in driver-side scripts) resolve
        # in workers — the minimal slice of the reference's runtime-env
        # working_dir propagation (reference:
        # python/ray/_private/runtime_env/working_dir.py capability).
        env["PYTHONPATH"] = os.pathsep.join(
            [p for p in sys.path if p] +
            [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
        return env

    # -- fork-server template (core/prefork.py)

    def _start_prefork_template(self) -> None:
        """Spawn the pre-imported worker template.  Non-blocking: the
        template warms up (~0.5 s) while the node finishes starting;
        until its socket accepts, spawns fall back to cold Popen."""
        logdir = os.path.join(self.session_dir, "logs")
        os.makedirs(logdir, exist_ok=True)
        self._prefork_path = os.path.join(self.session_dir, "prefork.sock")
        out = open(os.path.join(logdir, "prefork.out"), "ab", buffering=0)
        err = open(os.path.join(logdir, "prefork.err"), "ab", buffering=0)
        self._prefork_proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu.core.prefork",
             "--socket", self._prefork_path],
            env=self._worker_env(), stdout=out, stderr=err,
            start_new_session=True)

    def _prefork_ready(self) -> bool:
        if self._prefork_conn is not None:
            return True
        if (self._prefork_proc is None
                or self._prefork_proc.poll() is not None):
            return False
        import socket as _socket
        s = _socket.socket(_socket.AF_UNIX)
        s.settimeout(0.05)
        try:
            s.connect(self._prefork_path)
        except OSError:
            s.close()
            return False
        # short bound: this socket is read on the EVENT-LOOP thread, so
        # a wedged template must not stall scheduling for long — on
        # timeout we drop the template and cold-spawn instead
        s.settimeout(2.0)
        self._prefork_conn = s
        self._prefork_buf = b""
        return True

    def _fork_worker(self, outp: str, errp: str):
        """Request a forked worker from the template; None -> caller
        should cold-spawn instead."""
        if not self.config.prefork_workers or not self._prefork_ready():
            return None
        import json as _json
        try:
            req = {"address": self.worker_address,
                   "stdout": outp, "stderr": errp,
                   "env": {"RAY_TPU_SESSION": self.session}}
            self._prefork_conn.sendall(_json.dumps(req).encode() + b"\n")
            while b"\n" not in self._prefork_buf:
                chunk = self._prefork_conn.recv(4096)
                if not chunk:
                    raise OSError("prefork template closed")
                self._prefork_buf += chunk
            line, self._prefork_buf = self._prefork_buf.split(b"\n", 1)
            return _ForkedProc(_json.loads(line)["pid"])
        except (OSError, ValueError):
            try:
                self._prefork_conn.close()
            except OSError:
                pass
            self._prefork_conn = None
            return None

    def _h_worker_logs(self, rec, m):
        """List this node's worker log files, or tail one (reference:
        the dashboard's per-worker log viewer, dashboard/modules/log/)."""
        logdir = os.path.join(self.session_dir, "logs")
        name = m.get("name")
        if not name:
            files = []
            try:
                for f in sorted(os.listdir(logdir)):
                    full = os.path.join(logdir, f)
                    files.append({"name": f,
                                  "size": os.path.getsize(full)})
            except OSError:
                pass
            self._reply(rec, m["reqid"], files=files)
            return
        # basename only — no path escape out of the log dir
        path = os.path.join(logdir, os.path.basename(str(name)))
        nbytes = int(m.get("nbytes", 64 * 1024))
        try:
            with open(path, "rb") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                f.seek(max(0, size - nbytes))
                data = f.read()
            self._reply(rec, m["reqid"],
                        data=data.decode("utf-8", "replace"), size=size)
        except OSError as e:
            self._reply(rec, m["reqid"], error=str(e))

    def _h_profile_worker(self, rec, m):
        """Sampling-profile a live worker (reference: dashboard
        profile_manager.py py-spy wrapper): route the request to the
        worker's executor, which samples its own interpreter and pushes
        folded stacks back."""
        pid = int(m["pid"])
        target = next((c for c in self.clients.values()
                       if c.kind in ("worker", "tpu_executor")
                       and c.pid == pid), None)
        if target is None:
            self._reply(rec, m["reqid"],
                        error=f"no live worker with pid {pid}")
            return
        self._profile_seq = getattr(self, "_profile_seq", 0) + 1
        prof_id = self._profile_seq
        self._profile_pending = getattr(self, "_profile_pending", {})
        self._profile_pending[prof_id] = (rec.conn_id, m["reqid"])
        duration = float(m.get("duration", 2.0))
        self._push(target, {"t": "profile", "prof_id": prof_id,
                            "duration": duration,
                            "hz": float(m.get("hz", 99.0))})

        def expire():
            pend = self._profile_pending.pop(prof_id, None)
            if pend is not None:
                w = self.clients.get(pend[0])
                if w is not None:
                    self._reply(w, pend[1],
                                error="profile timed out (worker busy "
                                      "outside its message loop?)")
        self.post_later(duration + 30.0, expire)

    def _h_profile_result(self, rec, m):
        pend = getattr(self, "_profile_pending", {}).pop(
            m.get("prof_id"), None)
        if pend is None:
            return
        w = self.clients.get(pend[0])
        if w is None:
            return
        if m.get("error"):
            self._reply(w, pend[1], error=m["error"])
        else:
            self._reply(w, pend[1], folded=m.get("folded", ""))

    def _h_stack_dump(self, rec, m):
        """Dump a live worker's thread stacks (reference: `ray stack`,
        scripts.py:1767 / profile_manager.py): SIGUSR1 triggers the
        worker's faulthandler into its .err log; reply with the fresh
        tail."""
        pid = int(m["pid"])
        target = next((c for c in self.clients.values()
                       if c.kind == "worker" and c.pid == pid), None)
        logs = self._worker_log_by_pid.get(pid)
        if target is None or logs is None:
            self._reply(rec, m["reqid"],
                        error=f"no live spawned worker with pid {pid}")
            return
        err_path = logs[1]
        try:
            start = os.path.getsize(err_path)
        except OSError:
            start = 0
        try:
            os.kill(pid, signal.SIGUSR1)
        except OSError as e:
            self._reply(rec, m["reqid"], error=str(e))
            return

        def collect(attempt: int = 0, last: int = -1):
            # The dump is async — poll THIS worker's own .err for growth
            # (other workers' stderr chatter must not be misattributed),
            # then wait until it QUIESCES: faulthandler writes the
            # threads one at a time with the CURRENT thread (the one
            # executing the task) LAST, so replying on first growth
            # captured a partial dump missing exactly the frames the
            # caller wants (`ray stack` showed only the recv thread).
            try:
                size = os.path.getsize(err_path)
            except OSError:
                size = start
            if attempt < 40 and (size <= start or size != last):
                self.post_later(0.05, lambda: collect(attempt + 1, size))
                return
            if size <= start:
                self._reply(rec, m["reqid"],
                            error="worker produced no stack dump "
                                  "(faulthandler unavailable?)")
                return
            with open(err_path, "rb") as f:
                f.seek(start)
                data = f.read()
            self._reply(rec, m["reqid"], pid=pid,
                        data=data.decode("utf-8", "replace"),
                        log=os.path.basename(err_path))
        collect()
