"""Node providers: how the autoscaler creates and destroys nodes.

Reference capability: the NodeProvider interface
(reference: python/ray/autoscaler/node_provider.py:13,121 —
create_node / terminate_node / non_terminated_nodes / node lifecycle
tags).  A node here is a whole worker HOST running one NodeService
joined to the head (on TPU pods: one host of a slice).
"""

from __future__ import annotations

import os
import subprocess
import sys
import uuid
from dataclasses import dataclass, field
from typing import Optional


@dataclass
class NodeStatus:
    node_id: str
    status: str          # pending | running | terminated
    metadata: dict = field(default_factory=dict)


class NodeProvider:
    """Provider contract (reference: node_provider.py NodeProvider)."""

    def create_node(self, head_address: str, node_config: dict) -> str:
        """Launch one node joined to `head_address`; returns provider
        node id (the node registers itself with the head
        asynchronously)."""
        raise NotImplementedError

    def terminate_node(self, node_id: str) -> None:
        raise NotImplementedError

    def non_terminated_nodes(self) -> list[NodeStatus]:
        raise NotImplementedError

    def shutdown(self) -> None:
        for n in self.non_terminated_nodes():
            self.terminate_node(n.node_id)


class LocalNodeProvider(NodeProvider):
    """Nodes as local NodeService subprocesses — the test/dev provider
    (reference analogue: autoscaler/_private/fake_multi_node/
    node_provider.py, the multi-node-on-one-machine provider)."""

    def __init__(self, base_dir: Optional[str] = None):
        self._procs: dict[str, subprocess.Popen] = {}
        self._base = base_dir or os.path.join(
            "/tmp/ray_tpu", f"autoscale_{uuid.uuid4().hex[:8]}")
        os.makedirs(self._base, exist_ok=True)
        # pid files make nodes findable across provider INSTANCES — the
        # launcher's `down` runs in a fresh process and must still reap
        # what `up` started
        self._n = self._next_index()

    def _next_index(self) -> int:
        import glob
        mx = 0
        for p in glob.glob(os.path.join(self._base, "*.pid")):
            tail = os.path.basename(p).rsplit("-", 1)[-1][:-4]
            if tail.isdigit():
                mx = max(mx, int(tail))
        return mx

    def _write_pid(self, node_id: str, pid: int) -> None:
        with open(os.path.join(self._base, f"{node_id}.pid"), "w") as f:
            f.write(str(pid))

    def _read_pid(self, node_id: str) -> Optional[int]:
        try:
            with open(os.path.join(self._base, f"{node_id}.pid")) as f:
                return int(f.read().strip())
        except (FileNotFoundError, ValueError):
            return None

    def _drop_pid(self, node_id: str) -> None:
        try:
            os.unlink(os.path.join(self._base, f"{node_id}.pid"))
        except FileNotFoundError:
            pass

    def create_node(self, head_address: str, node_config: dict) -> str:
        self._n += 1
        node_id = f"local-{self._n:03d}"
        # distinct session prefix => distinct shm arena (arena name is
        # derived from session[:8])
        session = f"a{self._n:03d}{uuid.uuid4().hex[:8]}"
        # --port 0: several nodes share this host, and the service's own
        # default (6379) let only ONE of them bind, on the whole machine:
        # two tests that launched a local node at once took turns failing
        # with "no alive node joined the launched head" (PR 43)
        args = [sys.executable, "-m", "ray_tpu.core.node",
                "--port", "0",
                "--head-address", head_address,
                "--session", session,
                "--session-dir", os.path.join(self._base, node_id),
                "--label", f"provider_node_id={node_id}"]
        if node_config.get("num_cpus") is not None:
            args += ["--num-cpus", str(node_config["num_cpus"])]
        if node_config.get("num_tpus"):
            args += ["--num-tpus", str(node_config["num_tpus"])]
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [p for p in sys.path if p]
            + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
        log = open(os.path.join(self._base, f"{node_id}.log"), "ab")
        self._procs[node_id] = subprocess.Popen(
            args, env=env, stdout=log, stderr=log, start_new_session=True)
        self._write_pid(node_id, self._procs[node_id].pid)
        return node_id

    def create_head(self, node_config: dict, port: int = 0
                    ) -> tuple[str, str]:
        """Local head process for the launcher's `local` provider type:
        spawn a head service, read its address from the ready file."""
        self._n += 1
        node_id = f"local-head-{self._n:03d}"
        addr_file = os.path.join(self._base, f"{node_id}.addr")
        args = [sys.executable, "-m", "ray_tpu.core.head",
                "--port", str(port), "--address-file", addr_file]
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [p for p in sys.path if p]
            + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
        log = open(os.path.join(self._base, f"{node_id}.log"), "ab")
        self._procs[node_id] = subprocess.Popen(
            args, env=env, stdout=log, stderr=log, start_new_session=True)
        self._write_pid(node_id, self._procs[node_id].pid)
        import time as _t
        deadline = _t.monotonic() + 30
        while _t.monotonic() < deadline:
            try:
                with open(addr_file) as f:
                    addr = f.read().strip()
                if addr:
                    return node_id, addr
            except FileNotFoundError:
                pass
            _t.sleep(0.1)
        raise RuntimeError("local head did not publish its address")

    def exec_on(self, node_id: str, command: str,
                all_workers: bool = False) -> str:
        proc = subprocess.run(["sh", "-c", command], capture_output=True,
                              text=True, timeout=600)
        if proc.returncode != 0:
            raise RuntimeError(f"exec failed ({proc.returncode}): "
                               f"{proc.stderr[-1000:]}")
        return proc.stdout

    def ssh_command(self, node_id: str) -> list[str]:
        return ["sh"]   # "attach" to a local cluster is just a shell

    def terminate_node(self, node_id: str) -> None:
        import signal as _signal
        import time as _t
        p = self._procs.pop(node_id, None)
        if p is not None:
            p.terminate()
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
            self._drop_pid(node_id)
            return
        pid = self._read_pid(node_id)     # started by another process
        if pid is None:
            return
        for sig in (_signal.SIGTERM, _signal.SIGKILL):
            try:
                os.kill(pid, sig)
            except ProcessLookupError:
                break
            deadline = _t.monotonic() + 8
            while _t.monotonic() < deadline:
                try:
                    os.kill(pid, 0)
                except ProcessLookupError:
                    break
                _t.sleep(0.1)
            else:
                continue
            break
        self._drop_pid(node_id)

    def non_terminated_nodes(self) -> list[NodeStatus]:
        import glob
        out = []
        seen = set()
        for nid, p in list(self._procs.items()):
            seen.add(nid)
            if p.poll() is None:
                out.append(NodeStatus(nid, "running", {"pid": p.pid}))
            else:
                self._procs.pop(nid, None)
                self._drop_pid(nid)
        for path in glob.glob(os.path.join(self._base, "*.pid")):
            nid = os.path.basename(path)[:-4]
            if nid in seen:
                continue
            pid = self._read_pid(nid)
            alive = False
            if pid is not None:
                try:
                    os.kill(pid, 0)
                    alive = True
                except (ProcessLookupError, PermissionError):
                    pass
            if alive:
                out.append(NodeStatus(nid, "running", {"pid": pid}))
            else:
                self._drop_pid(nid)
        return out
