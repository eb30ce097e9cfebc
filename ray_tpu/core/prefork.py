"""Fork-server ("zygote") worker template.

The reference raylet amortizes worker startup with prestarted pool
processes and a startup-concurrency cap (reference:
src/ray/raylet/worker_pool.h:352 PrestartWorkers, :192).  On this
framework's hosts the dominant spawn cost is interpreter + import time
(a cold python plus the worker module graph is seconds); the fork
server pays it once: the template pre-imports the worker's module
graph, then forks a ready worker per request in milliseconds.

Protocol: the node service connects to the template's unix socket and
sends one JSON line per worker request
``{"address": ..., "stdout": path, "stderr": path, "env": {...}}``;
the template forks and replies ``{"pid": N}``.  Lifecycle ties: the
template exits when the control connection closes (node death leaves
no orphan template), and each child exits when its node connection
drops (normal worker behavior).

The template stays single-threaded and never connects to the node
itself, so fork() is safe: no locks can be mid-held, no recv threads
are lost in children.
"""

from __future__ import annotations

import argparse
import json
import os
import select
import signal
import socket
import sys


def _reap_children() -> None:
    """Collect exited workers so they don't sit as zombies (children of
    the template, not of the node service)."""
    while True:
        try:
            pid, _ = os.waitpid(-1, os.WNOHANG)
        except ChildProcessError:
            return
        if pid == 0:
            return


def _child(conn: socket.socket, req: dict) -> None:
    """Runs in the forked worker.  Never returns."""
    try:
        conn.close()
        os.setsid()
        out = os.open(req["stdout"],
                      os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        err = os.open(req["stderr"],
                      os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        os.dup2(out, 1)
        os.dup2(err, 2)
        os.close(out)
        os.close(err)
        os.environ.update(req.get("env") or {})
        from ray_tpu.core.worker import run_worker
        run_worker(req["address"])
        code = 0
    except BaseException:
        import traceback
        traceback.print_exc()
        code = 1
    finally:
        # _exit: the template's inherited atexit hooks / buffered state
        # must not run in the child
        os._exit(code)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--socket", required=True)
    args = ap.parse_args()
    # who to watch for (below), read BEFORE the second of imports: a
    # node that died meanwhile left a template that took its adopter
    # for its parent and stayed for good
    parent = os.getppid()

    # Pre-import the worker's dependency graph — the whole point of the
    # template.  Everything a worker touches before user code: client,
    # executor, serialization, runtime, numpy + the ctypes-based native
    # store binding (~0.25 s each, measured — at 24 concurrent children
    # on one core the un-preimported tail serializes into seconds).
    # NOT jax: its runtime may spawn threads, which don't survive
    # fork; workers lazily import jax pinned to CPU by JAX_PLATFORMS.
    import numpy                          # noqa: F401
    import ray_tpu.core.worker            # noqa: F401
    import ray_tpu.core.runtime           # noqa: F401
    import ray_tpu.core.remote_function   # noqa: F401
    import ray_tpu.core.device_objects    # noqa: F401
    import ray_tpu.runtime_env            # noqa: F401
    try:
        import ray_tpu.native.store       # noqa: F401
    except Exception:
        pass   # native store optional; workers fall back to shm
    from ray_tpu.core.serialization import get_context
    get_context()   # build the serde tables once (thread-free)

    lst = socket.socket(socket.AF_UNIX)
    try:
        os.unlink(args.socket)
    except FileNotFoundError:
        pass
    lst.bind(args.socket)
    lst.listen(1)
    # The node may die (SIGKILL, no cleanup) before ever connecting —
    # a plain accept() would orphan this template forever.  Poll for
    # reparenting (our parent IS the node service process).
    lst.settimeout(1.0)
    while True:
        try:
            conn, _ = lst.accept()
            break
        except socket.timeout:
            if os.getppid() != parent:
                sys.exit(0)     # orphaned before first connection
    lst.close()
    signal.signal(signal.SIGCHLD, signal.SIG_DFL)
    conn.setblocking(False)

    buf = b""
    while True:
        ready, _, _ = select.select([conn], [], [], 1.0)
        _reap_children()
        if not ready:
            continue
        try:
            chunk = conn.recv(1 << 16)
        except BlockingIOError:
            continue
        except OSError:
            break
        if not chunk:
            break   # node closed the control connection: we're done
        buf += chunk
        while b"\n" in buf:
            line, buf = buf.split(b"\n", 1)
            if not line.strip():
                continue
            req = json.loads(line)
            pid = os.fork()
            if pid == 0:
                _child(conn, req)
            try:
                conn.sendall(json.dumps({"pid": pid}).encode() + b"\n")
            except OSError:
                break
    _reap_children()
    sys.exit(0)


if __name__ == "__main__":
    main()
