"""Worker process entry point.

The analogue of the reference's default_worker.py (reference:
python/ray/_private/workers/default_worker.py + worker.py main_loop:764):
connect to the node service, register, and block in the execution loop.
Spawned by the node service's worker pool (JAX forced to CPU so the driver
keeps TPU ownership — see node.py _spawn_worker_proc).
"""

from __future__ import annotations

import argparse
import sys


def run_worker(address: str) -> None:
    """Connect to the node service and block in the execution loop.
    Shared by the cold-spawn path (main below) and the fork-server
    children (core/prefork.py)."""
    # Workers must not touch the TPU (the driver owns it — one process
    # per chip).  The spawner sets JAX_PLATFORMS=cpu in this process's
    # environment (node_workers._worker_env), which jax reads when user
    # code first imports it; jax is not imported here (it would add
    # ~1-2s spawn latency for pure-CPU workloads).

    # on-demand stack dumps (reference: `ray stack` /
    # dashboard/modules/reporter/profile_manager.py): SIGUSR1 makes the
    # worker write every thread's stack to its .err log, even mid-task
    import faulthandler
    import signal
    try:
        faulthandler.register(signal.SIGUSR1, file=sys.stderr,
                              all_threads=True)
    except (AttributeError, ValueError):
        pass   # non-POSIX or non-main-thread: dumps unavailable

    from ray_tpu.core import fault_injection, flight_recorder
    from ray_tpu.core.client import NodeClient
    from ray_tpu.core.executor import (Executor, make_message_queue,
                                       queue_push_handler)
    from ray_tpu.core import runtime as rt

    fault_injection.autoinstall_from_env()   # chaos plane in workers
    flight_recorder.autoenable_from_env()    # lifecycle stamps in workers

    inbox = make_message_queue()
    cell: dict = {}
    client = NodeClient(address, kind="worker",
                        push_handler=queue_push_handler(inbox, cell))
    cell["client"] = client
    executor = Executor(client, msg_queue=inbox, threaded_actors=True)

    # Make the public API (ray_tpu.get/put/remote/...) work inside tasks.
    rt.attach_worker_runtime(client, executor)

    try:
        executor.run_loop()
    except KeyboardInterrupt:
        pass
    finally:
        client.close()


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--address", required=True)
    parser.add_argument("--session", required=True)
    args = parser.parse_args()
    run_worker(args.address)
    sys.exit(0)


if __name__ == "__main__":
    main()
