"""The WAIT of a pass that ran the fused step+chunk program alone (ms),
untraced: of ``step_chunk_pass_ms.serve``'s pass, the loop thread's
time in ``wait``, blocked until the launched program has landed: the
program's device time plus launch latency and the thread's wake-up; a
mean over the window's passes of kind ``step_chunk`` (``by_kind``'s
``wait_ns``, ``chipbench/pass_ledger.py``).  What a PR that moves
``itl_p95_ms`` from the device's side moves."""

from chipbench import pass_ledger


def read(obs):
    return pass_ledger.kind_ms_per_pass(obs, "step_chunk", "wait_ns")
