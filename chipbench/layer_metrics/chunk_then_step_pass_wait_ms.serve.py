"""The WAIT of a pass that ran chunk programs and then the plain step
(ms), untraced: of ``chunk_then_step_pass_ms.serve``'s pass, the loop
thread's time in ``wait``, blocked until what it launched has landed
(the chunk programs' and the step's device time, launch latency, the
thread's wake-up); a mean over the window's passes of kind
``chunk+step`` (``by_kind``'s ``wait_ns``, ``chipbench/pass_ledger.py``)."""

from chipbench import pass_ledger


def read(obs):
    return pass_ledger.kind_ms_per_pass(obs, "chunk+step", "wait_ns")
