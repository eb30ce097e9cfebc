"""The HOST's part of a pass that ran chunk programs and then the plain
step (ms), untraced: of ``chunk_then_step_pass_ms.serve``'s pass, the
loop thread's time in every phase but ``wait`` (``unaccounted`` and the
loop's turn-around too), a mean over the window's passes of kind
``chunk+step`` (``by_kind``'s ``host_ns``, ``chipbench/pass_ledger.py``).
With ``chunk_then_step_pass_wait_ms.serve`` it adds up to the pass
exactly."""

from chipbench import pass_ledger


def read(obs):
    return pass_ledger.kind_ms_per_pass(obs, "chunk+step", "host_ns")
