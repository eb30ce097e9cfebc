"""The WAIT of ``linear_step_chunk_pass_ms.serve``'s pass (ms), untraced:
the loop thread blocked until the ONE launched program has landed, as
``step_chunk_pass_wait_ms.serve`` reads it where it is listed: the fused
program's device time plus one launch's latency."""

from chipbench import pass_ledger


def read(obs):
    return pass_ledger.kind_ms_per_pass(obs, "step_chunk", "wait_ns")
