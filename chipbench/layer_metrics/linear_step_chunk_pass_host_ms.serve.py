"""The HOST's part of ``linear_step_chunk_pass_ms.serve``'s pass (ms),
untraced: the loop thread's time in every phase but ``wait``, as
``step_chunk_pass_host_ms.serve`` reads it where it is listed.  With
``linear_step_chunk_pass_wait_ms.serve`` it adds up to the pass."""

from chipbench import pass_ledger


def read(obs):
    return pass_ledger.kind_ms_per_pass(obs, "step_chunk", "host_ns")
