"""The HOST's part of a pass that ran the fused step+chunk program alone
(ms), untraced: of ``step_chunk_pass_ms.serve``'s pass, the loop
thread's time in every phase but ``wait`` (``unaccounted`` and the
loop's turn-around too), a mean over the window's passes of kind
``step_chunk`` (``by_kind``'s ``host_ns``, ``chipbench/pass_ledger.py``).
What a PR that moves ``itl_p95_ms`` from the host's side moves: the
chunk's preparation, packing, dispatch, the row loop.  With
``step_chunk_pass_wait_ms.serve`` it adds up to the pass exactly."""

from chipbench import pass_ledger


def read(obs):
    return pass_ledger.kind_ms_per_pass(obs, "step_chunk", "host_ns")
