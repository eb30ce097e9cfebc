"""A pass that ran the fused step+chunk program alone in a layout with
linear-attention sublayers (ms), untraced: ``step_chunk_pass_ms.serve``'s
reading (the mean whole time on the loop thread of the window's passes
of kind ``step_chunk``, ``chipbench/pass_ledger.py``) for the cell that
metric does not list.  Since the delta rule has the two-part form this is
the pass the cell's p95 gap is made of, where ``chunk_then_step_pass_ms.
serve``'s was.  None where no such pass ended (a parent commit)."""

from chipbench import pass_ledger


def read(obs):
    return pass_ledger.kind_ms_per_pass(obs, "step_chunk")
