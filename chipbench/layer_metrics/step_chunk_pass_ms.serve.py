"""A pass that ran the fused step+chunk program alone (ms), untraced:
the mean over the window's passes of kind ``step_chunk`` of the pass's
whole time on the loop thread, from where the pass before it ended to
its own end: the program's wait, the host's work around it and the
loop's turn-around (``chipbench/pass_ledger.py``; the kinds:
``ray_tpu/inference/engine.py`` ``_PASS_KIND``).  Every decoding row
waits exactly this for its token; in the cells with the fused program
it is the pass the p95 gap is made of.  None for a model without the
program, or a program without the account by kind."""

from chipbench import pass_ledger


def read(obs):
    return pass_ledger.kind_ms_per_pass(obs, "step_chunk")
