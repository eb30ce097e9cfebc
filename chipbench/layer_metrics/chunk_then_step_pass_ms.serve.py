"""A pass that ran chunk programs on their own and then the plain decode
step (ms), untraced: the mean over the window's passes of kind
``chunk+step`` of the pass's whole time on the loop thread
(``chipbench/pass_ledger.py``).  Where no program runs a chunk and the
step together (a latent, delta-rule or window-attention layout) this is
the pass the p95 gap is made of: every decoding row waits for the chunk
and the step.  None where no such pass ended, or for a program without
the account by kind."""

from chipbench import pass_ledger


def read(obs):
    return pass_ledger.kind_ms_per_pass(obs, "chunk+step")
