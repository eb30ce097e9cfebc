"""A pass that ran the decode step alone (ms), untraced: the mean over
the window's passes of kind ``step`` of the pass's whole time on the
loop thread, from where the pass before it ended to its own end
(``chipbench/pass_ledger.py``): what the median gap is made of.
``decode_program_ms.serve`` is the device's part of it, read from the
trace; ``decode_pass_ms.serve`` the same pass under the profiler."""

from chipbench import pass_ledger


def read(obs):
    return pass_ledger.kind_ms_per_pass(obs, "step")
