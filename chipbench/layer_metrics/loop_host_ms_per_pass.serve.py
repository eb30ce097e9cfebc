"""The host's own work in a scheduler pass (ms), UNTRACED: the engine
loop's self time in every phase but ``wait`` (the wait for the device)
and ``parked`` (no work), plus what no phase covers, over the passes,
from the loop's always-on account (``chipbench/loop_account.py``) over
the part of the window no profiler session touched.  With the wait's
share it accounts for the pass a client sees; the traced
``engine_host_ms_per_pass.serve`` reads the same work stretched by the
profiler's Python hooks."""

from chipbench import loop_account


def read(obs):
    acct = loop_account.read(obs)
    if acct is None or not acct["passes"]:
        return None
    return loop_account.ms_per_pass(
        acct, [p for p in acct["ns"] if p not in loop_account.NOT_HOST])
