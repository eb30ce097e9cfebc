"""Launching a pass's programs (ms), untraced: the loop's self time in
``dispatch`` (the jitted calls' host side: argument handling, the
executable's launch) over the passes, from the account's intervals no
profiler session touched (``chipbench/pass_ledger.py``).  The largest
phase of the host in every serving cell; what stacked parameters or a
launch ahead of the fetch would move."""

from chipbench import pass_ledger


def read(obs):
    led = pass_ledger.engine(obs)
    if led is None or not led["passes"]:
        return None
    return led["ns"]["dispatch"] / led["passes"] / 1e6
