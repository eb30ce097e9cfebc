"""Handing a pass's tokens out (ms), untraced: the loop's self time in
``emit`` (the loop over the live rows: each token to its request's
mailbox and its waiting stream, a finished row's eviction), over the
passes (``chipbench/loop_account.py``).  It grows with the live rows."""

from chipbench import loop_account


def read(obs):
    acct = loop_account.read(obs)
    if acct is None or not acct["passes"]:
        return None
    return loop_account.ms_per_pass(acct, ("emit",))
