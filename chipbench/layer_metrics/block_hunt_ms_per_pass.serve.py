"""The scheduler's two turns in a pass (ms), untraced: the loop's self
time in ``admit`` (under the lock: cross-thread ops, reaping,
admission) and ``grow`` (a block for every row that crosses a block
boundary: the hunt through the radix index when the free list is
empty), over the passes (``chipbench/loop_account.py``).  The hybrid
cells keep no index and are its control."""

from chipbench import loop_account


def read(obs):
    acct = loop_account.read(obs)
    if acct is None or not acct["passes"]:
        return None
    return loop_account.ms_per_pass(acct, ("admit", "grow"))
