"""Share of the loop's wall time in which it had NO program in flight
on the device (%), untraced: the starved time of every phase but
``parked`` (an engine idle for want of requests is nobody's fault) and
of what no phase covers, over the wall time of the intervals read
(``chipbench/loop_account.py``).  A program is in flight from where its
dispatch returns to where the fetch that reads the newest result
returns.  With a synchronous loop that is everything but the wait; once
a step is launched behind the one in flight it is what that has left.
It lies under the trace's ``device_idle_share.serve`` by the launch
latency and the idle inside a program."""

from chipbench import loop_account


def read(obs):
    acct = loop_account.read(obs)
    if acct is None or not acct["wall_ns"]:
        return None
    starved = sum(ns for phase, ns in acct["starved_ns"].items()
                  if phase != "parked")
    return 100.0 * starved / acct["wall_ns"]
