"""Share of the window's admissions whose row took its recurrent state
from an adopted block's snapshot (%): the engine's ``state_snapshots_
restored`` (once a request: at its admission, or at the re-match before
a chunk) over ``admissions``, both as window deltas.  The rest started
from a zero state and prefilled their whole prompt.  A program that
keeps no snapshots (a parent commit, a layout whose state has no
snapshot form) counts none: None."""


def read(obs):
    c = obs.get("counters") or {}
    if not c.get("admissions") or "state_snapshots_restored" not in c:
        return None
    return 100.0 * c["state_snapshots_restored"] / c["admissions"]
