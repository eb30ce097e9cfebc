"""The engine loop's own time account, for the per-layer metrics that
read it.

The serving engine counts its loop thread's wall time by phase at its
span sites, with no tracing on (``ray_tpu/util/tracing.py``
``Account``; the phases: ``ray_tpu/inference/engine.py``
``_LOOP_PHASES``), and about once a second of that time writes the
counters so far into the ring as ONE always-on ``engine.account`` span
that starts where the last one ended.  Its attributes: ``engine``;
cumulative ``ns`` / ``starved_ns`` / ``count`` by phase (self time; the
part of it with no program in flight on the device; entries),
``unaccounted_ns`` / ``unaccounted_starved_ns`` (what no site covers),
``passes``; ``profiling``, whether a ``jax.profiler`` session touched
the interval.

A metric differences consecutive spans of one engine whose ends lie in
the measured window and sums the intervals NO session touched: in a
``--trace 1`` run all but the traced seconds, so the host is read where
the profiler does not stretch it.  A program without such spans (a
parent commit) gives None.
"""

from __future__ import annotations

from collections import defaultdict

from chipbench import spans

NAME = "engine.account"
COUNTED = ("ns", "starved_ns")
# not the loop's work: the wait for the device, an engine with no work
NOT_HOST = ("wait", "parked")


def intervals(obs: dict) -> list:
    """(earlier, later) for every two consecutive account spans of one
    engine that both END in the window; a pair the ring has lost a span
    between (``later`` does not start where ``earlier`` ended) is left
    out, since nothing says whether a session touched it."""
    window = spans.window_ns(obs)
    if window is None:
        return []
    by_engine = defaultdict(list)
    for s in spans.finished_spans(obs):
        if s["name"] == NAME and window[0] <= s["t1_ns"] <= window[1]:
            by_engine[s["attributes"]["engine"]].append(s)
    out = []
    for chain in by_engine.values():
        chain.sort(key=lambda s: s["t1_ns"])
        out += [(a, b) for a, b in zip(chain, chain[1:])
                if b["t0_ns"] == a["t1_ns"]]
    return out


def total(pairs: list):
    """What the loop spent over ``pairs``: ``wall_ns``, ``passes``,
    ``ns`` / ``starved_ns`` by phase (``unaccounted`` among them); None
    for no pairs."""
    if not pairs:
        return None
    out = {"wall_ns": 0, "passes": 0,
           **{k: defaultdict(int) for k in COUNTED}}
    for a, b in pairs:
        out["wall_ns"] += b["t1_ns"] - b["t0_ns"]
        a, b = a["attributes"], b["attributes"]
        out["passes"] += b["passes"] - a["passes"]
        for k in COUNTED:
            for phase, ns in b[k].items():
                out[k][phase] += ns - a[k][phase]
            out[k]["unaccounted"] += (b["unaccounted_" + k]
                                      - a["unaccounted_" + k])
    return out


def read(obs: dict):
    """The window's account over the intervals no profiler session
    touched, or None."""
    return total([(a, b) for a, b in intervals(obs)
                  if not b["attributes"]["profiling"]])


def ms_per_pass(acct, phases) -> float:
    return sum(acct["ns"][p] for p in phases) / acct["passes"] / 1e6
