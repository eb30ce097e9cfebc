"""The program's own spans, for the per-layer metrics that read them.

``ray_tpu/util/tracing.py`` keeps finished spans in an in-memory ring:
dicts with ``name``, ``t0_ns`` / ``t1_ns`` (``time.monotonic_ns()``,
the clock of ``run.py``'s ``T_START``), ``span_id`` / ``parent_id`` /
``trace_id`` and ``attributes``.  A reader runs in the process that ran
the system, after it was shut down, so the ring is still there.

Request spans (``front.request``, ``request.queue`` / ``.prefill`` /
``.decode``) are written always; pass-level spans (``engine.*``,
``train.*``) only while tracing is on or a ``jax.profiler`` session is
active — in a ``--trace 1`` run exactly over the interval the device
trace covers.

A program without such spans (a parent commit) gives an empty list, and
every reader then returns None.
"""

from __future__ import annotations

import sys
from collections import defaultdict

from chipbench import stats

REQUEST_STAGES = ("request.queue", "request.prefill", "request.decode")


def finished_spans(obs: dict) -> list:
    """``obs["spans"]`` where a caller hands the spans over (the tests
    do), else the program's ring."""
    if "spans" in obs:
        return list(obs["spans"])
    try:
        from ray_tpu.util import tracing
        spans = tracing.get_finished_spans()
    except (ImportError, AttributeError):
        return []
    return [s for s in spans if "t0_ns" in s]


def window_ns(obs: dict):
    """The measured window on the spans' clock, or None: it starts
    ``setup_s`` after ``T_START`` (both traffic kinds define ``setup_s``
    so) and lasts ``window_s``.  ``T_START`` is ``obs["t_start"]`` if
    given, else the module global of ``run.py`` running as
    ``__main__``."""
    t_start = obs.get("t_start",
                      getattr(sys.modules.get("__main__"), "T_START", None))
    setup_s = (obs.get("end_to_end") or {}).get("setup_s")
    if t_start is None or setup_s is None or "window_s" not in obs:
        return None
    t0 = t_start + setup_s
    return int(t0 * 1e9), int((t0 + obs["window_s"]) * 1e9)


def ms(span: dict) -> float:
    return (span["t1_ns"] - span["t0_ns"]) / 1e6


def named(spans: list, name: str, window=None) -> list:
    """Spans of one name, oldest first; with a window only those that
    started in it."""
    return sorted((s for s in spans if s["name"] == name and (
        window is None or window[0] <= s["t0_ns"] <= window[1])),
        key=lambda s: s["t0_ns"])


def window_requests(obs: dict) -> list:
    """One dict per request SUBMITTED in the window: its lifecycle spans
    by name, and ``front.request`` where the request came through the
    serve front.  Spans of one request share a trace id."""
    window = window_ns(obs)
    if window is None:
        return []
    by_trace = defaultdict(dict)
    for s in finished_spans(obs):
        if s["name"] in REQUEST_STAGES or s["name"] == "front.request":
            by_trace[s["trace_id"]][s["name"]] = s
    return [r for r in by_trace.values() if "request.queue" in r
            and window[0] <= r["request.queue"]["t0_ns"] <= window[1]]


def p90_ms(values: list):
    return stats.percentile(values, 90) if values else None


def whole_passes(obs: dict) -> list:
    """(pass, its descendants) for every ``engine.pass`` recorded, the
    newest left out: the end of a profiler session may have cut it
    (spans opened after the session's end record nothing)."""
    spans = finished_spans(obs)
    passes = named(spans, "engine.pass", window_ns(obs))[:-1]
    if not passes:
        return []
    root_of = {p["span_id"]: p["span_id"] for p in passes}
    inside = defaultdict(list)
    # a span starts no earlier than its parent (the outer one first on
    # a tie), so one sweep in start order finds every descendant
    for s in sorted(spans, key=lambda s: (s["t0_ns"], -s["t1_ns"])):
        root = root_of.get(s.get("parent_id"))
        if root is not None and s["name"].startswith("engine."):
            root_of[s["span_id"]] = root
            inside[root].append(s)
    return [(p, inside[p["span_id"]]) for p in passes]
