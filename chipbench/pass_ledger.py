"""What a token's gap is made of, read inside the program with no
profiler on: the engine loop's account by KIND of pass, the gaps as the
engine emits them and as the serve front writes them, the engine's whole
counter table, and the first token's hops.

Beside the phases that ``chipbench/loop_account.py`` reads, an
``engine.account`` span carries (``ray_tpu/util/tracing.py`` ``Account``,
``Histogram``; ``ray_tpu/inference/engine.py`` ``_pass_ended``), all
cumulative: ``by_kind`` {kind of pass: ``count``, ``ns`` = ``host_ns`` +
``wait_ns``, ``tokens``} (the kinds: ``engine._PASS_KIND``, and ``idle``,
the loop's time with no work; a pass's time runs from the last pass's
end, or from where the loop left ``parked``, to its own end);
``gaps`` {bucket: weight}, a histogram of the passes' times, each
weighted by the tokens the pass emitted to rows that already had one;
``counters``, every counter of ``ray_tpu/serve/engine_stats.py``.  The
serve front writes a chain of the same form, ``front.account``
(``ray_tpu/serve/asgi.py`` ``_chunk_written``): ``proxy`` and
``write_gaps`` {bucket: count} of the time between a streamed response's
consecutive chunks, written and drained.  A bucket's
edges are ``tracing.Histogram.edge_ns``'s: no copy of the layout here.
``request.decode`` carries ``first_yield_ns``, when the request's
``stream()`` read the first token: with ``request.prefill``'s end (the
engine emitted it) and ``front.request``'s ``first_chunk_ns`` (written
and drained) it splits the front's share of the first token in two.

Everything is differenced over consecutive spans of one chain whose
ends lie in the measured window; by default over the intervals NO
profiler session touched, so a ``--trace 1`` run reads the program where
the profiler does not stretch it.  A program without the spans or the
keys (a parent commit) gives None.
"""

from __future__ import annotations

from collections import defaultdict

from chipbench import loop_account, spans

FRONT = "front.account"


def _minus(later: dict, earlier: dict) -> dict:
    """Two cumulative {key: count}, differenced; a key may have come
    through JSON as text."""
    earlier = {int(k): v for k, v in earlier.items()}
    return {int(k): v - earlier.get(int(k), 0) for k, v in later.items()}


def _add(total: dict, delta: dict) -> None:
    for k, v in delta.items():
        total[k] += v


def engine(obs: dict, every: bool = False):
    """The engine chain's growth over the window: ``passes``, ``ns`` by
    phase, ``by_kind`` {kind: {field: n}}, ``gaps`` {bucket: weight},
    ``counters`` {key: n}; over the intervals no session touched, or
    ``every`` chained one (a count is not stretched).  None without
    such intervals or without the keys."""
    pairs = [(a, b) for a, b in loop_account.intervals(obs)
             if every or not b["attributes"]["profiling"]]
    if not pairs or any("by_kind" not in s["attributes"]
                        for pair in pairs for s in pair):
        return None
    out = {"passes": 0, "ns": defaultdict(int), "gaps": defaultdict(int),
           "counters": defaultdict(int),
           "by_kind": defaultdict(lambda: defaultdict(int))}
    for a, b in pairs:
        a, b = a["attributes"], b["attributes"]
        out["passes"] += b["passes"] - a["passes"]
        for phase, ns in b["ns"].items():
            out["ns"][phase] += ns - a["ns"][phase]
        _add(out["gaps"], _minus(b["gaps"], a["gaps"]))
        for key, n in b["counters"].items():
            out["counters"][key] += n - a["counters"][key]
        for kind, row in b["by_kind"].items():
            was = a["by_kind"].get(kind, {})
            for field, n in row.items():
                out["by_kind"][kind][field] += n - was.get(field, 0)
    return out


def kind_ms_per_pass(obs: dict, kind: str, part: str = "ns"):
    """A kind's mean pass (ms), or of it the ``host_ns`` / ``wait_ns``
    ``part``; None where no such pass ended."""
    led = engine(obs)
    row = led and led["by_kind"].get(kind)
    if not row or not row["count"]:
        return None
    return row[part] / row["count"] / 1e6


def front_gaps(obs: dict):
    """The front chains' ``write_gaps`` {bucket: count} as they grew
    over the window's intervals NO session touched, all proxies
    together; None without such intervals."""
    window = spans.window_ns(obs)
    if window is None:
        return None
    by_proxy = defaultdict(list)
    for s in spans.finished_spans(obs):
        if s["name"] == FRONT and window[0] <= s["t1_ns"] <= window[1]:
            by_proxy[s["attributes"]["proxy"]].append(s)
    out = None
    for chain in by_proxy.values():
        chain.sort(key=lambda s: s["t1_ns"])
        for a, b in zip(chain, chain[1:]):
            # a pair the ring has lost a span between is left out
            if b["t0_ns"] == a["t1_ns"] and not b["attributes"]["profiling"]:
                out = defaultdict(int) if out is None else out
                _add(out, _minus(b["attributes"]["write_gaps"],
                                 a["attributes"]["write_gaps"]))
    return out


def quantile_ms(hist: dict, q: float):
    """The ``q``-th percentile (ms) of a differenced histogram, by
    linear interpolation inside the bucket that holds it (the last
    bucket has no end: its start); None for an empty one."""
    from ray_tpu.util.tracing import Histogram
    total = sum(hist.values())
    if total <= 0:
        return None
    rank, below = q / 100 * total, 0
    for i in sorted(hist):
        if hist[i] and below + hist[i] >= rank:
            lo = Histogram.edge_ns(i)
            hi = Histogram.edge_ns(i + 1) if i + 1 < Histogram.N else lo
            return (lo + (hi - lo) * (rank - below) / hist[i]) / 1e6
        below += hist[i]
    return None


def first_tokens(obs: dict) -> list:
    """The first token's two hops through the front, ms: ``wake`` (the
    engine's emit, ``request.prefill``'s end, to the request's
    ``stream()`` awake with the token) and ``write`` (awake to the first
    chunk written and drained); one dict a request submitted in the
    window that came through the front, whose stream read its first
    token before it finished, and whose first token (emitted and
    written) lies inside ``engine.account`` intervals that NO profiler
    session touched."""
    clean = [(s["t0_ns"], s["t1_ns"]) for s in spans.finished_spans(obs)
             if s["name"] == loop_account.NAME
             and not s["attributes"]["profiling"]]

    def untouched(t_ns):
        return any(t0 < t_ns <= t1 for t0, t1 in clean)
    out = []
    for r in spans.window_requests(obs):
        woke = r.get("request.decode", {}).get("attributes", {}).get(
            "first_yield_ns")
        wrote = r.get("front.request", {}).get("attributes", {}).get(
            "first_chunk_ns")
        if woke is None or wrote is None or "request.prefill" not in r:
            continue
        emitted = r["request.prefill"]["t1_ns"]
        if untouched(emitted) and untouched(wrote):
            out.append({"wake": (woke - emitted) / 1e6,
                        "write": (wrote - woke) / 1e6})
    return out
