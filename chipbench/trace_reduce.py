"""From a profiler trace to numbers: the one reduction every PR shares.

Two stages.  ``load_events`` reads an ``.xplane.pb`` file with
``jax.profiler.ProfileData`` into plain rows
``[plane, line, name, start_ns, duration_ns]``; ``summarize`` turns rows
into busy time, per-name sums and the longest idle gaps.  The second
stage is pure Python, so the recorded rows in ``chipbench/tests/`` check
it without a chip.

What is a device: a plane whose name starts with ``/device:TPU:``.  On
such a plane the line ``XLA Ops`` holds one event per executed
operation (a fusion, a custom call = a Pallas kernel, a copy) and the
line ``XLA Modules`` one event per executed program (a jitted step).
Busy time is the union of the ``XLA Ops`` intervals; the window is the
span from the first op's start to the last op's end unless the caller
gives the traced wall span.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'


def short_name(name: str) -> str:
    """An op's event name is its whole HLO text (hundreds of
    characters).  Keep the instruction's own name and the start of its
    result shape; mark a Pallas kernel (a Mosaic custom call — the text
    carries no kernel name, ``kernel_metadata={}``) with ``pallas:``; cut
    a program's ``(fingerprint)``."""
    head, sep, rest = name.partition(" = ")
    head = head.lstrip("%")
    if not sep:
        return head.split("(")[0]
    if PALLAS_TARGET in rest:
        head = "pallas:" + head
    return f"{head} {rest.split('{')[0][:40]}".strip()


def load_events(xplane_path: str) -> list:
    """Rows of the device planes' op and module lines."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    rows = []
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                rows.append([plane.name, line.name, short_name(ev.name),
                             int(ev.start_ns), int(ev.duration_ns)])
    return rows


def _union(intervals) -> tuple:
    """(covered ns, merged intervals) of [start, end) pairs."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def self_times(events) -> list:
    """(name, self ns) of ``(start, end, name)`` events that may nest
    (a ``while`` op's event spans the events of its body): an event's
    self time is its duration minus that of its direct children."""
    out, stack = [], []
    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][1] <= s:
            done = stack.pop()
            out.append((done[2], done[1] - done[0] - done[3]))
        if stack:
            stack[-1][3] += e - s
        stack.append([s, e, name, 0])
    out.extend((ev[2], ev[1] - ev[0] - ev[3]) for ev in stack)
    return out


def summarize(rows, window_s: float | None = None) -> dict:
    """Per-device busy time and the sums the per-layer readers use.

    Returns ``busy_s`` (mean over the device planes of the union of op
    intervals), ``window_s`` (given, or first op start to last op end),
    ``n_devices``, ``op_seconds`` {op name: summed SELF seconds (an op
    that encloses others, as a ``while`` does, is charged only what its
    children leave), mean over devices}, ``module_seconds`` / ``module_counts`` likewise for
    programs, and ``idle_gaps``: the longest gaps between consecutive
    busy intervals on the first device, summed by label, as (label,
    seconds) where the label names the ops on either side (the program writes no host
    spans yet, so a gap cannot be attributed to host work).
    """
    planes = defaultdict(lambda: {"ops": [], "mods": []})
    for plane, line, name, start, dur in rows:
        planes[plane]["ops" if line == OPS_LINE else "mods"].append(
            (start, start + dur, name))
    if not any(p["ops"] for p in planes.values()):
        return {"busy_s": 0.0, "window_s": window_s or 0.0,
                "n_devices": 0, "op_seconds": {}, "module_seconds": {},
                "module_counts": {}, "idle_gaps": []}
    n = len(planes)
    busy_ns, span_ns = 0, 0
    op_s, mod_s, mod_n = defaultdict(float), defaultdict(float), \
        defaultdict(float)
    gap_s = defaultdict(float)
    for i, (_name, p) in enumerate(sorted(planes.items())):
        covered, merged = _union((s, e) for s, e, _ in p["ops"])
        busy_ns += covered
        if merged:
            span_ns = max(span_ns, merged[-1][1] - merged[0][0])
        for name, self_ns in self_times(p["ops"]):
            op_s[name] += self_ns / 1e9 / n
        for s, e, name in p["mods"]:
            mod_s[name] += (e - s) / 1e9 / n
            mod_n[name] += 1 / n
        if i == 0:
            by_end = {e: nm for _s, e, nm in p["ops"]}
            by_start = {s: nm for s, _e, nm in p["ops"]}
            for (_s0, e0), (s1, _e1) in zip(merged, merged[1:]):
                label = (f"after {by_end.get(e0, '?')} before "
                         f"{by_start.get(s1, '?')} (host work not "
                         "attributed: no program spans)")
                gap_s[label] += (s1 - e0) / 1e9
    gaps = sorted(gap_s.items(), key=lambda g: -g[1])
    return {"busy_s": busy_ns / 1e9 / n,
            "window_s": window_s if window_s else span_ns / 1e9,
            "n_devices": n, "op_seconds": dict(op_s),
            "module_seconds": dict(mod_s), "module_counts": dict(mod_n),
            "idle_gaps": gaps[:10]}


def breakdown(summary: dict) -> dict:
    """The contract's optional ``breakdown``: top device ops by summed
    time and the longest idle gaps, at most 10 each."""
    ops = sorted(summary["op_seconds"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in summary["idle_gaps"]]}
