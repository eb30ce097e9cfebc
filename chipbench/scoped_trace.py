"""Device time by MECHANISM of the hybrid model's programs: the reduction
the per-layer metrics of single mechanisms share.

``trace_reduce.load_events`` keeps an op's instruction name and the start
of its result shape.  Which mechanism an op belongs to needs more of the
event's text (the op's whole HLO line): the profiler keeps neither the
``jax.named_scope`` nor the ``op_name`` metadata in an event's name or
stats (looked at on the chip, PR 29: the stats are offsets and
durations), so an op is labelled by what its text DOES keep —

  * the parameter it reads, by name: an op that consumes a weight names
    it (``%params__layers___3___ffn____w_in__``), and those ops are most
    of the time;
  * the grouped matmul's custom call, named ``ragged-dot``;
  * else a shape no other mechanism of these programs has: the SSM
    state's ``8192,128``, the convolution's 8448 channels and the
    mixer's inner width 8192; the routed assignments' leading dim
    (tokens x top-k).

``RULES`` is that table for ``models/hybrid.py`` at the published widths
(first match wins); ``label_of`` applies it, given the sizes that depend
on the cell (``assignment_rows``).  An op that matches nothing is
``other`` (norms, residuals, the head, attention over the K/V blocks).

``load_events`` reads an ``.xplane.pb`` into rows ``[plane, line, label,
start_ns, duration_ns]`` (``XLA Modules`` rows keep the program's name);
``summarize`` gives, per program name, its run count and the summed SELF
seconds of each label inside its runs (an op that encloses others is
charged only what they leave).  The second stage is pure Python and is
checked on the recorded rows in ``chipbench/tests/``.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict

from chipbench.trace_reduce import (DEVICE_PREFIX, MODULES_LINE, OPS_LINE,
                                    short_name)

RULES = (
    (re.compile(r"ragged-dot|ffn____w_in|ffn____w_out|ffn____router"),
     "routed_experts"),
    (re.compile(r"ffn____shared"), "shared_expert"),
    (re.compile(r"mixer____in_proj|mixer____out_proj"), "mixer_ssm_proj"),
    (re.compile(r"mixer____wqkv|mixer____wo"), "mixer_attention"),
    (re.compile(r"mixer____(conv_|dt_bias|A_log|D_|gnorm)"
                r"|8192,128\]|[\[,]8448\]|[\[,]8192\]"), "mixer_ssm"),
)


def label_of(text: str, assignment_rows: tuple = ()) -> str:
    """``assignment_rows``: the leading dims of the routed experts'
    per-assignment arrays in this cell (decode rows x top-k, chunk x
    top-k), which no other array of the programs has."""
    for pattern, label in RULES:
        if pattern.search(text):
            return label
    head = text.split(" = ", 1)[-1].split(" ", 1)[0]
    if any(f"[{n}," in head or f"[{n}]" in head for n in assignment_rows):
        return "routed_experts"
    return "other"


def load_events(xplane_path: str, assignment_rows: tuple = ()) -> list:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    rows = []
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name == MODULES_LINE:
                for ev in line.events:
                    rows.append([plane.name, line.name, short_name(ev.name),
                                 int(ev.start_ns), int(ev.duration_ns)])
            elif line.name == OPS_LINE:
                for ev in line.events:
                    rows.append([plane.name, line.name,
                                 label_of(ev.name, assignment_rows),
                                 int(ev.start_ns), int(ev.duration_ns)])
    return rows


def summarize(rows) -> dict:
    """{program: {"runs": n, "label_seconds": {label: self seconds inside
    that program's runs}}} over the first device plane.  An op belongs
    to the program run that contains its start."""
    planes = defaultdict(lambda: {"ops": [], "mods": []})
    for plane, line, name, start, dur in rows:
        planes[plane]["ops" if line == OPS_LINE else "mods"].append(
            (start, start + dur, name))
    if not planes:
        return {}
    p = planes[sorted(planes)[0]]
    mods = sorted(p["mods"])
    starts = [m[0] for m in mods]
    out = {name: {"runs": 0, "label_seconds": defaultdict(float)}
           for _s, _e, name in mods}
    for _s, _e, name in mods:
        out[name]["runs"] += 1
    ordered = sorted(p["ops"], key=lambda ev: (ev[0], -ev[1]))
    for (s, _e, label), self_ns in zip(ordered, _self_ns(ordered)):
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < mods[i][1]:
            out[mods[i][2]]["label_seconds"][label] += self_ns / 1e9
    return {k: {"runs": v["runs"], "label_seconds": dict(v["label_seconds"])}
            for k, v in out.items()}


def _self_ns(ordered) -> list:
    """Self nanoseconds of events sorted by (start, -end), in that order:
    an event's duration minus that of its direct children
    (``trace_reduce.self_times`` gives the same sums, by name)."""
    out = [0] * len(ordered)
    stack = []                       # [index, end, children's ns]
    for i, (s, e, _label) in enumerate(ordered):
        while stack and stack[-1][1] <= s:
            j, end, child = stack.pop()
            out[j] = end - ordered[j][0] - child
        if stack:
            stack[-1][2] += e - s
        stack.append([i, e, 0])
    for j, end, child in stack:
        out[j] = end - ordered[j][0] - child
    return out


def ms_per_run(obs: dict, program: str, labels: tuple):
    """Mean self milliseconds a run of ``program`` spends in ``labels``,
    or None where the trace has none."""
    prog = (obs.get("scoped") or {}).get(program)
    if not prog or not prog["runs"]:
        return None
    secs = sum(prog["label_seconds"].get(k, 0.0) for k in labels)
    return 1e3 * secs / prog["runs"] if secs else None
