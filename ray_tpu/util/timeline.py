"""Merged Chrome/Perfetto trace export.

One ``trace_event``-format JSON from every observability source the
framework has (reference: ``ray.timeline()`` Chrome-trace export,
python/ray/experimental/state + _private/profiling.py):

  * task state events        → ``X`` slices (RUNNING→FINISHED pairs)
  * flight-recorder records  → one ``X`` slice PER LIFECYCLE STAGE, so
    "where do the milliseconds go" is visible per task
  * tracing spans            → ``X`` slices grouped by emitting pid,
    a span's attributes in the slice args (engine passes, request
    lifecycle, serve front, trainer loop: util/tracing.py)
  * chaos (fault-injection)  → ``i`` instant events, so injected faults
    show up attributed in the same view as the latency they caused
  * serve-fleet ingress      → admission/shed/route/resume/scale events
    (serve/fleet): queued admissions render as ``X`` slices (the queue
    wait is visible time), everything else as ``i`` instants, one track
    per event kind; drain begin/settle pairs and cluster-prefix
    adoption begin/complete/fallback pairs merge into single ``X``
    slices so their durations read straight off the trace
  * inference-engine request slices → one ``X`` per completed request
    (pid "engine", tid = engine name) spanning submit→finish, with
    speculative-decoding accept/reject counts — and, for meshed
    engines, the serving geometry (mesh_devices / tp_shards) — merged
    into the slice args (engine_request events from
    InferenceEngine._fr_note)

Output loads in chrome://tracing and ui.perfetto.dev (both accept the
``{"traceEvents": [...]}`` object form and string pid/tid values).
"""

from __future__ import annotations

from typing import Iterable


def build_trace(task_events: Iterable = (), records: Iterable = (),
                spans: Iterable = (), faults: Iterable = (),
                ingress: Iterable = ()) -> dict:
    """Merge all sources into one Perfetto-loadable trace dict."""
    from ray_tpu.util.state import events_to_trace

    ev: list = list(events_to_trace(list(task_events)))

    for r in records:
        # r: flight-recorder export — {"task_id", "name", "worker",
        # "start_ts", "stages": [(stage, wall_ts), ...]}
        stages = r.get("stages") or []
        # tid must be unique per task: concurrent tasks of one function
        # would otherwise collapse onto a single track and interleave as
        # bogus nesting exactly when there IS concurrency to look at
        tid = f"{r.get('name') or '?'} {r.get('task_id', '?')[:8]}"
        prev_ts = None
        for stage, ts in stages:
            if prev_ts is not None:
                ev.append({
                    "name": stage, "cat": "lifecycle", "ph": "X",
                    "ts": prev_ts * 1e6,
                    "dur": max(0.0, (ts - prev_ts) * 1e6),
                    "pid": "lifecycle", "tid": tid,
                    "args": {"task_id": r.get("task_id"),
                             "worker": r.get("worker")},
                })
            prev_ts = ts

    for s in spans:
        if "start" not in s or "end" not in s:
            continue
        ev.append({
            "name": s.get("name", "span"), "cat": "span", "ph": "X",
            "ts": s["start"] * 1e6,
            "dur": max(0.0, (s["end"] - s["start"]) * 1e6),
            "pid": f"pid {s.get('pid', '?')}",
            "tid": s.get("kind", "span"),
            "args": {**(s.get("attributes") or {}),
                     "trace_id": s.get("trace_id"),
                     "span_id": s.get("span_id"),
                     "parent_id": s.get("parent_id"),
                     "status": s.get("status")},
        })

    for f in faults:
        ev.append({
            "name": f"chaos:{f.get('point')}:{f.get('action')}",
            "cat": "chaos", "ph": "i", "s": "g",
            "ts": float(f.get("t", 0.0)) * 1e6,
            "pid": "chaos", "tid": f.get("point", "?"),
            "args": {"detail": f.get("detail")},
        })

    # drain lifecycle pairing: a drain_begin and its settling
    # drain_complete / drain_timeout (same replica) render as ONE slice
    # so the drain DURATION is visible time; unpaired events fall back
    # to instants below
    drain_open: dict = {}    # replica tag -> begin event
    # prefix-adoption pairing: an adopt_begin and its settling
    # adopt_complete / adopt_fallback (same adopt id) render as ONE
    # slice — the remote fetch+install cost is visible time, and a
    # fallback slice carries the failure reason in args
    adopt_open: dict = {}    # adopt id -> begin event
    for g in ingress:
        # g: fleet ingress event — {"t", "kind", "deployment", ...}
        # (serve/fleet/ingress.py Fleet.note); an admit that waited in
        # the admission queue becomes a slice ENDING at the admit stamp
        # so the queueing delay is visible time, everything else an
        # instant on its kind's track
        kind = g.get("kind", "?")
        ts = float(g.get("t", 0.0)) * 1e6
        args = {k: v for k, v in g.items() if k not in ("t", "kind")}
        queued = float(g.get("queued_s") or 0.0)
        if kind == "engine_request":
            # inference-engine request slice (engine._fr_note): one X
            # per completed request on the engine's own track, carrying
            # speculative accept/reject counts in args so "why was this
            # stream fast/slow" reads straight off the trace
            t0 = float(g.get("start_t", g.get("t", 0.0))) * 1e6
            ev.append({
                "name": f"engine:{g.get('req', '?')}",
                "cat": "engine", "ph": "X",
                "ts": t0, "dur": max(0.0, ts - t0),
                "pid": "engine", "tid": g.get("engine", "?"),
                "args": args,
            })
            continue
        if kind == "admit" and queued > 0:
            ev.append({
                "name": "ingress:queued", "cat": "ingress", "ph": "X",
                "ts": ts - queued * 1e6, "dur": queued * 1e6,
                "pid": "ingress", "tid": "admit", "args": args,
            })
            continue
        if kind == "adopt_begin" and g.get("adopt") is not None:
            adopt_open[g["adopt"]] = g
            continue
        if kind in ("adopt_complete", "adopt_fallback") \
                and g.get("adopt") in adopt_open:
            begin = adopt_open.pop(g["adopt"])
            t0 = float(begin.get("t", 0.0)) * 1e6
            args["outcome"] = kind
            args.setdefault("holder", begin.get("holder"))
            args.setdefault("replica", begin.get("replica"))
            args.setdefault("tokens", begin.get("tokens"))
            ev.append({
                "name": f"ingress:adopt:{begin.get('holder', '?')}"
                        f"->{begin.get('replica', '?')}",
                "cat": "ingress", "ph": "X",
                "ts": t0, "dur": max(0.0, ts - t0),
                "pid": "ingress", "tid": "adopt", "args": args,
            })
            continue
        if kind == "drain_begin" and g.get("replica") is not None:
            drain_open[g["replica"]] = g
            continue
        if kind in ("drain_complete", "drain_timeout") \
                and g.get("replica") in drain_open:
            begin = drain_open.pop(g["replica"])
            t0 = float(begin.get("t", 0.0)) * 1e6
            args["outcome"] = kind
            args["reason"] = begin.get("reason")
            ev.append({
                "name": f"ingress:drain:{g['replica']}",
                "cat": "ingress", "ph": "X",
                "ts": t0, "dur": max(0.0, ts - t0),
                "pid": "ingress", "tid": "drain", "args": args,
            })
            continue
        ev.append({
            "name": f"ingress:{kind}", "cat": "ingress", "ph": "i",
            "s": "g", "ts": ts, "pid": "ingress", "tid": kind,
            "args": args,
        })
    for tag, begin in drain_open.items():
        # drain still in progress at export time: show the begin
        ev.append({
            "name": "ingress:drain_begin", "cat": "ingress", "ph": "i",
            "s": "g", "ts": float(begin.get("t", 0.0)) * 1e6,
            "pid": "ingress", "tid": "drain",
            "args": {k: v for k, v in begin.items()
                     if k not in ("t", "kind")},
        })
    for aid, begin in adopt_open.items():
        # adoption still in flight (or its settle event was evicted):
        # show the begin rather than dropping it
        ev.append({
            "name": "ingress:adopt_begin", "cat": "ingress", "ph": "i",
            "s": "g", "ts": float(begin.get("t", 0.0)) * 1e6,
            "pid": "ingress", "tid": "adopt",
            "args": {k: v for k, v in begin.items()
                     if k not in ("t", "kind")},
        })

    ev.sort(key=lambda e: e.get("ts", 0.0))
    return {"traceEvents": ev, "displayTimeUnit": "ms"}
