"""chipbench/run.py — one cell of BENCHMARK.json, one process, one line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up (set-up), measures for ``--seconds``, checks the
outputs, and prints as the LAST line of its standard output one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
and, with ``--trace 1``, ``breakdown``.  With ``--trace 0`` the metrics
are the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics.  An untraced line's ``notes.untraced_per_layer`` also holds the
cell's per-layer metrics whose reader says ``UNTRACED = True`` (it reads
what the program keeps with no profiler on): for ``sweep.py`` and
``spreads.py``, not for the driver.

Everything that belongs to one cell is found by name, nothing is listed
here: the cell's entry in ``BENCHMARK.json`` names its configuration
(``chipbench/configs/<config>.json``) and its traffic mix
(``chipbench/traffic/<traffic>.json``); the mix names the kind of
traffic, a module ``chipbench/traffic/<kind>.py`` with a ``run(ctx)``;
every per-layer metric that ``BENCHMARK.json`` gives this cell is read
by ``chipbench/layer_metrics/<metric>.py``'s ``read(obs)``.  A reader
that finds nothing to read returns None and the metric is left out.

No TPU, a ``device_kind`` without an entry in ``chipbench/peaks.json``,
or fewer chips than the cell asks for: exit code 2 and nothing on the
standard output.  ``--rehearse`` (the driver never passes it) runs the
same code on the CPU at the fixture size of ``chipbench/tests/``; its
line says ``"correct": false`` and carries no metric (what the checks
came to is under ``rehearsal_verdict_not_a_result``).
``--mix-override`` lays a JSON object over the traffic mix: for
``chipbench/sweep.py`` alone, never passed by the driver.
"""

from __future__ import annotations

import time

T_START = time.monotonic()          # set-up is counted from here

import argparse                     # noqa: E402
import importlib                    # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402
from dataclasses import dataclass, field   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(msg: str) -> None:
    sys.stderr.write(f"[chipbench +{time.monotonic() - T_START:6.1f}s] "
                     f"{msg}\n")
    sys.stderr.flush()


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@dataclass
class Ctx:
    """What a traffic kind gets: the cell's data and the run's arguments."""
    cell: dict                  # the workloads entry of BENCHMARK.json
    config: dict                # chipbench/configs/<config>.json
    mix: dict                   # chipbench/traffic/<traffic>.json
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    root: str = ROOT
    t_start: float = T_START
    log: callable = field(default=log, repr=False)


def metrics_of_cell(bench: dict, section: str, cell: str) -> list:
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def device_report(devs, trace_summary, peak: int) -> dict:
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    if trace_summary is not None:
        out["busy_s"] = trace_summary["busy_s"]
        out["window_s"] = trace_summary["window_s"]
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at fixture size; never a result")
    ap.add_argument("--mix-override", type=json.loads, default={},
                    help="JSON object laid over the traffic mix, for "
                    "chipbench/sweep.py alone; the driver never passes it")
    args = ap.parse_args()

    # fd 1 -> stderr: nothing a library or a child prints can land on
    # the standard output; only the last line is written there
    sys.stdout.flush()
    out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    sys.stdout = sys.stderr
    sys.path.insert(0, ROOT)

    bench = load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        log(f"no cell {args.workload!r} in BENCHMARK.json; "
            f"known: {sorted(cells)}")
        return 2
    cell = cells[args.workload]
    config = load_json("chipbench", "configs", cell["config"] + ".json")
    mix = load_json("chipbench", "traffic", cell["traffic"] + ".json")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        fixture = load_json("chipbench", "tests", "rehearse.json")
        config = {**config, **fixture["config"]}
        mix = {**mix, **fixture["traffic"].get(mix["kind"], {})}
    mix = {**mix, **args.mix_override}

    import jax
    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    peaks = load_json("chipbench", "peaks.json")
    if args.rehearse:
        if platform != "cpu":
            log("--rehearse is the CPU rehearsal; refusing on " + platform)
            return 2
    elif platform != "tpu" or kind not in peaks:
        log(f"needs a TPU listed in chipbench/peaks.json; jax found "
            f"{platform!r} ({kind})")
        return 2
    if len(devs) < cell["chips"]:
        log(f"cell needs {cell['chips']} chips, jax found {len(devs)}")
        return 2

    from ray_tpu._compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    log(f"cell {cell['name']} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace} on {len(devs)} x {kind}; compile cache "
        f"{cache_dir}")

    ctx = Ctx(cell=cell, config=config, mix=mix, seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace),
              rehearse=args.rehearse)
    kind_mod = importlib.import_module("chipbench.traffic." + mix["kind"])
    res = kind_mod.run(ctx)
    # res: correct, attempted, failed, setup_s, end_to_end {name: value},
    # obs {what the per-layer readers read}, notes {..}, checks {name:
    # {value, limit}}: every number `correct` was decided from, and
    # memory_peak_bytes as read when the window closed

    declared_e2e = metrics_of_cell(bench, "end_to_end", cell["name"])
    values = {**res["end_to_end"], "setup_s": res["setup_s"]}
    missing = [m["name"] for m in declared_e2e if m["name"] not in values]
    if missing:
        log(f"traffic kind {mix['kind']!r} gave no value for {missing}")
        return 1
    summary = res["obs"].get("trace")
    obs = {**res["obs"], "end_to_end": values, "peaks": peaks.get(kind),
           "config": config, "mix": mix}
    from chipbench.readers import load_reader
    # a traced run reads every per-layer metric of the cell; an untraced
    # one those whose reader says ``UNTRACED`` (it reads what the program
    # keeps with no profiler on), into the notes: the line's metrics are
    # the end-to-end ones
    read = {}
    for m in metrics_of_cell(bench, "per_layer", cell["name"]):
        reader = load_reader(m["name"])
        if args.trace or getattr(reader, "UNTRACED", False):
            value = reader.read(obs)
            if value is not None:
                read[m["name"]] = {"value": value, "unit": m["unit"]}
    metrics = read if args.trace else {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in declared_e2e}

    line = {"correct": bool(res["correct"]) and not args.rehearse,
            "attempted": res["attempted"], "failed": res["failed"],
            "metrics": {} if args.rehearse else metrics,
            "device": device_report(devs, summary, res["memory_peak_bytes"]),
            "notes": {**res.get("notes", {}),
                      "mix_override": args.mix_override,
                      "untraced_per_layer": {} if args.trace else {
                          k: v["value"] for k, v in read.items()},
                      "memory_stats": devs[0].memory_stats()}}
    if args.rehearse:
        line["rehearsal_metrics_not_device_numbers"] = metrics
        line["rehearsal_verdict_not_a_result"] = bool(res["correct"])
    if args.trace and summary is not None:
        from chipbench.trace_reduce import breakdown
        line["breakdown"] = breakdown(summary)
    # what was compared, each number beside its limit: the line's last
    # key and the last lines of the standard error
    line["checks"] = res["checks"]
    out.write(json.dumps(line) + "\n")
    out.flush()
    log(f"correct: {line['correct']}")
    for name, c in res["checks"].items():
        sys.stderr.write(f"check {name}: " + " ".join(
            f"{k} {v}" for k, v in c.items()) + "\n")
    sys.stderr.flush()
    return 0      # the run reached its end; `correct` is in the line


if __name__ == "__main__":
    code = main()
    sys.stderr.flush()
    # every shutdown has run and the last line is out: leave without
    # giving interpreter teardown a chance to print or to hang
    os._exit(code)
