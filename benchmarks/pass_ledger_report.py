"""One cell of the benchmark, run in THIS process as ``chipbench/run.py``
runs it, and then what ``chipbench/pass_ledger.py`` reads from the ring
that the result line has no room for (PR 54; PERF.md section 5's table
a cell): every KIND of pass with its count and its mean time, host and
wait, how many of them were launched ahead of an unread pass and how
many read early (PR 55), the gaps' p50 / p95 / p99 as the engine emitted them and as the
front wrote them beside the client's, and the first token's two hops.

    chiprun -- python benchmarks/pass_ledger_report.py \
        --out chiprun_out/ledger_xl.json -- \
        --workload serve-xl-chat-r80-v2 --seed 7 --seconds 51 --trace 1

What follows ``--`` goes to ``chipbench/run.py`` as it is; its result
line is printed last on the standard output, as always.  On the CPU
(``--rehearse`` after the ``--``) the numbers are no device numbers."""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def report(obs: dict, client: dict) -> dict:
    """``obs`` as a per-layer reader is handed it; ``client``: the
    client's own quantiles of the gap, {50: ms, 95: ms}."""
    from chipbench import loop_account, pass_ledger, spans, stats
    led = pass_ledger.engine(obs)
    wrote = pass_ledger.front_gaps(obs)
    firsts = pass_ledger.first_tokens(obs)
    out = {"passes": led and led["passes"], "kinds": {}, "gaps_ms": {}}
    # (``chunks_in_step`` over ``chunk_passes``: the share of the
    # window's chunks that rode a decode step as ONE program)
    for key in ("passes_launched_ahead", "passes_drained", "chunk_passes",
                "chunks_in_step"):
        out[key] = led["counters"].get(key) if led else None
    # why passes were read early, since the engine was made (the newest
    # ``engine.account`` span's own table)
    chain = [s for s in spans.finished_spans(obs)
             if s["name"] == loop_account.NAME]
    out["drained_by"] = max(chain, key=lambda s: s["t1_ns"])[
        "attributes"].get("drained_by") if chain else None
    for kind, row in sorted(led["by_kind"].items() if led else ()):
        if row["count"]:
            out["kinds"][kind] = {
                "passes": row["count"],
                **{part + "ms": row[part + "ns"] / row["count"] / 1e6
                   for part in ("", "host_", "wait_")},
                "tokens": row.get("tokens", 0) / row["count"],
                # (since PR 55; a parent's rows have neither)
                **({"launched_ahead": row["ahead"],
                    "drained": row["drained"]} if "ahead" in row else {})}
    for q in (50, 95, 99):
        out["gaps_ms"][f"p{q}"] = {
            "engine": led and pass_ledger.quantile_ms(led["gaps"], q),
            "front": wrote and pass_ledger.quantile_ms(wrote, q),
            "client": client.get(q)}
    if firsts:
        out["first_token_ms"] = {
            "requests": len(firsts),
            **{f"{hop}_p{q}": stats.percentile([r[hop] for r in firsts], q)
               for hop in ("wake", "write") for q in (50, 90)}}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True, help="the report, JSON")
    ap.add_argument("run_args", nargs=argparse.REMAINDER,
                    help="-- and then chipbench/run.py's arguments")
    args = ap.parse_args()
    run_args = [a for a in args.run_args if a != "--"]
    cell = run_args[run_args.index("--workload") + 1]
    seconds = float(run_args[run_args.index("--seconds") + 1])
    from chipbench import run          # T_START is taken here
    sys.modules["__main__"].T_START = run.T_START   # for the line's readers
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        traffic = {w["name"]: w["traffic"]
                   for w in json.load(f)["workloads"]}[cell]
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           traffic + ".json")) as f:
        kind = importlib.import_module(
            "chipbench.traffic." + json.load(f)["kind"])
    # a traced run's line does not say when its window opened: the
    # cell's traffic kind is wrapped to keep what it returned
    kept, kind_run = {}, kind.run
    kind.run = lambda ctx: kept.setdefault("res", kind_run(ctx))
    sys.argv = [os.path.join(ROOT, "chipbench", "run.py")] + run_args
    code = run.main()
    if "res" in kept:
        res = kept["res"]
        obs = {"t_start": run.T_START, "window_s": seconds,
               "end_to_end": {"setup_s": res["setup_s"]}}
        client = {50: res.get("notes", {}).get("itl_p50_ms"),
                  95: res["end_to_end"].get("itl_p95_ms")}
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report(obs, client), f, indent=1)
    return code


if __name__ == "__main__":
    code = main()
    sys.stderr.flush()
    os._exit(code)      # as ``chipbench/run.py`` leaves
