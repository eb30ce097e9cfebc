"""ray_tpu command line: start/stop/status/list/summary/timeline/memory.

The analogue of the reference CLI (reference: python/ray/scripts/
scripts.py:529 `ray start`, :1809 `ray status`, plus `ray list/summary/
timeline/memory` from python/ray/experimental/state/state_cli.py).
No pip entry point in this environment, so it runs as
``python -m ray_tpu <command>``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import uuid


def _observer(address: str):
    from ray_tpu.core.observer import observer_connect
    return observer_connect(address)


def cmd_start(args) -> int:
    from ray_tpu._config import RayTpuConfig
    from ray_tpu.core.node import NodeService

    overrides = {}
    if args.metrics_port:
        overrides["metrics_export_port"] = args.metrics_port
    config = RayTpuConfig(overrides)
    session = uuid.uuid4().hex
    session_dir = os.path.join("/tmp/ray_tpu", f"session_{session[:8]}")

    head = None
    head_address = args.address
    if args.head:
        from ray_tpu.core.head import HeadService
        head = HeadService(config, session, port=args.port or 0)
        head.start_thread()
        head_address = head.address
        print(f"head service listening on {head.address}")
    elif not head_address:
        print("either --head or --address=<head> is required",
              file=sys.stderr)
        return 2

    node = NodeService(config, session, session_dir,
                       num_cpus=args.num_cpus, num_tpus=args.num_tpus,
                       head_address=head_address,
                       stop_on_driver_exit=False)
    print(f"node service listening on {node.address} "
          f"(session {session[:8]})")
    if node.metrics_exporter is not None:
        print(f"metrics at http://127.0.0.1:"
              f"{node.metrics_exporter.port}/metrics")
    print("connect with: ray_tpu.init(address="
          f"{node.address!r})", flush=True)
    try:
        node.run()
    except KeyboardInterrupt:
        pass
    finally:
        # every exit path must reap workers/shm/metrics threads
        node.stop()
        if head is not None:
            head.stop()
    return 0


def cmd_stop(args) -> int:
    import signal
    import subprocess

    # match the module paths exactly (a looser pattern would match the
    # invoking shell; see repo verify notes)
    n = 0
    for pat in ("ray_tpu.core.worker", "ray_tpu.core.node",
                "ray_tpu.core.head", "ray_tpu start"):
        r = subprocess.run(["pkill", "-f", pat],
                           capture_output=True)
        n += 1 if r.returncode == 0 else 0
    print(f"stopped ({n} process groups signalled)")
    del signal
    return 0


def cmd_status(args) -> int:
    conn, request = _observer(args.address)
    try:
        nodes = request({"t": "state", "what": "nodes"})["data"]
        res = request({"t": "state", "what": "resources"})["data"]
        stats = request({"t": "object_stats"})["stats"]
    finally:
        conn.close()
    print("======== cluster status ========")
    print(f"nodes: {len(nodes)} "
          f"({sum(1 for n in nodes if n.get('alive'))} alive)")
    for n in nodes:
        mark = "+" if n.get("alive") else "-"
        print(f"  {mark} {n['node_id'][:12]} {n['address']} "
              f"avail={n['available']} total={n['resources']}")
    print(f"resources: available={res['available']} total={res['total']}")
    print(f"object store: {stats['num_objects']} objects, "
          f"{stats['used_bytes'] / 1e6:.1f}/"
          f"{stats['capacity_bytes'] / 1e6:.1f} MB used"
          + (", spilled=%d" % stats["num_spilled"]
             if stats.get("num_spilled") else ""))
    return 0


def cmd_list(args) -> int:
    conn, request = _observer(args.address)
    try:
        what = {"nodes": "nodes", "tasks": "tasks", "actors": "actors",
                "objects": "objects", "workers": "workers"}[args.what]
        data = request({"t": "state", "what": what})["data"]
    finally:
        conn.close()
    print(json.dumps(data, indent=2, default=str))
    return 0


def cmd_summary(args) -> int:
    conn, request = _observer(args.address)
    try:
        data = request({"t": "state", "what": args.what})["data"]
    finally:
        conn.close()
    from ray_tpu.util.state import group_counts
    key = {"tasks": "name", "actors": "class_name",
           "objects": "loc"}[args.what]
    summ = group_counts(data, key)
    for name, states in summ["cluster"].items():
        print(f"{name}: {states}")
    print(f"total: {summ['total']}")
    return 0


def cmd_timeline(args) -> int:
    """Merged Perfetto export: task events + flight-recorder lifecycle
    stages + tracing spans + chaos events in one trace (reference:
    ray.timeline Chrome-trace export)."""
    conn, request = _observer(args.address)
    try:
        events = request({"t": "state", "what": "task_events"})["data"]
        # export the recorder's WHOLE ring, not the server default
        fr = request({"t": "flight_recorder", "limit": 1_000_000})
    finally:
        conn.close()
    spans = []
    trace_dir = getattr(args, "trace_dir", None) \
        or os.environ.get("RAY_TPU_TRACE_DIR")
    if trace_dir:
        from ray_tpu.util.tracing import collect_spans
        spans = collect_spans(trace_dir)
    # serve-fleet ingress events: from the armed flight recorder, plus
    # any Fleet.dump_events file (ingress processes that ran without a
    # recorder)
    ingress = list(fr.get("ingress", []))
    serve_events = getattr(args, "serve_events", None)
    if serve_events:
        with open(serve_events) as f:
            ingress += json.load(f)
    from ray_tpu.util.timeline import build_trace
    trace = build_trace(task_events=events,
                        records=fr.get("records", []),
                        spans=spans,
                        faults=fr.get("faults", []),
                        ingress=ingress)
    out = args.output or f"timeline-{int(time.time())}.json"
    with open(out, "w") as f:
        json.dump(trace, f)
    n = len(trace["traceEvents"])
    lifecycle = sum(1 for e in trace["traceEvents"]
                    if e.get("cat") == "lifecycle")
    n_ingress = sum(1 for e in trace["traceEvents"]
                    if e.get("cat") == "ingress")
    print(f"wrote {n} events ({lifecycle} lifecycle stage slices, "
          f"{n_ingress} ingress events) to "
          f"{out} (open in chrome://tracing or ui.perfetto.dev)"
          + ("" if fr.get("enabled") else
             "; flight recorder disabled — set "
             "RAY_TPU_FLIGHT_RECORDER=1 for per-stage slices"))
    return 0


def cmd_memory(args) -> int:
    conn, request = _observer(args.address)
    try:
        stats = request({"t": "object_stats"})
        objects = request({"t": "state", "what": "objects"})["data"]
    finally:
        conn.close()
    print(json.dumps(stats["stats"], indent=2))
    biggest = sorted(objects, key=lambda o: -(o.get("size") or 0))[:20]
    for o in biggest:
        print(f"  {o['object_id'][:16]} {o['state']:8} "
              f"{o.get('loc') or '-':7} {(o.get('size') or 0) / 1e6:.2f} MB")
    return 0


def cmd_stack(args) -> int:
    """Dump the thread stacks of every live worker on a node
    (reference: `ray stack`, scripts.py:1767)."""
    conn, request = _observer(args.address)
    try:
        workers = request({"t": "state", "what": "workers"})["data"]
        workers = [w for w in workers if w["kind"] == "worker"]
        if not workers:
            print("no live workers on this node")
            return 0
        for w in workers:
            print(f"===== worker pid={w['pid']} state={w['state']} =====")
            try:
                r = request({"t": "stack_dump", "pid": w["pid"]})
                print(r.get("data", ""))
            except RuntimeError as e:
                print(f"  <{e}>")
        return 0
    finally:
        conn.close()


def cmd_flame(args) -> int:
    """Flamegraph a live worker by pid (folded stacks sampled in the
    worker; rendered here)."""
    from ray_tpu.core.observer import observer_query
    from ray_tpu.util.profiling import flamegraph_svg
    (reply,) = observer_query(
        args.address,
        [{"t": "profile_worker", "pid": args.pid,
          "duration": args.duration}],
        request_timeout=args.duration + 40)
    folded = reply.get("folded", "")
    with open(args.output, "w") as f:
        f.write(flamegraph_svg(folded))
    n = len([ln for ln in folded.splitlines() if ln.strip()])
    print(f"wrote {args.output} ({n} distinct stacks)")
    return 0


def cmd_kill_random_node(args) -> int:
    from ray_tpu.util.chaos import kill_random_node
    victim = kill_random_node(args.address,
                              exclude_addresses=tuple(args.spare))
    if victim is None:
        print("no killable node found")
        return 1
    print(f"killed node at {victim}")
    return 0


def cmd_dashboard(args) -> int:
    from ray_tpu.dashboard import Dashboard

    dash = Dashboard(args.address, port=args.port)
    dash.start()
    print(f"dashboard at http://{dash.host}:{dash.port}/", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        dash.stop()
    return 0


def cmd_job(args) -> int:
    from ray_tpu.job import JobStatus, JobSubmissionClient

    client = JobSubmissionClient(address=args.address)
    if args.job_cmd == "submit":
        runtime_env = {}
        if args.working_dir:
            runtime_env["working_dir"] = args.working_dir
        import shlex
        job_id = client.submit_job(
            entrypoint=shlex.join(args.entrypoint),
            runtime_env=runtime_env or None)
        print(f"submitted {job_id}")
        if args.wait:
            status = client.wait_until_finished(job_id,
                                                timeout=args.timeout)
            print(client.get_job_logs(job_id), end="")
            print(f"status: {status}")
            return 0 if status == JobStatus.SUCCEEDED else 1
        return 0
    if args.job_cmd == "status":
        print(client.get_job_status(args.job_id))
        return 0
    if args.job_cmd == "logs":
        print(client.get_job_logs(args.job_id), end="")
        return 0
    if args.job_cmd == "stop":
        print("stopped" if client.stop_job(args.job_id)
              else "not running")
        return 0
    if args.job_cmd == "list":
        for info in client.list_jobs():
            print(f"{info.job_id}  {info.status:10}  {info.entrypoint}")
        return 0
    return 2


def cmd_serve(args) -> int:
    """`serve run/deploy/status/shutdown` (reference:
    python/ray/serve/scripts.py serve CLI)."""
    import json as _json

    if args.serve_cmd == "run":
        import ray_tpu
        from ray_tpu.serve.rest import ServeRestServer, apply_config
        ray_tpu.init(address=args.address)
        apply_config({"applications": [
            {"name": args.name or args.import_path,
             "import_path": args.import_path}]},
            http=True, port=args.port)
        from ray_tpu import serve as _serve
        rest = ServeRestServer(port=args.rest_port)
        print(f"serving {args.import_path}  "
              f"ingress={_serve.proxy_address()}  rest={rest.address}")
        # always block: the proxy/REST servers are daemon threads of
        # THIS process — returning would tear the service down
        import time as _time
        try:
            while True:
                _time.sleep(1)
        except KeyboardInterrupt:
            pass
        return 0

    if args.serve_cmd == "deploy":
        import urllib.request
        with open(args.config_file) as f:
            cfg = (_json.load(f) if args.config_file.endswith(".json")
                   else _load_yaml_or_json(f.read()))
        req = urllib.request.Request(
            args.address.rstrip("/") + "/api/serve/applications/",
            data=_json.dumps(cfg).encode(), method="PUT",
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as resp:
            print(resp.read().decode())
        return 0

    if args.serve_cmd == "status":
        import urllib.request
        with urllib.request.urlopen(
                args.address.rstrip("/") + "/api/serve/applications/",
                timeout=30) as resp:
            print(_json.dumps(_json.loads(resp.read()), indent=2))
        return 0

    if args.serve_cmd == "shutdown":
        import urllib.request
        req = urllib.request.Request(
            args.address.rstrip("/") + "/api/serve/applications/",
            method="DELETE")
        with urllib.request.urlopen(req, timeout=60):
            print("shut down")
        return 0
    return 2


def cmd_up(args) -> int:
    """`ray_tpu up cluster.yaml` (reference: scripts.py up:1216)."""
    from ray_tpu.autoscaler.commands import load_cluster_config, up
    up(load_cluster_config(args.cluster_config))
    return 0


def cmd_down(args) -> int:
    from ray_tpu.autoscaler.commands import down, load_cluster_config
    down(load_cluster_config(args.cluster_config),
         keep_head=args.keep_head)
    return 0


def cmd_attach(args) -> int:
    from ray_tpu.autoscaler.commands import attach, load_cluster_config
    return attach(load_cluster_config(args.cluster_config))


def cmd_exec(args) -> int:
    from ray_tpu.autoscaler.commands import exec_cmd, load_cluster_config
    out = exec_cmd(load_cluster_config(args.cluster_config),
                   " ".join(args.command),
                   on_head=not args.workers,
                   all_workers=args.all_hosts)
    print(out, end="" if out.endswith("\n") else "\n")
    return 0


def cmd_submit(args) -> int:
    from ray_tpu.autoscaler.commands import load_cluster_config, submit
    out = submit(load_cluster_config(args.cluster_config), args.script)
    print(out, end="" if out.endswith("\n") else "\n")
    return 0


def _load_yaml_or_json(text: str) -> dict:
    import json as _json
    try:
        return _json.loads(text)
    except ValueError:
        try:
            import yaml
            return yaml.safe_load(text)
        except ImportError as e:
            raise SystemExit(
                "config is not JSON and pyyaml is unavailable") from e


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m ray_tpu",
        description="ray_tpu cluster CLI (reference: `ray` CLI surface)")
    sub = parser.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("start", help="start a head and/or node service")
    p.add_argument("--head", action="store_true",
                   help="start a head service (plus a node joined to it)")
    p.add_argument("--address", default=None,
                   help="existing head address to join")
    p.add_argument("--port", type=int, default=0, help="head listen port")
    p.add_argument("--num-cpus", type=float, default=None)
    p.add_argument("--num-tpus", type=float, default=None)
    p.add_argument("--metrics-port", type=int, default=0)
    p.set_defaults(fn=cmd_start)

    p = sub.add_parser("stop", help="kill local ray_tpu processes")
    p.set_defaults(fn=cmd_stop)

    p = sub.add_parser("up", help="launch a cluster from a YAML config "
                                  "(reference: `ray up`)")
    p.add_argument("cluster_config")
    p.set_defaults(fn=cmd_up)

    p = sub.add_parser("down", help="tear a launched cluster down")
    p.add_argument("cluster_config")
    p.add_argument("--keep-head", action="store_true")
    p.set_defaults(fn=cmd_down)

    p = sub.add_parser("attach", help="interactive shell on the head")
    p.add_argument("cluster_config")
    p.set_defaults(fn=cmd_attach)

    p = sub.add_parser("exec", help="run a shell command on the cluster")
    p.add_argument("cluster_config")
    p.add_argument("--workers", action="store_true",
                   help="run on worker nodes instead of the head")
    p.add_argument("--all-hosts", action="store_true",
                   help="every host of a multi-host slice")
    p.add_argument("command", nargs="+")
    p.set_defaults(fn=cmd_exec)

    p = sub.add_parser("submit", help="copy a script to the head and "
                                      "run it (reference: `ray submit`)")
    p.add_argument("cluster_config")
    p.add_argument("script")
    p.set_defaults(fn=cmd_submit)

    for name, fn in (("status", cmd_status), ("memory", cmd_memory)):
        p = sub.add_parser(name)
        p.add_argument("--address", required=True)
        p.set_defaults(fn=fn)

    p = sub.add_parser("list", help="list tasks/actors/objects/workers/nodes")
    p.add_argument("what", choices=["tasks", "actors", "objects",
                                    "workers", "nodes"])
    p.add_argument("--address", required=True)
    p.set_defaults(fn=cmd_list)

    p = sub.add_parser("summary")
    p.add_argument("what", choices=["tasks", "actors", "objects"])
    p.add_argument("--address", required=True)
    p.set_defaults(fn=cmd_summary)

    p = sub.add_parser("timeline",
                       help="merged Perfetto trace: task events + "
                            "flight-recorder stages + spans + chaos + "
                            "serve-fleet ingress events")
    p.add_argument("--address", required=True)
    p.add_argument("-o", "--output", default=None)
    p.add_argument("--trace-dir", default=None,
                   help="RAY_TPU_TRACE_DIR to merge span files from")
    p.add_argument("--serve-events", default=None,
                   help="Fleet.dump_events JSON to merge ingress "
                        "admission/shed/route events from")
    p.set_defaults(fn=cmd_timeline)

    p = sub.add_parser("stack", help="dump live worker thread stacks "
                                     "(reference: `ray stack`)")
    p.add_argument("--address", required=True)
    p.set_defaults(fn=cmd_stack)

    p = sub.add_parser("flame", help="sampling-profile a worker into a "
                                     "flamegraph SVG (reference: the "
                                     "dashboard's py-spy profiling)")
    p.add_argument("--address", required=True)
    p.add_argument("--pid", type=int, required=True)
    p.add_argument("--duration", type=float, default=2.0)
    p.add_argument("-o", "--output", default="flame.svg")
    p.set_defaults(fn=cmd_flame)

    p = sub.add_parser("kill-random-node",
                       help="chaos: hard-stop a random alive node "
                            "(reference: chaos release tests / "
                            "test_utils NodeKiller)")
    p.add_argument("--address", required=True,
                   help="any cluster node's address")
    p.add_argument("--spare", action="append", default=[],
                   help="node address to never kill (repeatable)")
    p.set_defaults(fn=cmd_kill_random_node)

    from ray_tpu.analysis.cli import add_parser as _add_lint
    _add_lint(sub)

    p = sub.add_parser("dashboard", help="serve the web dashboard")
    p.add_argument("--address", required=True)
    p.add_argument("--port", type=int, default=8265)
    p.set_defaults(fn=cmd_dashboard)

    p = sub.add_parser("job", help="submit/inspect cluster jobs")
    jsub = p.add_subparsers(dest="job_cmd", required=True)
    ps = jsub.add_parser("submit")
    ps.add_argument("--address", required=True)
    ps.add_argument("--working-dir", default=None)
    ps.add_argument("--wait", action="store_true")
    ps.add_argument("--timeout", type=float, default=600.0)
    ps.add_argument("entrypoint", nargs="+",
                    help="command to run on the cluster (after --)")
    ps.set_defaults(fn=cmd_job)
    for name in ("status", "logs", "stop"):
        pj = jsub.add_parser(name)
        pj.add_argument("--address", required=True)
        pj.add_argument("job_id")
        pj.set_defaults(fn=cmd_job)
    pl = jsub.add_parser("list")
    pl.add_argument("--address", required=True)
    pl.set_defaults(fn=cmd_job)

    p = sub.add_parser("serve", help="model-serving CLI")
    ssub = p.add_subparsers(dest="serve_cmd", required=True)
    pr = ssub.add_parser("run", help="deploy module:app and serve HTTP")
    pr.add_argument("import_path")
    pr.add_argument("--name", default=None)
    pr.add_argument("--address", default=None,
                    help="cluster address (default: local node)")
    pr.add_argument("--port", type=int, default=8000)
    pr.add_argument("--rest-port", type=int, default=8001)
    pr.set_defaults(fn=cmd_serve)
    pd = ssub.add_parser("deploy", help="PUT a config to a serve REST API")
    pd.add_argument("config_file")
    pd.add_argument("--address", required=True,
                    help="serve REST address, e.g. http://host:8001")
    pd.set_defaults(fn=cmd_serve)
    for name in ("status", "shutdown"):
        psx = ssub.add_parser(name)
        psx.add_argument("--address", required=True)
        psx.set_defaults(fn=cmd_serve)

    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
