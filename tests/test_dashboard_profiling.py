"""Dashboard depth + worker profiling.

Reference: dashboard/ (task drill-down, log viewer),
dashboard/modules/reporter/profile_manager.py:11 and `ray stack`
(python/ray/scripts/scripts.py:1767) — on-demand stack dumps of live
workers.
"""

from __future__ import annotations

import json
import time
import urllib.request

import pytest

import ray_tpu


@pytest.fixture
def rt():
    r = ray_tpu.init(num_cpus=1, num_tpus=0)
    yield r
    ray_tpu.shutdown()


def _get(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return json.loads(r.read())


def test_dashboard_task_drilldown_logs_and_stack(rt):
    from ray_tpu.dashboard import Dashboard

    @ray_tpu.remote
    def loud(x):
        print(f"loud says {x}")
        return x * 2

    @ray_tpu.remote
    def napper():
        time.sleep(8.0)
        return "rested"

    assert ray_tpu.get(loud.remote(21), timeout=90) == 42
    nap_ref = napper.remote()

    dash = Dashboard(rt.node_service.address, port=0)
    dash.start()
    base = f"http://127.0.0.1:{dash.port}"
    try:
        s = _get(base + "/api/summary")
        assert s["nodes"] and s["workers"]
        loud_task = next(t for t in s["recent_tasks"]
                         if t["name"].endswith("loud"))
        assert loud_task["state"] == "finished"

        # drill-down: the finished task has a full event timeline
        ev = _get(base + f"/api/tasks/{loud_task['task_id']}")
        states = [e["state"] for e in ev["events"]]
        assert "PENDING" in states and "RUNNING" in states \
            and "FINISHED" in states

        # per-worker logs: the print landed in a worker .out file
        files = _get(base + "/api/logs")["files"]
        assert any(f["name"].endswith(".out") for f in files)
        outs = [f["name"] for f in files if f["name"].endswith(".out")]
        found = ""
        for name in outs:
            body = _get(base + f"/api/logs?name={name}")
            if "loud says 21" in (body.get("data") or ""):
                found = name
        assert found, "task stdout never reached a worker log"

        # live stack dump of the worker running the sleeping task
        deadline = time.time() + 60
        busy = None
        while time.time() < deadline and busy is None:
            s = _get(base + "/api/summary")
            busy = next((w for w in s["workers"]
                         if w["kind"] == "worker"
                         and w["state"] != "idle"), None)
            if busy is None:
                time.sleep(0.2)
        assert busy is not None, "napper never showed as busy"
        dump = _get(base + f"/api/stack?pid={busy['pid']}")
        assert not dump.get("error"), dump
        assert "Thread" in dump["data"] or "File" in dump["data"]
        # the dump caught the worker inside the user function
        assert "napper" in dump["data"] or "sleep" in dump["data"]
    finally:
        dash.stop()
    assert ray_tpu.get(nap_ref, timeout=90) == "rested"


def test_stack_cli(rt, capsys, tmp_path):
    from ray_tpu.scripts import main as cli_main

    stop = tmp_path / "release_hold"
    started = tmp_path / "hold_started"

    @ray_tpu.remote
    def hold(started_path, stop_path):
        # run until the test has captured the stack — a fixed sleep
        # raced the dump under parallel suite load
        import os as _os
        open(started_path, "w").close()
        deadline = time.time() + 60
        while not _os.path.exists(stop_path) and time.time() < deadline:
            time.sleep(0.1)
        return 1

    ref = hold.remote(str(started), str(stop))
    # wait for the task BODY, not for a busy worker: a worker is busy
    # from the dispatch on, while it still unpickles the function, and
    # a stack taken then has no frame of `hold` (failed alone, every run)
    deadline = time.time() + 60
    while time.time() < deadline and not started.exists():
        time.sleep(0.05)
    assert started.exists(), "hold never started"
    rc = cli_main(["stack", "--address", rt.node_service.address])
    out = capsys.readouterr().out
    stop.write_text("go")
    assert rc == 0
    assert "worker pid=" in out
    assert "sleep" in out or "hold" in out
    assert ray_tpu.get(ref, timeout=90) == 1
