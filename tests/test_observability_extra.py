"""Round-5 gap closers: sampling profiler + flamegraphs, spill
backends, container runtime-env gating, TF/Horovod backend contracts,
dashboard metrics history."""

import json
import time
import urllib.request

import numpy as np
import pytest

import ray_tpu


@pytest.fixture(scope="module")
def rt():
    ray_tpu.init(num_cpus=2, num_tpus=0,
                 object_store_memory=64 * 1024 * 1024)
    yield ray_tpu
    ray_tpu.shutdown()


# -- sampling profiler ------------------------------------------------------

def test_sample_folded_captures_own_stacks():
    from ray_tpu.util.profiling import sample_folded

    def busy(deadline):
        x = 0.0
        while time.monotonic() < deadline:
            x += 1.0
        return x

    import threading
    t = threading.Thread(target=busy, args=(time.monotonic() + 1.0,),
                         name="busy-thread")
    t.start()
    folded = sample_folded(duration=0.5, hz=200)
    t.join()
    assert any("busy" in line for line in folded.splitlines()), folded
    # folded format: path;path;... COUNT
    for line in folded.splitlines():
        assert line.rsplit(" ", 1)[1].isdigit()


def test_flamegraph_svg_renders():
    from ray_tpu.util.profiling import flamegraph_svg
    folded = "main;work;inner 10\nmain;work;other 5\nmain;idle 3"
    svg = flamegraph_svg(folded)
    assert svg.startswith("<svg") and svg.endswith("</svg>")
    assert svg.count("<rect") >= 5          # root bg + frames
    assert "inner" in svg and "&lt;" not in "inner"


def test_profile_live_worker_end_to_end(rt, tmp_path):
    from ray_tpu.core.observer import observer_query
    from ray_tpu.core.runtime import get_runtime

    @ray_tpu.remote
    def spin(started, stop, ceiling_s):
        """Busy from ``started`` until the file ``stop`` exists: however
        late the profile starts on a loaded machine, the task outlasts
        it."""
        import math
        import os
        open(started, "w").close()
        t0 = time.time()
        x = 0.0
        while time.time() - t0 < ceiling_s and not os.path.exists(stop):
            for _ in range(20000):
                x += math.sin(x) ** 2
        return x

    started, stop = tmp_path / "started", tmp_path / "stop"
    ref = spin.remote(str(started), str(stop), 150.0)
    try:
        # the task is RUNNING once its file is there: a worker that has
        # only just turned busy may still be connecting, and a profile
        # request that reaches it then waits in its inbox behind the
        # task (ROADMAP.md C17)
        deadline = time.monotonic() + 60
        while not started.exists():
            assert time.monotonic() < deadline, "the task never started"
            time.sleep(0.05)
        svc = get_runtime().node_service
        pid = next(c.pid for c in list(svc.clients.values())
                   if c.kind == "worker" and c.state == "busy")
        (reply,) = observer_query(
            svc.address,
            [{"t": "profile_worker", "pid": pid, "duration": 1.0}],
            request_timeout=60)
        folded = reply.get("folded", "")
        assert any("spin" in ln for ln in folded.splitlines()), folded
    finally:
        stop.touch()
    ray_tpu.get(ref, timeout=30)


# -- spill backends ---------------------------------------------------------

def test_file_spill_backend_roundtrip(tmp_path):
    from ray_tpu.core.spill import make_spill_backend
    b = make_spill_backend("", str(tmp_path / "spill"))
    loc = b.put("abc", b"hello world")
    assert b.get(loc) == b"hello world"
    b.delete(loc)
    with pytest.raises(FileNotFoundError):
        b.get(loc)


def test_s3_spill_backend_with_stub_client():
    from ray_tpu.core.spill import S3SpillBackend

    class StubS3:
        def __init__(self):
            self.objects = {}

        def put_object(self, Bucket, Key, Body):
            self.objects[(Bucket, Key)] = Body

        def get_object(self, Bucket, Key):
            import io
            return {"Body": io.BytesIO(self.objects[(Bucket, Key)])}

        def delete_object(self, Bucket, Key):
            self.objects.pop((Bucket, Key), None)

    stub = StubS3()
    b = S3SpillBackend("s3://bkt/spill/prefix", client=stub)
    loc = b.put("objhex", b"\x00\x01payload")
    assert loc == "s3://bkt/spill/prefix/objhex"
    assert b.get(loc) == b"\x00\x01payload"
    b.delete(loc)
    assert not stub.objects


def test_unknown_spill_scheme_rejected_at_config():
    from ray_tpu.core.spill import make_spill_backend
    with pytest.raises(ValueError, match="scheme"):
        make_spill_backend("gs://nope/x", "/tmp")


def test_spill_restore_through_backend(rt):
    """A real put > store budget spills through the backend and restores
    on get (the end-to-end spill path with the new indirection)."""
    from ray_tpu.core.runtime import get_runtime
    svc = get_runtime().node_service
    before = svc.store.stats()["num_spilled"]
    refs = [ray_tpu.put(np.ones(6 << 20, np.uint8)) for _ in range(14)]
    out = ray_tpu.get(refs[0], timeout=120)     # likely spilled: restore
    assert out.nbytes == 6 << 20
    assert svc.store.stats()["num_spilled"] > before
    ray_tpu.free(refs)


# -- container runtime env --------------------------------------------------

def test_container_env_validation():
    from ray_tpu.runtime_env import validate
    ok = validate({"container": {"image": "img:tag",
                                 "run_options": ["--cpus=2"]}})
    assert ok["container"]["image"] == "img:tag"
    with pytest.raises(ValueError, match="container"):
        validate({"container": "img:tag"})
    with pytest.raises(ValueError, match="container"):
        validate({"container": {"run_options": []}})


def test_container_command_construction():
    from ray_tpu.runtime_env import container_command
    argv = container_command(
        {"image": "repo/img:1", "run_options": ["--cpus=2"]},
        ["python", "-m", "ray_tpu.core.worker", "--address", "a:1"],
        "/tmp/ray_tpu/session_x", runtime="podman")
    assert argv[0] == "podman" and argv[1] == "run"
    assert "--network=host" in argv and "--ipc=host" in argv
    assert "-v" in argv and "/tmp/ray_tpu/session_x:/tmp/ray_tpu/session_x" in argv
    assert "--cpus=2" in argv
    assert argv[argv.index("repo/img:1") + 1] == "python"
    assert "RAY_TPU_CONTAINER_IMAGE=repo/img:1" in argv


def test_container_command_gated_without_runtime(monkeypatch):
    import shutil
    from ray_tpu.runtime_env import container_command
    monkeypatch.setattr(shutil, "which", lambda _: None)
    with pytest.raises(RuntimeError, match="podman nor docker"):
        container_command({"image": "x"}, ["cmd"], "/tmp/s")


def test_container_task_fails_with_clear_error(rt):
    @ray_tpu.remote(runtime_env={"container": {"image": "repo/img:9"}})
    def f():
        return 1

    with pytest.raises(Exception, match="container"):
        ray_tpu.get(f.remote(), timeout=120)


# -- TF / Horovod backend contracts ----------------------------------------

def test_tf_config_assembly():
    from ray_tpu.train import build_tf_config
    cfg = json.loads(build_tf_config(["h1:1", "h2:2", "h3:3"], 1))
    assert cfg["cluster"]["worker"] == ["h1:1", "h2:2", "h3:3"]
    assert cfg["task"] == {"type": "worker", "index": 1}


def test_tensorflow_trainer_sets_tf_config_on_every_worker(rt):
    """The backend's full contract without tensorflow itself: every
    rank's loop sees a consistent TF_CONFIG cluster spec (reference:
    tensorflow/config.py:21 — that IS the backend)."""
    import os as _os
    from ray_tpu.train import ScalingConfig, TensorflowTrainer
    from ray_tpu.train import session as ts

    def loop():
        cfg = json.loads(_os.environ["TF_CONFIG"])
        ts.report({"rank": cfg["task"]["index"],
                   "workers": len(cfg["cluster"]["worker"])})

    result = TensorflowTrainer(
        loop, scaling_config=ScalingConfig(num_workers=2)).fit()
    assert result.metrics["workers"] == 2


def test_horovod_env_layout():
    from ray_tpu.train import build_horovod_env
    hosts = ["10.0.0.1", "10.0.0.1", "10.0.0.2"]
    env1 = build_horovod_env(hosts, 1, "10.0.0.1", 9999)
    assert env1["HOROVOD_RANK"] == "1"
    assert env1["HOROVOD_SIZE"] == "3"
    assert env1["HOROVOD_LOCAL_RANK"] == "1"   # 2nd worker on host .1
    assert env1["HOROVOD_LOCAL_SIZE"] == "2"
    assert env1["HOROVOD_CROSS_SIZE"] == "2"
    env2 = build_horovod_env(hosts, 2, "10.0.0.1", 9999)
    assert env2["HOROVOD_LOCAL_RANK"] == "0"
    assert env2["HOROVOD_GLOO_RENDEZVOUS_PORT"] == "9999"


def test_horovod_trainer_env_contract(rt):
    import os as _os
    from ray_tpu.train import HorovodTrainer, ScalingConfig
    from ray_tpu.train import session as ts

    def loop():
        ts.report({"rank": int(_os.environ["HOROVOD_RANK"]),
                   "size": int(_os.environ["HOROVOD_SIZE"])})

    result = HorovodTrainer(
        loop, scaling_config=ScalingConfig(num_workers=2)).fit()
    assert result.metrics["size"] == 2


# -- dashboard metrics history ---------------------------------------------

def test_dashboard_metrics_history(rt):
    from ray_tpu.core.runtime import get_runtime
    from ray_tpu.dashboard import Dashboard

    svc = get_runtime().node_service
    db = Dashboard(svc.address, port=0, history_interval_s=0.3)
    db.start()
    try:
        @ray_tpu.remote
        def hold(s):
            time.sleep(s)
            return 1
        ref = hold.remote(1.5)
        time.sleep(1.2)
        hist = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{db.port}/api/metrics/history",
            timeout=10).read())
        assert len(hist) >= 2
        assert {"ts", "cpu_used", "tasks_running",
                "store_used_mb"} <= set(hist[-1])
        assert any(h["cpu_used"] > 0 for h in hist)
        ray_tpu.get(ref, timeout=60)
    finally:
        db.stop()
