"""Cluster launcher: up/down/attach/exec from YAML configs (reference:
python/ray/scripts/scripts.py up:1216 down:1292 attach:1376 exec:1674
over autoscaler/_private/commands.py)."""

import json
import os

import pytest

from ray_tpu.autoscaler import commands as C


@pytest.fixture(autouse=True)
def isolated_state(tmp_path, monkeypatch):
    monkeypatch.setattr(C, "_STATE_DIR", str(tmp_path / "clusters"))


def _write_cfg(tmp_path, **over):
    import yaml
    cfg = {"cluster_name": "t1",
           "provider": {"type": "tpu_pod", "project": "p",
                        "zone": "us-central2-b"},
           "min_workers": 0, "max_workers": 3, "initial_workers": 2}
    cfg.update(over)
    p = tmp_path / "cluster.yaml"
    p.write_text(yaml.safe_dump(cfg))
    return str(p)


class StubProvider:
    """Records lifecycle calls; mimics the TpuPodNodeProvider surface."""

    def __init__(self):
        self.calls = []
        self._n = 0
        self.live = set()

    def create_head(self, node_config, port=6380):
        self.calls.append(("create_head", port))
        self.live.add("head-1")
        return "head-1", f"10.0.0.1:{port}"

    def create_node(self, head_address, node_config):
        self._n += 1
        nid = f"w-{self._n}"
        self.calls.append(("create_node", head_address, nid))
        self.live.add(nid)
        return nid

    def terminate_node(self, node_id):
        self.calls.append(("terminate", node_id))
        self.live.discard(node_id)

    def non_terminated_nodes(self):
        return []

    def exec_on(self, node_id, command, all_workers=False):
        self.calls.append(("exec", node_id, command, all_workers))
        return f"ran on {node_id}"

    def ssh_command(self, node_id):
        return ["ssh", node_id]


def test_config_validation(tmp_path):
    import yaml
    bad = tmp_path / "bad.yaml"
    bad.write_text(yaml.safe_dump({"provider": {"type": "tpu_pod"}}))
    with pytest.raises(C.ClusterConfigError, match="cluster_name"):
        C.load_cluster_config(str(bad))
    bad.write_text(yaml.safe_dump({"cluster_name": "x",
                                   "provider": {"type": "nope"}}))
    with pytest.raises(C.ClusterConfigError, match="provider.type"):
        C.load_cluster_config(str(bad))
    bad.write_text(yaml.safe_dump({"cluster_name": "x",
                                   "provider": {"type": "tpu_pod"}}))
    with pytest.raises(C.ClusterConfigError, match="project"):
        C.load_cluster_config(str(bad))
    bad.write_text(yaml.safe_dump({
        "cluster_name": "x", "min_workers": 3, "max_workers": 1,
        "provider": {"type": "tpu_pod", "project": "p", "zone": "z"}}))
    with pytest.raises(C.ClusterConfigError, match="min_workers"):
        C.load_cluster_config(str(bad))


def test_up_exec_attach_down_lifecycle(tmp_path):
    cfg = C.load_cluster_config(_write_cfg(tmp_path))
    prov = StubProvider()
    logs = []

    state = C.up(cfg, provider=prov, log=logs.append)
    assert state["head_address"] == "10.0.0.1:6380"
    assert state["workers"] == ["w-1", "w-2"]
    assert ("create_head", 6380) in prov.calls
    assert ("create_node", "10.0.0.1:6380", "w-1") in prov.calls

    # state persisted: a second up is idempotent on the head
    state2 = C.up(cfg, provider=prov, log=logs.append)
    assert state2["head_id"] == "head-1"
    assert prov.calls.count(("create_head", 6380)) == 1

    out = C.exec_cmd(cfg, "hostname", provider=prov)
    assert out == "ran on head-1"
    assert ("exec", "head-1", "hostname", False) in prov.calls

    out = C.exec_cmd(cfg, "uptime", provider=prov, on_head=False)
    assert out == "ran on w-1\nran on w-2"

    assert C.attach_argv(cfg, provider=prov) == ["ssh", "head-1"]

    C.down(cfg, provider=prov, log=logs.append)
    assert prov.live == set()
    assert C.load_state("t1") is None


def test_down_partial_failure_keeps_tearing_down(tmp_path):
    cfg = C.load_cluster_config(_write_cfg(tmp_path))
    prov = StubProvider()
    C.up(cfg, provider=prov, log=lambda *_: None)

    orig = prov.terminate_node
    def flaky(nid):
        if nid == "w-1":
            raise RuntimeError("gcloud transient")
        orig(nid)
    prov.terminate_node = flaky

    C.down(cfg, provider=prov, log=lambda *_: None)
    # w-2 and the head still torn down; state cleared
    assert "w-2" not in prov.live and "head-1" not in prov.live
    assert C.load_state("t1") is None


def test_submit_uploads_then_runs(tmp_path):
    cfg = C.load_cluster_config(_write_cfg(tmp_path))
    prov = StubProvider()
    C.up(cfg, provider=prov, log=lambda *_: None)
    script = tmp_path / "job.py"
    script.write_text("print('hi')\n")
    C.submit(cfg, str(script), provider=prov, log=lambda *_: None)
    execs = [c for c in prov.calls if c[0] == "exec"]
    import base64
    assert base64.b64encode(b"print('hi')\n").decode() in execs[-2][2]
    assert execs[-1][2].startswith("python /tmp/ray_tpu_submit_")


def test_tpu_pod_provider_head_lifecycle(monkeypatch):
    """create_head over the stubbed gcloud CLI: create → READY →
    bootstrap head on worker 0 → describe for the internal IP."""
    import shutil as _shutil
    from ray_tpu.autoscaler import tpu_pod_provider as tp

    monkeypatch.setattr(_shutil, "which", lambda _: "/usr/bin/gcloud")
    calls = []

    def fake_run(self, *args, timeout=600.0):
        calls.append(args)
        if args[0] == "describe":
            return json.dumps({"state": "READY", "networkEndpoints":
                               [{"ipAddress": "10.1.2.3"}]})
        if args[0] == "ssh" and any("pgrep" in a for a in args):
            return "HEAD_ALIVE\n"
        return "{}"

    monkeypatch.setattr(tp.TpuPodNodeProvider, "_run", fake_run)
    p = tp.TpuPodNodeProvider(project="p", zone="z")
    p._poll_s = 0.01
    nid, addr = p.create_head({}, port=6380)
    assert nid.startswith("ray-tpu-head-")
    assert addr == "10.1.2.3:6380"
    boot = next(c for c in calls if c[0] == "ssh"
                and not any("pgrep" in a for a in c))
    assert any("--worker=0" in a for a in boot)
    assert any("start --head" in a for a in boot)
    assert p.exec_on(nid, "echo hi") == "{}"
    assert p.ssh_command(nid)[:6] == ["gcloud", "compute", "tpus",
                                      "tpu-vm", "ssh", nid]


def test_local_provider_end_to_end(tmp_path):
    """`provider.type: local`: a real head process + a real worker node
    process come up, a driver connects and runs a task, down() reaps."""
    import time
    import yaml

    import ray_tpu
    from ray_tpu.autoscaler.node_provider import LocalNodeProvider

    cfgp = tmp_path / "local.yaml"
    cfgp.write_text(yaml.safe_dump({
        "cluster_name": "loc1",
        "provider": {"type": "local", "base_dir": str(tmp_path / "nodes")},
        # a port asked of the OS: the default, 6380, is held for good by
        # any head a killed run left behind, and this test then failed
        # with "local head did not publish its address"
        "head": {"port": 0},
        "initial_workers": 1,
        "worker_nodes": {"num_cpus": 2}}))
    cfg = C.load_cluster_config(str(cfgp))
    prov = LocalNodeProvider(base_dir=str(tmp_path / "nodes"))
    try:
        state = C.up(cfg, provider=prov, log=lambda *_: None)
        # join the launched cluster through the worker node's address —
        # resolve it by polling the head for membership
        ray_tpu.init(address=_wait_node_addr(state, prov))

        @ray_tpu.remote
        def f():
            return "up"
        assert ray_tpu.get(f.remote(), timeout=120) == "up"
        ray_tpu.shutdown()
    finally:
        C.down(cfg, provider=prov, log=lambda *_: None)
    assert prov.non_terminated_nodes() == []


def _wait_node_addr(state, prov, timeout=60):
    """The driver connects to a NODE service; ask the head for one."""
    import time

    from ray_tpu.core import protocol

    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            conn = protocol.connect(state["head_address"], timeout=5.0)
            conn.send({"t": "state", "what": "nodes", "reqid": 1})
            reply = conn.recv(timeout=5.0)
            conn.close()
            for n in reply.get("data") or []:
                if n.get("alive") and n.get("address"):
                    return n["address"]
        except Exception:
            pass
        time.sleep(0.5)
    raise RuntimeError("no alive node joined the launched head")
