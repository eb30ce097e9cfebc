"""Elastic gang: shrink-and-resume without restarting survivors.

Covers the gang layer (reform / readmit / prompt member-death
surfacing / formation-leak cleanup) in tier-1, and the trainer-level
kill-a-host-mid-epoch + head-loss-mid-fit flows behind ``slow``.
"""

from __future__ import annotations

import os
import signal
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.parallel.gang import GangMember, GangMemberDied, MultiHostGang


@pytest.fixture
def rt():
    ray_tpu.init(num_cpus=6, num_tpus=0)
    yield ray_tpu
    ray_tpu.shutdown()


def _spmd_sum(rank):
    """Cross-process allreduce whose value encodes the WORLD SIZE, so a
    reformed gang provably reshards dp to the new world."""
    import jax
    import jax.numpy as jnp
    import numpy as _np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    devs = jax.devices()
    mesh = Mesh(_np.array(devs).reshape(len(devs)), ("dp",))
    sh = NamedSharding(mesh, P("dp"))
    local = _np.full((1, 4), float(rank + 1))
    garr = jax.make_array_from_process_local_data(
        sh, local, (jax.process_count() or 1, 4))
    return float(jax.jit(jnp.sum)(garr))


class FailingSetupMember(GangMember):
    """Rank 1's setup dies — the partial-formation shape."""

    def setup(self, coordinator: str) -> dict:
        if self.rank == 1:
            raise RuntimeError("injected setup failure (rank 1)")
        return super().setup(coordinator)


def _gang_actor_states(client) -> list[str]:
    reply = client.request({"t": "state", "what": "actors"}, timeout=30)
    return [a["state"] for a in reply["data"]
            if "Member" in a.get("class_name", "")]


_RESILIENT_CHILD = """
import os, sys
import jax
from ray_tpu.parallel.jax_compat import (distributed_abandon,
                                         distributed_initialize)
rank = int(sys.argv[2])
mode = distributed_initialize(sys.argv[1], 2, rank, resilient=True)
from jax._src import distributed
assert distributed.global_state.client is not None
if rank == 0:
    assert distributed.global_state.service is not None
print("MODE", mode, jax.process_count(), len(jax.devices()), flush=True)
distributed_abandon()
assert distributed.global_state.client is None
os._exit(0)   # parked world: no shutdown handshake, by design
"""


def test_distributed_initialize_installs_the_resilient_client():
    """jax_compat reaches into jax's PRIVATE coordination-service
    surface (jax._src.lib._jax) for the peer-death-survivable client.
    On the installed jax it must return "resilient" — a jax that moves
    that surface fails HERE instead of the gang silently running with
    the stock client that kills surviving members.  Two real local CPU
    processes form one world."""
    import subprocess
    import sys

    from ray_tpu.parallel.gang import _free_port
    addr = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    procs = [subprocess.Popen(
        [sys.executable, "-c", _RESILIENT_CHILD, addr, str(rank)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for rank in range(2)]
    for rank, p in enumerate(procs):
        out, err = p.communicate(timeout=120)
        assert p.returncode == 0, f"rank {rank} failed:\n{err[-2000:]}"
        # world of 2 processes x 1 cpu device each
        assert "MODE resilient 2 2" in out, out


def test_distributed_initialize_has_no_silent_fallback(monkeypatch):
    """A private surface that no longer takes the installed arguments
    is an error, not a quiet downgrade to the public client."""
    from jax._src.lib import _jax

    from ray_tpu.parallel.jax_compat import distributed_initialize

    def moved(*a, **k):
        raise TypeError("get_distributed_runtime_service() got an "
                        "unexpected keyword argument")
    monkeypatch.setattr(_jax, "get_distributed_runtime_service", moved)
    with pytest.raises(TypeError):
        distributed_initialize("127.0.0.1:1", 1, 0, resilient=True)
    from jax._src import distributed
    assert distributed.global_state.client is None


def test_partial_formation_kills_all_members(rt):
    """One member's setup failing must not leak the other member
    actors (they used to stay alive — and hold their reservations —
    forever)."""
    with pytest.raises(Exception, match="injected setup failure"):
        MultiHostGang(2, cpu_backend=True, devices_per_member=1,
                      member_cls=FailingSetupMember, setup_timeout=120)
    client = ray_tpu.get_runtime().client
    deadline = time.time() + 60
    while time.time() < deadline:
        states = _gang_actor_states(client)
        if states and all(s == "dead" for s in states):
            return
        time.sleep(0.2)
    pytest.fail(f"leaked gang members after failed formation: "
                f"{_gang_actor_states(client)}")


def test_member_death_during_run_names_rank_promptly(rt):
    gang = MultiHostGang(2, cpu_backend=True, devices_per_member=1)
    try:
        pids = gang.member_pids()

        def long_attempt(rank):
            time.sleep(120)
            return rank

        holder: dict = {}

        def run():
            t0 = time.perf_counter()
            try:
                gang.run(long_attempt)
            except Exception as e:
                holder["error"] = e
            holder["elapsed"] = time.perf_counter() - t0

        t = threading.Thread(target=run)
        t.start()
        time.sleep(2.0)          # let the run land on both members
        os.kill(pids[1], signal.SIGKILL)
        t.join(timeout=60)
        assert not t.is_alive(), "run() hung after member death"
        err = holder.get("error")
        assert isinstance(err, GangMemberDied), err
        assert err.rank == 1                      # names the dead rank
        assert "rank 1" in str(err)
        assert holder["elapsed"] < 30, \
            f"death took {holder['elapsed']:.1f}s to surface"
    finally:
        gang.shutdown()


@pytest.mark.slow
@pytest.mark.chaos
def test_reform_shrinks_then_readmits_without_restarting_survivors(rt):
    """THE elastic contract: kill one of three members; reform keeps
    the survivors' PROCESSES (same pids) and reshards dp to world 2;
    readmit grows back to 3 with one fresh process, survivors still
    untouched."""
    gang = MultiHostGang(3, cpu_backend=True, devices_per_member=1)
    try:
        pids = gang.member_pids()
        assert len(set(pids)) == 3
        assert gang.run(_spmd_sum, timeout=300) == [24.0] * 3  # (1+2+3)*4

        os.kill(pids[1], signal.SIGKILL)
        alive = []
        deadline = time.time() + 60
        while time.time() < deadline:
            alive = gang.alive_ranks()
            if alive == [0, 2]:
                break
            time.sleep(0.2)
        assert alive == [0, 2], alive

        gang.reform(alive)
        assert gang.num_members == 2
        new_pids = gang.member_pids()
        assert new_pids == [pids[0], pids[2]]     # survivors NOT restarted
        # dp resharded to the new world: ranks are 0,1 now → (1+2)*4
        assert gang.run(_spmd_sum, timeout=300) == [12.0] * 2

        assert gang.readmit() == 3                # back to target world
        final_pids = gang.member_pids()
        assert final_pids[:2] == [pids[0], pids[2]]
        assert final_pids[2] not in pids          # a fresh replacement
        assert gang.run(_spmd_sum, timeout=300) == [24.0] * 3
    finally:
        gang.shutdown()


# ---------------------------------------------------------------------------
# trainer-level flows (long: behind slow)


def _make_trainer(tmp_path, num_hosts, num_steps=30, name="elastic"):
    import jax.numpy as jnp
    import optax

    from ray_tpu.train import JaxTrainer
    from ray_tpu.train.config import (FailureConfig, RunConfig,
                                      ScalingConfig)

    class SlowBatches:
        def __init__(self, n):
            self.n = n

        def __iter__(self):
            rng = np.random.RandomState(0)
            for _ in range(self.n):
                time.sleep(0.12)
                yield {"x": rng.rand(6, 4).astype(np.float32)}

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - 1.0) ** 2)

    def init_params(key):
        import jax
        return {"w": jax.random.normal(key, (4, 1)) * 0.1}

    return JaxTrainer(
        loss_fn=loss_fn, init_params=init_params,
        optimizer=optax.adam(0.1),
        train_data=SlowBatches(num_steps + 5),
        num_steps=num_steps,
        params_logical=None, rules=(),
        report_every=5, checkpoint_every=5,
        scaling_config=ScalingConfig(mesh={"dp": -1}, num_hosts=num_hosts,
                                     use_cpu_devices=True,
                                     devices_per_host=1),
        run_config=RunConfig(name=name, storage_path=str(tmp_path),
                             failure_config=FailureConfig(max_failures=2)))


def _wait_for_checkpoint(tmp_path, name, timeout=120):
    root = os.path.join(str(tmp_path), name, "checkpoints")
    deadline = time.time() + timeout
    while time.time() < deadline:
        if os.path.isdir(root) and any(
                d.startswith("checkpoint_") for d in os.listdir(root)):
            return
        time.sleep(0.1)
    pytest.fail("no checkpoint appeared before the kill")


@pytest.mark.slow
@pytest.mark.chaos
def test_trainer_kill_host_mid_epoch_shrinks_and_resumes(rt, tmp_path):
    """Acceptance: kill one of three members mid-epoch; the gang
    shrinks 3→2, the SURVIVING member processes keep their pids,
    training resumes from the last checkpoint and reaches the target
    step — no full-gang restart."""
    num_steps = 30
    trainer = _make_trainer(tmp_path, num_hosts=3, num_steps=num_steps)
    gang = trainer.gang
    pids = gang.member_pids()
    assert len(set(pids)) == 3

    holder: dict = {}

    def run_fit():
        try:
            holder["result"] = trainer.fit()
        except Exception as e:
            holder["error"] = e

    t = threading.Thread(target=run_fit)
    t.start()
    _wait_for_checkpoint(tmp_path, "elastic")
    os.kill(pids[1], signal.SIGKILL)

    t.join(timeout=600)
    assert not t.is_alive(), "fit() hung after member death"
    assert "error" not in holder, holder.get("error")
    result = holder["result"]
    assert result.error is None
    assert result.metrics["step"] == num_steps
    steps_seen = [m["step"] for m in result.metrics_history]
    assert steps_seen[-1] == num_steps

    # the elastic contract, post-hoc: same gang object, shrunk to the
    # survivors, whose processes were never restarted
    gang2 = trainer.gang
    assert gang2 is gang
    assert gang2.num_members == 2
    assert gang2.member_pids() == [pids[0], pids[2]]


@pytest.mark.slow
@pytest.mark.chaos
def test_trainer_head_killed_mid_fit_completes_via_promotion(tmp_path):
    """Acceptance: the head MACHINE dies mid-fit (local snapshot gone);
    a replacement head is promoted from a surviving node's replica;
    training completes with no client-visible error."""
    from ray_tpu.cluster_utils import Cluster

    c = Cluster(head_persistence=True)
    try:
        n0 = c.add_node(num_cpus=4)
        c.add_node(num_cpus=4)
        c.wait_for_nodes()
        ray_tpu.init(address=n0.address)

        num_steps = 30
        trainer = _make_trainer(tmp_path, num_hosts=2, num_steps=num_steps,
                                name="headloss")
        holder: dict = {}

        def run_fit():
            try:
                holder["result"] = trainer.fit()
            except Exception as e:
                holder["error"] = e

        t = threading.Thread(target=run_fit)
        t.start()
        _wait_for_checkpoint(tmp_path, "headloss")

        # kill the head mid-epoch, snapshot included (machine loss)...
        c.head.stop()
        time.sleep(2.0)
        # ...and promote a replacement from the freshest node replica
        c.restart_head(simulate_machine_loss=True)

        t.join(timeout=600)
        assert not t.is_alive(), "fit() hung across head failover"
        assert "error" not in holder, holder.get("error")
        result = holder["result"]
        assert result.error is None
        assert result.metrics["step"] == num_steps
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        c.shutdown()
