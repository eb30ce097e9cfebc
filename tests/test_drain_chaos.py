"""Chaos coverage for the drain protocol (ISSUE 14): planned removal
must stay graceful under real failures — a replica drain interrupted by
a genuine kill falls back to token-exact resume, a node killed
mid-decommission still converges via lineage reconstruction, and a
drain that can't finish takes the EXPLICIT timeout path (counted, never
masked).  All scripted through ``FaultPlan.on_drain``."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.cluster_utils import Cluster
from ray_tpu.core import fault_injection
from ray_tpu.inference import EngineConfig, build_gpt_deployment
from ray_tpu.models import gpt
from ray_tpu.serve import fleet
from ray_tpu.serve.fleet import FleetConfig

pytestmark = pytest.mark.chaos

CFG = gpt.GPTConfig.tiny(dtype=jnp.float32, max_seq=64)
SEED = 0


@pytest.fixture(autouse=True)
def _cleanup():
    yield
    fault_injection.uninstall()
    serve.shutdown()


def _ref_tokens(prompt, max_new):
    params = gpt.init_params(CFG, jax.random.PRNGKey(SEED))
    out = gpt.generate(params, CFG, jnp.asarray([prompt], jnp.int32),
                       max_new=max_new, temperature=0.0)
    return np.asarray(out)[0, len(prompt):].tolist()


def _run_fleet(num_replicas=2):
    dep = build_gpt_deployment(
        cfg=CFG, engine_cfg=EngineConfig(max_slots=4), seed=SEED,
        num_replicas=num_replicas)
    handle = serve.run(dep, use_actors=False)
    f = fleet.enable("v1", FleetConfig(rate=500, burst=64))
    return handle, f


def _serving_replica(st, f):
    """The replica the last route event picked (stream in flight)."""
    tag = [e for e in f.events() if e["kind"] == "route"][-1]["replica"]
    with st._lock:
        return next(r for r in st.replicas if r.tag == tag)


# ------------------------------------------------ (1) drain + real kill


def test_replica_drain_interrupted_by_kill_resumes_token_exact():
    """A replica being DRAINED dies for real before it finishes its
    in-flight stream (chaos kill scripted at the replica_drain point):
    the fallback is the token-exact resume path — the client still sees
    one seamless stream, and the re-route is classified as a SCALE-DOWN
    resume (the replica had already left "active")."""
    handle, f = _run_fleet(num_replicas=2)
    st = serve.get_handle("v1")._state
    prompt, max_tokens = [9, 2, 6], 24

    def kill_mid_drain(ctx):
        # a genuine crash landing exactly when the drain begins
        ctx["state"].fleet.kill_replica(ctx["replica"])

    plan = fault_injection.FaultPlan(seed=0)
    plan.script(kill_mid_drain, point="replica_drain", nth=1)

    gen = handle.remote({"prompt": prompt, "max_tokens": max_tokens,
                         "stream": True}).result(timeout=120)
    chunks = [next(gen)]
    victim = _serving_replica(st, f)
    with fault_injection.injected(plan):
        st.drain_replicas(1, 30.0, replicas=[victim])
        for c in gen:
            chunks.append(c)
    toks = [c["token"] for c in chunks if "token" in c]
    assert toks == _ref_tokens(prompt, max_tokens)
    assert [c["index"] for c in chunks if "token" in c] \
        == list(range(max_tokens))
    snap = f.fleet_snapshot()
    assert snap["resumed_scale_down"] >= 1
    assert snap["resumed_failure"] == 0
    assert snap["admitted"] == snap["completed"] + snap["errored"] \
        + snap["cancelled"]
    assert any(p == "replica_drain" for p, _, _ in plan.log)


# --------------------------------- (2) node killed mid-decommission


def test_node_killed_mid_decommission_recovers_via_lineage():
    """The handoff is NOT load-bearing for durability: a node hard-
    killed just before its owned-object handoff ships (scripted at
    node_drain_handoff) loses the handoff entirely — and the object is
    STILL recovered, by lineage re-execution on the owner."""
    c = Cluster()
    n0 = c.add_node(num_cpus=2)
    a = c.add_node(num_cpus=2, resources={"tag": 2})
    b = c.add_node(num_cpus=2, resources={"tag": 2})
    try:
        c.wait_for_nodes()
        ray_tpu.init(address=n0.address)

        @ray_tpu.remote(resources={"tag": 1})
        def produce():
            return np.arange(200_000, dtype=np.int64)   # shm-sized

        ref = produce.remote()
        ob = ref.id.binary()
        deadline = time.time() + 60
        while time.time() < deadline:
            orec = n0.owned.get(ob)
            if orec is not None and orec.locations \
                    and ob not in n0._fwd_by_oid:
                break
            time.sleep(0.05)
        else:
            pytest.fail("producer never settled")
        holder_hex = next(iter(n0.owned[ob].locations))
        victim = next(n for n in (a, b)
                      if n.node_id.hex() == holder_hex)

        def hard_kill(ctx):
            ctx["node"]._stop.set()    # dies before the handoff ships

        plan = fault_injection.FaultPlan(seed=0)
        plan.script(hard_kill, point="node_drain_handoff", nth=1)
        with fault_injection.injected(plan):
            ray_tpu.drain_node(victim.node_id.hex(), deadline_s=10)
            # the read follows the DEATH: drain_node returns when the
            # drain has begun, and a read sent at once sometimes pulled
            # the only copy off the victim before the scripted kill
            # landed; nothing was then lost and nothing reconstructed
            # (recons 0: failed alone and in turns, by box load)
            deadline = time.time() + 60
            while victim._thread.is_alive() and time.time() < deadline:
                time.sleep(0.05)
            assert not victim._thread.is_alive(), "scripted kill never landed"
            out = ray_tpu.get(ref, timeout=120)
        assert out.shape == (200_000,) and out[123] == 123
        recons = sum(lin["recons"] for lin in n0.lineage.values())
        assert recons >= 1, "mid-decommission kill must fall back to " \
                            "lineage reconstruction"
        assert any(p == "node_drain_handoff" for p, _, _ in plan.log)
    finally:
        ray_tpu.shutdown()
        c.shutdown()


# ------------------------------------------- (3) deadline expiry path


def test_drain_deadline_expiry_takes_explicit_timeout_path():
    """A drain whose deadline passes with work still in flight falls
    back to kill+resume EXPLICITLY: counted as drain_timeout (never
    ``drained``, never masked), the stream resumes token-exact on a
    survivor, and the re-route is classified resumed_scale_down."""
    handle, f = _run_fleet(num_replicas=2)
    st = serve.get_handle("v1")._state
    prompt, max_tokens = [5, 5], 48

    fired = []
    plan = fault_injection.FaultPlan(seed=0)
    plan.script(lambda ctx: fired.append(ctx["replica"].tag),
                point="replica_drain_timeout", nth=1)

    gen = handle.remote({"prompt": prompt, "max_tokens": max_tokens,
                         "stream": True}).result(timeout=120)
    chunks = [next(gen)]
    victim = _serving_replica(st, f)
    with fault_injection.injected(plan):
        st.drain_replicas(1, 0.0, replicas=[victim])  # already expired
        st.drain_tick()        # deterministic: don't race the 250ms tick
        for c in gen:
            chunks.append(c)
    toks = [c["token"] for c in chunks if "token" in c]
    assert toks == _ref_tokens(prompt, max_tokens)
    snap = f.fleet_snapshot()
    assert snap["drain_timeout"] == 1
    assert snap["resumed_scale_down"] >= 1
    assert snap["resumed_failure"] == 0
    assert fired == [victim.tag]
    kinds = [e["kind"] for e in f.events()]
    assert "drain_timeout" in kinds and "drain_complete" not in kinds
