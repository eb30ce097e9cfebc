"""Fleet serving layer: admission control + shedding, occupancy
routing, model multiplexing, priority preemption, per-replica metric
labels, the ingress timeline merge, and the HTTP surface (429 +
Retry-After, client-disconnect cancellation)."""

import json
import re
import socket
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu import serve
from ray_tpu.inference import (EngineConfig, build_gpt_deployment,
                               parse_stream_chunks)
from ray_tpu.inference.engine import (PRIORITY_BATCH, PRIORITY_INTERACTIVE,
                                      InferenceEngine)
from ray_tpu.models import gpt
from ray_tpu.serve import fleet
from ray_tpu.serve.fleet import (FleetConfig, ModelMultiplexer, ShedError,
                                 TokenBucket)
from ray_tpu.serve.fleet.admission import AdmissionController

pytestmark = pytest.mark.serve_fleet

CFG = gpt.GPTConfig.tiny(dtype=jnp.float32, max_seq=64)
SEED = 0


@pytest.fixture(autouse=True)
def _cleanup():
    yield
    serve.shutdown()


def _ref_tokens(prompt, max_new):
    params = gpt.init_params(CFG, jax.random.PRNGKey(SEED))
    out = gpt.generate(params, CFG, jnp.asarray([prompt], jnp.int32),
                       max_new=max_new, temperature=0.0)
    return np.asarray(out)[0, len(prompt):].tolist()


def _run_fleet(num_replicas=2, fleet_cfg=None, http=False, **dep_kw):
    dep = build_gpt_deployment(
        cfg=CFG, engine_cfg=dep_kw.pop("engine_cfg",
                                       EngineConfig(max_slots=4)),
        seed=SEED, num_replicas=num_replicas, **dep_kw)
    handle = serve.run(dep, use_actors=False, http=http)
    f = fleet.enable("v1", fleet_cfg or FleetConfig(rate=500, burst=64))
    return handle, f


def _post(addr, path, payload, timeout=120):
    req = urllib.request.Request(
        addr + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


# ---------------------------------------------------------------- admission


def test_token_bucket_refill_math():
    b = TokenBucket(rate=10.0, burst=2)
    t = time.monotonic() + 100.0
    assert b.take(t) and b.take(t) and not b.take(t)
    # 0.1 s -> one token back
    assert b.take(t + 0.1) and not b.take(t + 0.1)
    assert b.time_to_token(t + 0.1) == pytest.approx(0.1, abs=0.02)


def test_admission_fast_path_and_queue_full_shed():
    adm = AdmissionController(rate=1000.0, burst=2, max_queue_depth=0,
                              max_queue_wait_s=5.0)
    assert adm.acquire(PRIORITY_BATCH) == 0.0
    assert adm.acquire(PRIORITY_BATCH) == 0.0
    # burst drained, zero queue depth: immediate shed with a back-off
    with pytest.raises(ShedError) as ei:
        adm.acquire(PRIORITY_BATCH)
    assert ei.value.retry_after_s >= 0.0
    assert adm.stats.shed_queue_full == 1


def test_admission_deadline_shed():
    adm = AdmissionController(rate=0.5, burst=1, max_queue_depth=8,
                              max_queue_wait_s=0.1)
    adm.acquire(PRIORITY_BATCH)               # drain the bucket
    t0 = time.monotonic()
    with pytest.raises(ShedError) as ei:
        adm.acquire(PRIORITY_BATCH)           # 2 s/token >> 0.1 s deadline
    assert time.monotonic() - t0 < 1.0        # shed promptly, not at 2 s
    assert ei.value.reason == "queue deadline"
    assert adm.stats.shed_deadline == 1


def test_admission_priority_order_interactive_first():
    """Parked interactive requests take tokens ahead of batch requests
    that arrived EARLIER — the queue is priority-ordered, not FIFO."""
    adm = AdmissionController(rate=5.0, burst=1, max_queue_depth=8,
                              max_queue_wait_s=10.0)
    adm.acquire(PRIORITY_BATCH)               # drain
    order = []
    lock = threading.Lock()

    def worker(prio, name):
        adm.acquire(prio)
        with lock:
            order.append(name)

    batch = threading.Thread(target=worker, args=(PRIORITY_BATCH, "batch"))
    batch.start()
    time.sleep(0.05)                          # batch parks first
    inter = threading.Thread(target=worker,
                             args=(PRIORITY_INTERACTIVE, "interactive"))
    inter.start()
    batch.join(timeout=10)
    inter.join(timeout=10)
    assert order == ["interactive", "batch"]


# ------------------------------------------------------------------ routing


class _FakeUser:
    def __init__(self, stats):
        self._stats = stats

    def fleet_stats(self):
        return dict(self._stats)


def _fake_state(stats_list, maxq=32):
    """A DeploymentState-shaped object with stubbed in-proc replicas."""
    from ray_tpu.serve.controller import ReplicaHandle

    class _Impl:
        def __init__(self, user):
            self._user = user

    class _State:
        class _Dep:
            class options:
                max_concurrent_queries = maxq
            name = "fake"
        deployment = _Dep()
        _lock = threading.Lock()

    st = _State()
    st.replicas = [ReplicaHandle(_Impl(_FakeUser(s)), False, f"fake#{i}")
                   for i, s in enumerate(stats_list)]
    return st


def test_router_prefers_lower_occupancy():
    from ray_tpu.serve.fleet.router import OccupancyRouter
    st = _fake_state([
        {"max_slots": 8, "active_slots": 8, "waiting_requests": 6,
         "stopped": False, "models": []},
        {"max_slots": 8, "active_slots": 1, "waiting_requests": 0,
         "stopped": False, "models": []},
    ])
    r = OccupancyRouter(st, seed=1)
    picks = [r.assign().tag for _ in range(10)]
    assert picks.count("fake#1") == 10


def test_router_skips_stopped_and_prefers_model_holders():
    from ray_tpu.serve.fleet.router import OccupancyRouter
    st = _fake_state([
        {"max_slots": 8, "active_slots": 0, "waiting_requests": 0,
         "stopped": True, "models": []},                      # dead
        {"max_slots": 8, "active_slots": 7, "waiting_requests": 2,
         "stopped": False, "models": ["m2"]},                 # busy holder
        {"max_slots": 8, "active_slots": 0, "waiting_requests": 0,
         "stopped": False, "models": ["m1"]},                 # idle non-holder
    ])
    r = OccupancyRouter(st, seed=1)
    # model=m2: the busy HOLDER wins over the idle non-holder (variant
    # residency outranks load), and the dead replica is never picked
    assert all(r.assign("m2").tag == "fake#1" for _ in range(5))
    # no model: idle replica wins on occupancy
    assert r.assign().tag == "fake#2"


def test_router_prefix_affinity_prefers_holder():
    """The ``prefer`` hint (cluster prefix plane): a directory-confirmed
    holder wins outright over a less-loaded replica — serving there
    reuses cached KV with no transfer at all."""
    from ray_tpu.serve.fleet.router import OccupancyRouter
    st = _fake_state([
        {"max_slots": 8, "active_slots": 6, "waiting_requests": 2,
         "stopped": False, "models": []},               # busy holder
        {"max_slots": 8, "active_slots": 0, "waiting_requests": 0,
         "stopped": False, "models": []},               # idle
    ])
    r = OccupancyRouter(st, seed=1)
    assert r.assign(prefer="fake#0").tag == "fake#0"
    # unknown/dead preference degrades to the normal occupancy pick
    assert r.assign(prefer="nope#9").tag == "fake#1"


def test_router_prefer_skips_draining_holder_without_dead_mark():
    """Regression (drain vs dead-mark): a DRAINING prefix holder is
    skipped IMMEDIATELY — via lifecycle or its body's draining flag —
    and must NEVER be dead-marked, because a dead-mark expires after
    DEAD_TTL_S and expiry must not resurrect a deliberate drain."""
    from ray_tpu.serve.fleet.router import OccupancyRouter
    stats = [
        {"max_slots": 8, "active_slots": 0, "waiting_requests": 0,
         "stopped": False, "models": []},               # the holder
        {"max_slots": 8, "active_slots": 4, "waiting_requests": 1,
         "stopped": False, "models": []},
    ]
    st = _fake_state(stats)
    # controller-visible drain: lifecycle flips, holder leaves live set
    st.replicas[0].lifecycle = "draining"
    r = OccupancyRouter(st, seed=1)
    assert r.assign(prefer="fake#0").tag == "fake#1"
    with r._mlock:
        assert "fake#0" not in r._dead
    # body-first drain: lifecycle still active but the engine already
    # reports draining (the membership move is racing) — same outcome
    st.replicas[0].lifecycle = "active"
    stats[0]["draining"] = True
    r2 = OccupancyRouter(st, seed=1)
    assert r2.assign(prefer="fake#0").tag == "fake#1"
    with r2._mlock:
        assert "fake#0" not in r2._dead


# --------------------------------------------------------------- multiplex


def test_multiplexer_lru_eviction_and_reload():
    loads, unloads = [], []
    mux = ModelMultiplexer(
        {"a": 1, "b": 2, "c": 3},
        loader=lambda mid, spec: loads.append(mid) or f"body-{mid}",
        unloader=lambda body: unloads.append(body),
        capacity=2)
    assert mux.get("a") == "body-a"
    assert mux.get("b") == "body-b"
    assert mux.get("a") == "body-a"          # hit refreshes recency
    assert mux.get("c") == "body-c"          # evicts b (LRU), not a
    assert unloads == ["body-b"]
    assert sorted(mux.loaded_models()) == ["a", "c"]
    assert mux.get("b") == "body-b"          # reload after eviction
    assert loads == ["a", "b", "c", "b"]
    with pytest.raises(ValueError, match="unknown model"):
        mux.get("nope")


def test_multiplexed_replica_serves_variants_and_advertises():
    handle, f = _run_fleet(
        num_replicas=1,
        engine_cfg=EngineConfig(max_slots=2),
        variants={"base": 0, "alt": 1}, multiplex_capacity=2)
    out_base = handle.remote({"prompt": [3, 1, 4], "max_tokens": 4,
                              "model": "base"}).result(timeout=120)
    out_alt = handle.remote({"prompt": [3, 1, 4], "max_tokens": 4,
                             "model": "alt"}).result(timeout=120)
    # different seeds -> independently initialized params; "base" is
    # seed 0, the same params the reference oracle uses
    assert out_base["tokens"] == _ref_tokens([3, 1, 4], 4)
    st = serve.get_handle("v1")._state
    user = st.replicas[0].impl._user
    assert sorted(user.loaded_variants()) == ["alt", "base"]
    assert user.multiplex_stats()["loads"] == 2
    with pytest.raises(Exception, match="unknown model"):
        handle.remote({"prompt": [1], "max_tokens": 2,
                       "model": "ghost"}).result(timeout=60)


# ------------------------------------------------------- engine priority


def test_engine_priority_preempts_at_prefill_boundary():
    """With one slot busy, a later interactive submit is admitted ahead
    of an earlier batch submit when the slot frees."""
    params = gpt.init_params(CFG, jax.random.PRNGKey(SEED))
    eng = InferenceEngine(params, CFG, EngineConfig(max_slots=1,
                                                    max_seq=CFG.max_seq))
    try:
        blocker = eng.submit([1, 2], max_new=24)
        # wait until the blocker actually holds the slot
        deadline = time.monotonic() + 60
        while eng.stats()["active_slots"] == 0:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        batch = eng.submit([3, 4], max_new=2, priority=PRIORITY_BATCH)
        inter = eng.submit([5, 6], max_new=2,
                           priority=PRIORITY_INTERACTIVE)
        blocker.result(timeout=120)
        inter.result(timeout=120)
        batch.result(timeout=120)
        assert inter.first_token_s < batch.first_token_s
    finally:
        eng.shutdown()


# ----------------------------------------------------------- fleet e2e


def test_fleet_http_shed_returns_429_with_retry_after():
    # a token every 100 s: at 0.5 a second the bucket refilled whenever
    # the two POSTs below took 2 s together (the first one compiles), and
    # the third was then admitted, not shed (took turns failing under
    # six workers)
    _run_fleet(num_replicas=1,
               fleet_cfg=FleetConfig(rate=0.01, burst=2,
                                     max_queue_depth=0),
               http=True)
    addr = serve.proxy_address()
    body = {"prompt": [1, 2], "max_tokens": 2}
    for _ in range(2):                        # drain the burst
        _post(addr, "/v1/generate", body)
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(addr, "/v1/generate", body)
    assert ei.value.code == 429
    assert int(ei.value.headers["Retry-After"]) >= 1
    payload = json.loads(ei.value.read())
    assert payload["retry_after_s"] >= 0.0
    f = fleet.get("v1")
    snap = f.fleet_snapshot()
    assert snap["admitted"] == 2 and snap["shed"] == 1
    # zero silently-dropped: every request is accounted exactly once
    assert snap["admitted"] == snap["completed"] + snap["errored"]
    kinds = [e["kind"] for e in f.events()]
    assert "shed" in kinds and "admit" in kinds and "route" in kinds


def test_fleet_routes_across_replicas_and_counts():
    handle, f = _run_fleet(num_replicas=2)
    outs = [handle.remote({"prompt": [2, 7], "max_tokens": 3})
            for _ in range(8)]
    ref = _ref_tokens([2, 7], 3)
    for o in outs:
        assert o.result(timeout=120)["tokens"] == ref
    snap = f.fleet_snapshot()
    assert snap["admitted"] == 8 and snap["completed"] == 8
    routed = {e["replica"] for e in f.events() if e["kind"] == "route"}
    assert len(routed) == 2          # both replicas actually served


def test_fleet_occupancy_autoscale_up_and_down():
    """The autoscaler scales on the fleet's engine-load signal: load
    above target grows the replica set, idleness shrinks it.  Ticks
    are driven explicitly (autoscale_tick is what the controller
    thread calls every 250 ms) so the test can't race wall-clock tick
    timing under a loaded box."""
    from ray_tpu.serve.deployment import AutoscalingConfig
    handle, f = _run_fleet(
        num_replicas=1,
        engine_cfg=EngineConfig(max_slots=2),
        autoscaling=AutoscalingConfig(min_replicas=1, max_replicas=3,
                                      target_ongoing_requests=2.0))
    st = serve.get_handle("v1")._state
    # saturate: 8 concurrent long generations >> target 2/replica
    outs = [handle.remote({"prompt": [1, 2], "max_tokens": 48})
            for _ in range(8)]
    deadline = time.monotonic() + 60
    grew_to = 1
    while time.monotonic() < deadline:
        st.autoscale_tick()
        grew_to = max(grew_to, len(st.replicas))
        if grew_to >= 2:
            break
        time.sleep(0.05)
    for o in outs:
        o.result(timeout=120)
    assert grew_to >= 2, "autoscaler never grew on engine load"
    scale_events = [e for e in f.events() if e["kind"] == "scale"]
    assert scale_events and scale_events[0]["replicas_to"] > \
        scale_events[0]["replicas_from"]
    # drain -> shrink back toward min
    deadline = time.monotonic() + 60
    while len(st.replicas) > 1 and time.monotonic() < deadline:
        st.autoscale_tick()
        time.sleep(0.05)
    assert len(st.replicas) == 1, "autoscaler never shrank when idle"


# ------------------------------------------------------- metrics labels


_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})? '
    r'[-+]?((\d+(\.\d+)?([eE][-+]?\d+)?)|Inf|NaN)$')


def test_per_replica_engine_gauge_labels():
    """Two replicas must export two distinguishable engine series —
    deployment+replica labels, not one collapsed/ambiguous line — and
    the exposition must stay well-formed."""
    from ray_tpu import inference
    from ray_tpu.metrics import render_prometheus
    handle, f = _run_fleet(num_replicas=2)
    for _ in range(2):
        handle.remote({"prompt": [1, 2], "max_tokens": 2}).result(
            timeout=120)
    text = render_prometheus(serve.metrics_snapshot())
    active_lines = [ln for ln in text.splitlines()
                    if ln.startswith("ray_tpu_inference_active_slots{")]
    replicas = {m.group(1) for ln in active_lines
                for m in [re.search(r'replica="([^"]*)"', ln)] if m}
    assert len(replicas) >= 2, f"collapsed series: {active_lines}"
    assert all('deployment="v1"' in ln for ln in active_lines)
    # fleet ingress series ride the same endpoint
    assert "serve_fleet_admitted_total" in text
    for line in text.strip().splitlines():
        if not line.startswith("#"):
            assert _SAMPLE_RE.match(line), f"malformed: {line!r}"


# ------------------------------------------------- disconnect / timeline


def test_client_disconnect_mid_stream_cancels_engine_request():
    """A consumer that abandons a chunked /v1/generate stream must have
    its engine request cancelled and the slot freed — extends PR 5's
    cancellation coverage to the HTTP path."""
    handle, f = _run_fleet(num_replicas=1,
                           engine_cfg=EngineConfig(max_slots=2),
                           http=True)
    addr = serve.proxy_address()
    host, port = addr[len("http://"):].split(":")
    max_tokens = 56                  # prompt 3 + 56 < cache width 64
    body = json.dumps({"prompt": [9, 2, 6], "max_tokens": max_tokens,
                       "stream": True}).encode()
    st = serve.get_handle("v1")._state
    user = st.replicas[0].impl._user
    with socket.create_connection((host, int(port)), timeout=60) as s:
        s.sendall(b"POST /v1/generate HTTP/1.1\r\n"
                  b"Host: x\r\nContent-Type: application/json\r\n"
                  + f"Content-Length: {len(body)}\r\n\r\n".encode()
                  + body)
        s.settimeout(60)
        buf = b""
        while not parse_stream_chunks(buf.split(b"\r\n\r\n", 1)[-1]):
            data = s.recv(4096)
            assert data, "stream closed before first token"
            buf += data
        # abandon mid-generation (~55 tokens still to come)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        stats = user.fleet_stats()
        if stats["active_slots"] == 0 and stats["waiting_requests"] == 0:
            break
        time.sleep(0.05)
    assert stats["active_slots"] == 0, \
        f"abandoned stream still holds a slot: {stats}"
    # the slot was freed by CANCELLATION, not by decoding to the end:
    # the engine stopped well short of the requested budget
    assert user.engine.stats()["generated_tokens"] < max_tokens, \
        "engine decoded the full request for a disconnected client"
    # a hung-up client is accounted as cancelled, NOT as a server
    # error (error-rate metrics must not rise on disconnects)
    snap = f.fleet_snapshot()
    assert snap["cancelled"] >= 1 and snap["errored"] == 0
    assert snap["admitted"] == snap["completed"] + snap["errored"] \
        + snap["cancelled"]


def test_replica_death_classification():
    """Actor replicas die with the core runtime's errors, not the
    typed EngineStoppedError — the retry classifier must catch both."""
    from ray_tpu.core.client import ActorDiedError
    from ray_tpu.inference.engine import EngineStoppedError
    from ray_tpu.serve.controller import ReplicaHandle
    from ray_tpu.serve.fleet.ingress import _is_replica_death
    inproc = ReplicaHandle(object(), False, "d#0")
    actor = ReplicaHandle(object(), True, "d#1")
    assert _is_replica_death(EngineStoppedError("x"), inproc)
    assert _is_replica_death(EngineStoppedError("x"), actor)
    assert _is_replica_death(ActorDiedError("gone"), actor)
    assert _is_replica_death(
        RuntimeError("Actor died while executing method"), actor)
    # ...but only for actor replicas, and never for ordinary errors
    assert not _is_replica_death(RuntimeError("Actor died: x"), inproc)
    assert not _is_replica_death(ValueError("bad prompt"), actor)


def test_unstarted_stream_close_releases_replica():
    """Closing a streamed response WITHOUT ever iterating it (client
    disconnect during response-start) must still release the replica's
    ongoing count and cancel the engine request — a closed unstarted
    generator never runs its body, so the cleanup can't live only in
    the generator's finally."""
    handle, f = _run_fleet(num_replicas=1,
                           engine_cfg=EngineConfig(max_slots=2))
    st = serve.get_handle("v1")._state
    gen = handle.remote({"prompt": [1, 2], "max_tokens": 40,
                         "stream": True}).result(timeout=60)
    gen.close()                      # dropped before the first next()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        stats = st.replicas[0].impl._user.fleet_stats()
        if st.replicas[0].ongoing == 0 and stats["active_slots"] == 0 \
                and stats["waiting_requests"] == 0:
            break
        time.sleep(0.05)
    assert st.replicas[0].ongoing == 0, "router-side count leaked"
    assert stats["active_slots"] == 0, f"slot leaked: {stats}"
    assert f.fleet_snapshot()["cancelled"] >= 1


def test_timeline_merges_ingress_events():
    """Ingress admission/shed/route events land in the merged Perfetto
    trace (util/timeline.py), incl. queue-wait slices."""
    from ray_tpu.util.timeline import build_trace
    events = [
        {"t": 10.0, "kind": "admit", "deployment": "v1", "queued_s": 0.2,
         "priority": 0, "model": None},
        {"t": 10.1, "kind": "route", "deployment": "v1",
         "replica": "v1#0", "attempt": 0},
        {"t": 10.2, "kind": "shed", "deployment": "v1",
         "reason": "queue full", "retry_after_s": 1.5},
        {"t": 10.3, "kind": "scale", "deployment": "v1",
         "replicas_from": 1, "replicas_to": 2},
        # drain lifecycle: begin+complete pair into ONE slice, a
        # timeout pair likewise, an unpaired begin stays an instant
        {"t": 10.4, "kind": "drain_begin", "deployment": "v1",
         "replica": "v1#1", "reason": "scale_down", "deadline_s": 5.0},
        {"t": 10.5, "kind": "resume", "deployment": "v1",
         "from_replica": "v1#1", "resume_kind": "resumed_scale_down"},
        {"t": 10.9, "kind": "drain_complete", "deployment": "v1",
         "replica": "v1#1"},
        {"t": 11.0, "kind": "drain_begin", "deployment": "v1",
         "replica": "v1#2", "reason": "scale_down", "deadline_s": 0.1},
        {"t": 11.2, "kind": "drain_timeout", "deployment": "v1",
         "replica": "v1#2", "in_flight": 1},
        {"t": 11.5, "kind": "drain_begin", "deployment": "v1",
         "replica": "v1#3", "reason": "scale_down", "deadline_s": 5.0},
    ]
    trace = build_trace(ingress=events,
                        faults=[{"t": 10.05, "point": "serve_route",
                                 "action": "script", "detail": "x"}])
    evs = trace["traceEvents"]
    ing = [e for e in evs if e.get("cat") == "ingress"]
    queued = [e for e in ing if e["name"] == "ingress:queued"]
    assert queued and queued[0]["ph"] == "X" \
        and queued[0]["dur"] == pytest.approx(0.2e6)
    names = {e["name"] for e in ing}
    assert {"ingress:route", "ingress:shed", "ingress:scale",
            "ingress:resume"} <= names
    drains = [e for e in ing if e["tid"] == "drain"]
    slices = {e["name"]: e for e in drains if e["ph"] == "X"}
    assert slices["ingress:drain:v1#1"]["dur"] == pytest.approx(0.5e6)
    assert slices["ingress:drain:v1#1"]["args"]["outcome"] \
        == "drain_complete"
    assert slices["ingress:drain:v1#2"]["args"]["outcome"] \
        == "drain_timeout"
    # the in-progress drain stays visible as an instant
    assert any(e["name"] == "ingress:drain_begin" and e["ph"] == "i"
               for e in drains)
    # chaos instants share the view
    assert any(e.get("cat") == "chaos" for e in evs)


# ------------------------------------------------------ drain protocol


def test_drain_scale_down_accounting_identity():
    """Planned scale-down with streams in flight: every removal is
    accounted as drained / drain_timeout / resumed_scale_down — the
    request identity stays total, resumed_failure stays 0, and the
    counter SPLIT is structural (no aggregate field to hide behind, so
    the r13 masking bug cannot come back silently)."""
    from ray_tpu.serve.fleet.ingress import FleetCounters
    # the masking guard: reintroducing a catch-all `resumed` counter
    # fails here before any behavior test would notice
    assert not hasattr(FleetCounters(), "resumed")
    handle, f = _run_fleet(num_replicas=2)
    st = serve.get_handle("v1")._state
    gens = [handle.remote({"prompt": [2, 7], "max_tokens": 24,
                           "stream": True}).result(timeout=120)
            for _ in range(4)]
    first = [next(g) for g in gens]
    assert all("token" in c for c in first)
    # graceful shrink of ONE replica while all 4 streams are live
    st.drain_replicas(1, 30.0)
    ref = _ref_tokens([2, 7], 24)
    for head, g in zip(first, gens):
        toks = [head["token"]] + [c["token"] for c in g if "token" in c]
        assert toks == ref
    deadline = time.monotonic() + 30
    while st.draining and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not st.draining, "drain never settled"
    snap = f.fleet_snapshot()
    # identity: nothing lost, nothing double-counted
    assert snap["admitted"] == snap["completed"] + snap["errored"] \
        + snap["cancelled"]
    assert snap["resumed"] == snap["resumed_failure"] \
        + snap["resumed_scale_down"]
    # every scale-down accounted in a drain bucket; failures zero
    assert snap["drained"] + snap["drain_timeout"] \
        + snap["resumed_scale_down"] >= 1
    assert snap["resumed_failure"] == 0
    kinds = [e["kind"] for e in f.events()]
    assert "drain_begin" in kinds


def test_draining_replica_neither_routed_nor_restarted():
    """Lifecycle, not probe health, is what routing and self-heal
    consult: a replica stuck in the transitional DRAINING window (still
    listed, engines winding down) is skipped by the router and NEVER
    replaced by restart_dead — the self-heal/drain race regression."""
    handle, f = _run_fleet(num_replicas=2)
    st = serve.get_handle("v1")._state
    victim = st.replicas[0]
    # simulate the transitional window: lifecycle flipped while the
    # handle is still in the routable list
    victim.lifecycle = "draining"
    victim.impl._user.drain()
    for _ in range(6):
        out = handle.remote({"prompt": [4, 2],
                             "max_tokens": 3}).result(timeout=120)
        assert out["tokens"] == _ref_tokens([4, 2], 3)
    routed = {e["replica"] for e in f.events()
              if e["kind"] == "route"}
    assert victim.tag not in routed
    # engines wound down -> probe health reads idle/unhealthy-ish,
    # but restart_dead must not touch a non-active replica
    tags_before = [r.tag for r in st.replicas]
    assert st.restart_dead() == 0
    assert [r.tag for r in st.replicas] == tags_before


def test_engine_draining_error_reroutes_never_500_both_proxies():
    """The route/drain race: an engine that began draining AFTER the
    router picked its replica raises the typed EngineDrainingError —
    both HTTP proxies see a re-routed SUCCESS (200), never a 500, and
    the re-route is accounted as resumed_scale_down."""
    from ray_tpu.serve.http_proxy import HttpProxy
    _handle, f = _run_fleet(num_replicas=2, http=True)
    st = serve.get_handle("v1")._state
    addr_async = serve.proxy_address()
    threaded = HttpProxy(serve._get_controller())
    threaded.start()
    try:
        addr_threaded = f"http://{threaded.host}:{threaded.port}"
        body = {"prompt": [3, 1, 4], "max_tokens": 4}
        ref = _ref_tokens([3, 1, 4], 4)
        # drain the ENGINE only: the replica stays routable (its probe
        # still reads active) — exactly the race window — and submit()
        # on it raises the typed EngineDrainingError
        victim = st.replicas[0]
        for eng in victim.impl._user._engines():
            eng.drain()
        for addr in (addr_async, addr_threaded):
            out = [_post(addr, "/v1/generate", body) for _ in range(4)]
            assert all(o["result"]["tokens"] == ref for o in out)
        snap = f.fleet_snapshot()
        # the race fired at least once (the idle drained engine scores
        # best, so the router walks into it) and was re-routed — and
        # NOTHING surfaced as a failure or a 500
        assert snap["resumed_scale_down"] >= 1
        assert snap["resumed_failure"] == 0 and snap["errored"] == 0
        assert snap["admitted"] == snap["completed"]
    finally:
        threaded.stop()


def test_fleet_events_reach_armed_flight_recorder():
    from ray_tpu.core import flight_recorder as fr_mod
    rec = fr_mod.FlightRecorder()
    fr_mod._active = rec
    try:
        handle, f = _run_fleet(num_replicas=1)
        handle.remote({"prompt": [1], "max_tokens": 2}).result(timeout=120)
        kinds = {e["kind"] for e in rec.export_ingress()}
        assert {"admit", "route"} <= kinds
    finally:
        fr_mod._active = None
