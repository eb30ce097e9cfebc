"""Object plane tests: put/get, shm zero-copy, spill, free, placement groups
(reference analogue: python/ray/tests/test_object_spilling.py,
test_plasma_unlimited.py, test_placement_group.py)."""

import numpy as np
import pytest

import ray_tpu


@pytest.fixture(scope="module")
def rt():
    ray_tpu.init(num_cpus=2, num_tpus=0,
                 object_store_memory=50 * 1024 * 1024)
    yield ray_tpu
    ray_tpu.shutdown()


def test_put_get_small(rt):
    assert rt.get(rt.put({"a": 1, "b": [1, 2]}), timeout=60) == {"a": 1,
                                                                 "b": [1, 2]}


def test_put_get_large_numpy(rt):
    arr = np.random.rand(1 << 20).astype(np.float32)  # 4 MiB → shm
    out = rt.get(rt.put(arr), timeout=60)
    assert np.array_equal(out, arr)
    assert out.dtype == arr.dtype


def test_spill_and_restore(rt):
    # 9 x 10MiB > 50MiB budget forces spilling of early objects
    refs = [rt.put(np.full(10 * (1 << 20) // 8, i, dtype=np.float64))
            for i in range(9)]
    stats = rt.get_runtime().client.request(
        {"t": "object_stats"})["stats"]
    assert stats["num_spilled"] > 0
    # all objects still readable (restored transparently)
    for i, r in enumerate(refs):
        assert rt.get(r, timeout=60)[0] == i


def test_free(rt):
    ref = rt.put(np.zeros(1 << 20))
    rt.free([ref])
    with pytest.raises(Exception):
        rt.get(ref, timeout=1)


def test_placement_group_lifecycle(rt):
    pg = rt.placement_group([{"CPU": 1}, {"CPU": 1}], strategy="PACK")
    assert len(pg.bundle_specs) == 2

    @ray_tpu.remote
    def who():
        return "in-pg"

    strat = rt.PlacementGroupSchedulingStrategy(
        placement_group=pg, placement_group_bundle_index=1)
    assert rt.get(who.options(scheduling_strategy=strat).remote(),
                  timeout=60) == "in-pg"
    rt.remove_placement_group(pg)


def test_placement_group_infeasible_raises(rt):
    with pytest.raises(Exception, match="Infeasible"):
        rt.placement_group([{"CPU": 64}])


def test_placement_group_ready_blocks_until_capacity(rt):
    """ready() is truthful: a PG demanding busy resources stays pending
    until the holder releases them (reference:
    python/ray/util/placement_group.py ready() + the GCS pending queue)."""
    import time

    @ray_tpu.remote(num_cpus=2)
    class Hog:
        def ping(self):
            return "ok"

    hog = Hog.remote()
    assert rt.get(hog.ping.remote(), timeout=60) == "ok"

    pg = rt.placement_group([{"CPU": 2}])
    ref = pg.ready()
    # the hog holds both CPUs: the PG must NOT report ready
    with pytest.raises(Exception):
        rt.get(ref, timeout=1.5)
    state = rt.get_runtime().client.request(
        {"t": "pg_state", "pg_id": pg.id.binary()})["state"]
    assert state == "pending"

    ray_tpu.kill(hog)
    assert rt.get(pg.ready(), timeout=60) is True
    rt.remove_placement_group(pg)


def test_placement_group_ready_raises_after_remove(rt):
    @ray_tpu.remote(num_cpus=2)
    class Hog2:
        def ping(self):
            return "ok"

    hog = Hog2.remote()
    assert rt.get(hog.ping.remote(), timeout=60) == "ok"
    pg = rt.placement_group([{"CPU": 2}])   # stays pending behind the hog
    ref = pg.ready()
    rt.remove_placement_group(pg)
    with pytest.raises(Exception, match="removed"):
        rt.get(ref, timeout=60)
    ray_tpu.kill(hog)


def test_placement_group_bad_strategy(rt):
    with pytest.raises(ValueError):
        rt.placement_group([{"CPU": 1}], strategy="DIAGONAL")


def test_placement_group_wait_returns_bool(rt):
    """wait() is the retry-loop API: True when placed, False on timeout —
    it must not leak the poller's internal exceptions."""
    pg = rt.placement_group([{"CPU": 2}])
    assert pg.wait(timeout_seconds=60) is True

    pg2 = rt.placement_group([{"CPU": 2}])  # pends behind pg
    assert pg2.wait(timeout_seconds=1.5) is False
    rt.remove_placement_group(pg)
    assert pg2.wait(timeout_seconds=60) is True
    rt.remove_placement_group(pg2)


def test_zero_copy_read_is_view(rt):
    """Reads from shm come back without an extra copy of the buffer."""
    arr = np.arange(1 << 20, dtype=np.float32)
    out = rt.get(rt.put(arr), timeout=60)
    # the deserialized array's memory is backed by the shm mapping,
    # not a private heap copy
    assert not out.flags["OWNDATA"]


def test_automatic_release_holds_memory_flat(rt):
    """Dropping the last ObjectRef reclaims node storage without an
    explicit free() (reference: reference_count.h owner-count-zero).
    Churn many objects; the node table and shm usage must stay bounded."""
    import gc
    import time
    import numpy as np
    import ray_tpu
    from ray_tpu.core.runtime import get_runtime

    rt = get_runtime()
    svc = rt.node_service
    payload_mb = 1
    for i in range(30):
        ref = ray_tpu.put(np.zeros(payload_mb * 131072, dtype=np.float64))
        assert float(ray_tpu.get(ref, timeout=30)[0]) == 0.0
        del ref
    gc.collect()
    from ray_tpu.core.object_ref import get_tracker
    get_tracker().flush()
    deadline = time.time() + 10
    while time.time() < deadline:
        stats = ray_tpu.object_store_stats()
        if stats["num_objects"] <= 3 and \
                stats["used_bytes"] <= 4 * payload_mb * 1048576:
            break
        time.sleep(0.2)
    stats = ray_tpu.object_store_stats()
    assert stats["num_objects"] <= 3, stats
    # inline task returns are reclaimed too
    @ray_tpu.remote
    def one():
        return 1
    for _ in range(20):
        assert ray_tpu.get(one.remote(), timeout=60) == 1
    gc.collect()
    get_tracker().flush()
    deadline = time.time() + 10
    while time.time() < deadline:
        n = len(svc.objects) if svc else 0
        if n <= 6:
            break
        time.sleep(0.2)
    assert svc is None or len(svc.objects) <= 6, len(svc.objects)


def test_nested_ref_survives_inner_release(rt):
    """An object referenced only from inside a stored container must
    survive the release of the user's direct ref (reference:
    reference_count.h container-holds-ref)."""
    import gc
    import time
    import numpy as np
    import ray_tpu
    from ray_tpu.core.object_ref import get_tracker

    inner = ray_tpu.put(np.full(200_000, 3.0))   # shm-sized
    outer = ray_tpu.put({"payload": inner})
    del inner
    gc.collect()
    get_tracker().flush()
    time.sleep(1.0)   # give the release sweep every chance to misfire
    got_inner = ray_tpu.get(outer, timeout=30)["payload"]
    assert float(ray_tpu.get(got_inner, timeout=30)[0]) == 3.0
    # dropping the container finally releases both
    del outer, got_inner
    gc.collect()
    get_tracker().flush()
    deadline = time.time() + 10
    while time.time() < deadline:
        if ray_tpu.object_store_stats()["num_objects"] == 0:
            break
        time.sleep(0.2)
    assert ray_tpu.object_store_stats()["num_objects"] == 0


def test_reference_dying_under_the_trackers_lock_does_not_wait_for_it(
        monkeypatch):
    """The collector runs a dying ObjectRef's ``__del__`` on whatever
    thread crosses its threshold, after any call — also while that
    thread is inside the tracker, making the flush timer under its lock.
    The second death must not wait for the lock the first one holds
    (the tier-1 run that never reached its end, PR 43): both are
    counted, both are released, once."""
    import threading

    from ray_tpu.core import object_ref

    tracker = object_ref._RefTracker()
    released = []
    done = threading.Event()

    def sink(batch):
        released.extend(batch)
        done.set()

    tracker.set_sink(sink)
    tracker.incref(b"first")
    tracker.incref(b"second")

    class TimerThatCollects(threading.Timer):
        """What the collector did, made certain: a finalizer runs
        inside ``threading.Timer(...)``."""
        def __init__(self, *args, **kwargs):
            tracker.decref(b"second")
            super().__init__(*args, **kwargs)

    monkeypatch.setattr(object_ref.threading, "Timer", TimerThatCollects)
    dying = threading.Thread(target=tracker.decref, args=(b"first",),
                             daemon=True)
    dying.start()
    dying.join(timeout=10)
    assert not dying.is_alive(), "a dying reference waited for itself"
    assert done.wait(timeout=10)
    assert sorted(released) == [b"first", b"second"]
    assert tracker.held_count(b"first") == tracker.held_count(b"second") == 0
