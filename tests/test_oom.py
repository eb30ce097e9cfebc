"""Memory monitor + OOM worker-killing policy.

Reference: src/ray/common/memory_monitor.h:52 (threshold watcher),
src/ray/raylet/worker_killing_policy_group_by_owner.h:85 (victim
selection), ray.exceptions.OutOfMemoryError (user-facing error).
"""

from __future__ import annotations

import os
import time

import pytest

import ray_tpu


@pytest.fixture
def local_rt():
    rt = ray_tpu.init(num_cpus=1, num_tpus=0)
    yield rt
    ray_tpu.shutdown()


def _press(svc):
    svc.memory_monitor.get_usage = lambda: (99, 100)


def _relax(svc):
    svc.memory_monitor.get_usage = lambda: (10, 100)


def test_oom_kill_retries_without_losing_node(local_rt, tmp_path):
    """A memory-hog task's worker is killed and the task retried on a
    fresh worker; the node itself survives."""
    svc = local_rt.node_service
    assert svc.memory_monitor is not None, "monitor should be on by default"
    marker = tmp_path / "pids.txt"
    stop = tmp_path / "all_clear"

    @ray_tpu.remote(max_retries=2)
    def hog(path, stop_path):
        with open(path, "a") as f:
            f.write(f"{os.getpid()}\n")
            f.flush()
        # run until OOM-killed or the test says all-clear — a fixed sleep
        # raced the monitor tick under parallel suite load (the task
        # could finish before the kill landed, leaving nothing to kill).
        # The backstop deadline must exceed the test's kill-wait window
        # or the same race reappears at the boundary.
        deadline = time.time() + 600
        while not os.path.exists(stop_path) and time.time() < deadline:
            time.sleep(0.05)
        return "done"

    ref = hog.remote(str(marker), str(stop))
    # wait for the FIRST execution's pid, THEN press, then wait for that
    # process to die.  Pressing before the submit let the monitor kill
    # the worker 0.3-0.55 s after it, before the task body had written
    # its pid: the pid read here was then the RETRY's, which nothing
    # would ever kill once the pressure was relaxed, and the test waited
    # out its whole deadline (300 s, every run).  Asserting on
    # oom_kill_count alone raced too: a kill could be counted while the
    # hog itself survived to finish without a retry.  Every wait below
    # is an event poll with a WIDE deadline (box-load dependent flake,
    # PR 9's tier-1 run): the deadlines only bound a genuinely hung
    # monitor, they are not the expected durations.
    deadline = time.time() + 120
    while time.time() < deadline and not (
            marker.exists() and marker.read_text().split()):
        time.sleep(0.05)
    assert marker.exists(), "hog never started"
    first_pid = int(marker.read_text().split()[0])
    _press(svc)                      # simulated pressure: no allocation
    # relax the INSTANT the kill is counted: pressure left on past this
    # point raced the retry — the monitor could kill the re-executed hog
    # too, burn the max_retries=2 budget, and the get() below surfaced
    # OutOfMemoryError under suite load.  The kill just counted still
    # has to land on first_pid, so relaxing here forfeits nothing the
    # later assertions need.
    deadline = time.time() + 120
    while time.time() < deadline and svc.oom_kill_count < 1:
        time.sleep(0.05)
    assert svc.oom_kill_count >= 1, "monitor never killed the hog"
    _relax(svc)
    deadline = time.time() + 120
    while time.time() < deadline:
        try:
            os.kill(first_pid, 0)
        except OSError:
            break                    # the hog's worker is gone
        time.sleep(0.05)
    else:
        raise AssertionError("killed worker process never exited")
    stop.write_text("go")            # let the retried execution finish

    assert ray_tpu.get(ref, timeout=120) == "done"
    pids = [int(x) for x in marker.read_text().split()]
    assert len(pids) >= 2, "task was not re-executed on a new worker"
    assert pids[0] != pids[-1]
    # the first worker is really gone; the node kept serving
    with pytest.raises(OSError):
        os.kill(pids[0], 0)


def test_oom_error_when_retry_budget_exhausted(local_rt):
    """With retries disabled the kill surfaces as OutOfMemoryError, not
    a generic worker-death error."""
    svc = local_rt.node_service

    @ray_tpu.remote(max_retries=0)
    def hog():
        time.sleep(120)   # must outlive the kill wait or the task
        #                   finishes clean and no OOMError surfaces

    _press(svc)
    ref = hog.remote()
    try:
        with pytest.raises(ray_tpu.OutOfMemoryError) as ei:
            ray_tpu.get(ref, timeout=90)
        assert "threshold" in str(ei.value)
    finally:
        _relax(svc)


def test_group_by_owner_policy_prefers_newest_retriable():
    from ray_tpu.core.memory_monitor import pick_victim

    class T:
        def __init__(self, owner, started_at, retries_left):
            self.spec = {"owner": owner}
            self.started_at = started_at
            self.retries_left = retries_left

    a1, a2, a3 = T("a", 1.0, 0), T("a", 2.0, 1), T("a", 3.0, 0)
    b1 = T("b", 9.0, 5)
    cands = [("ra1", a1), ("ra2", a2), ("ra3", a3), ("rb1", b1)]
    # largest group is owner "a"; newest retriable within it is a2
    assert pick_victim(cands)[1] is a2
    # no retriable in the largest group -> newest overall in that group
    a2.retries_left = 0
    assert pick_victim(cands)[1] is a3
    assert pick_victim([]) is None
