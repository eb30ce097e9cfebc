"""Test fixtures.

Multi-device tests run on a virtual 8-device CPU mesh (the analogue of the
reference's multi-raylet-in-one-machine Cluster fixture,
python/ray/tests/conftest.py:375) — real TPU hardware is not required.
"""
import os

# Force the CPU platform and 8 virtual devices.  Both must be in the
# environment before the CPU backend initializes (first jax.devices()
# call), which this import-time hook is.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import ctypes
import faulthandler
import shutil
import signal
import time

import pytest

# Every tier-1 test runs under a time limit of its own.  One constant
# for the whole suite: a test that truly needs more is `slow`, there is
# no per-test override.  The longest honest tier-1 test is far below it
# (PERF.md section 7 has the table read from a whole run's trail).
LIMIT = 180.0     # s: the test FAILS under its own name, the run goes on
GRACE = 30.0      # s more: this worker process is ended, stacks dumped


class _TimeLimit:
    """The limit, round the whole of a test (set-up, call, tear-down),
    and the trail of what this process ran.

    Soft stage: SIGALRM at LIMIT dumps every thread's stack to fd 2
    (pytest's capture has it: the dump is in the failure's "Captured
    stderr") and fails the phase it interrupts, which ends any
    Python-level wait.  Hard stage: a test stuck in native code never
    returns to the interpreter to run that handler, so at LIMIT + GRACE
    faulthandler's own thread dumps every thread to the run's real
    stderr and ends this process; xdist reports the test as the one
    that crashed its worker and hands the rest to a replacement.

    The trail is one file a process under pytest's base temp directory
    (`/tmp/pytest-of-<user>/pytest-N/popen-gwK/trail.log` under xdist):
    `START <nodeid>` and `END <nodeid> <outcome> <seconds>`, flushed at
    once, so a run that is cut still names what each worker was in.

    Two defects of xdist 3.8's `loadfile` scheduler stand in the way of
    "hands the rest to a replacement", and both are met here.  It puts
    EVERY file the dead worker ever had back in the queue, the finished
    ones first, so the replacement is handed an empty list and the run
    never ends: `pytest_testnodedown` forgets the finished files before
    the scheduler looks.  And it hands the test that ended its worker
    to the next worker again (and again, up to `--max-worker-restart`):
    a test whose START is the last line of a sibling's trail fails at
    set-up instead, so the hard stage costs LIMIT + GRACE once.
    """

    def __init__(self, config):
        self.config = config
        # the run's real stderr: pytest's capture is suspended outside
        # collection and a test's phases, so fd 2 is the real one here;
        # at the hard stage it is a capture file that dies unread
        self.stderr_fd = os.dup(2)
        self.trail = None
        self.outcome = "passed"
        # session directories of the runtimes THIS process starts;
        # every one passes through NodeService
        self.session_dirs = []
        self.patch = pytest.MonkeyPatch()

    def first_test(self):
        """Not at configure: xdist's controller runs no test."""
        self.base = self.config._tmp_path_factory.getbasetemp()
        self.trail = open(self.base / "trail.log", "a", buffering=1)
        # what a killed child of a test orphans is adopted by this
        # process, not by init, and is found below it when the run ends
        ctypes.CDLL(None).prctl(36, 1)       # PR_SET_CHILD_SUBREAPER

        from ray_tpu.core.node import NodeService
        init, made = NodeService.__init__, self.session_dirs

        def noting(svc, config, session, session_dir, *args, **kwargs):
            made.append(session_dir)
            init(svc, config, session, session_dir, *args, **kwargs)

        self.patch.setattr(NodeService, "__init__", noting)

    @pytest.hookimpl(optionalhook=True)
    def pytest_testnodedown(self, node, error):
        sched = self.config.pluginmanager.getplugin("dsession").sched
        files = getattr(sched, "assigned_work", {}).get(node, {})
        for name in [f for f, tests in files.items() if all(tests.values())]:
            del files[name]

    @pytest.hookimpl(tryfirst=True)
    def pytest_runtest_setup(self, item):
        start = f"START {item.nodeid}".encode()
        for trail in self.base.parent.glob("popen-*/trail.log"):
            if trail.parent == self.base:
                continue
            with open(trail, "rb") as f:
                f.seek(max(0, trail.stat().st_size - len(start) - 1))
                if f.read().rstrip(b"\n") == start:
                    pytest.fail(
                        f"ended {trail.parent.name} at the hard stage "
                        f"({LIMIT + GRACE:g} s) and is not run again: "
                        f"the stacks are in the run's stderr")

    @pytest.hookimpl(wrapper=True, tryfirst=True)
    def pytest_runtest_protocol(self, item, nextitem):
        def on_alarm(signum, frame):
            faulthandler.dump_traceback(file=2, all_threads=True)
            pytest.fail(f"timed out after {LIMIT:g} s: {item.nodeid}")

        if self.trail is None:
            self.first_test()
        self.trail.write(f"START {item.nodeid}\n")
        self.outcome, t0 = "passed", time.monotonic()
        faulthandler.dump_traceback_later(LIMIT + GRACE, exit=True,
                                          file=self.stderr_fd)
        old_handler = signal.signal(signal.SIGALRM, on_alarm)
        old_timer = signal.setitimer(signal.ITIMER_REAL, LIMIT)
        try:
            return (yield)
        finally:
            signal.setitimer(signal.ITIMER_REAL, *old_timer)
            signal.signal(signal.SIGALRM, old_handler)
            faulthandler.cancel_dump_traceback_later()
            self.trail.write(f"END {item.nodeid} {self.outcome} "
                             f"{time.monotonic() - t0:.2f}\n")

    def pytest_runtest_logreport(self, report):
        if self.outcome == "passed":
            self.outcome = report.outcome

    @pytest.hookimpl(trylast=True)
    def pytest_sessionfinish(self, session):
        """Leave nothing behind: the processes below this one (what a
        dead parent orphaned was adopted, see `first_test`) and its own
        runtimes' session directories; never the whole of /tmp/ray_tpu,
        which the other workers and other runs share."""
        self.patch.undo()
        if self.trail is None:       # xdist's controller: its children
            return                   # are the workers
        for pid in _pids_below(os.getpid()):
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        for d in self.session_dirs:
            shutil.rmtree(d, ignore_errors=True)

    def pytest_unconfigure(self, config):
        if self.trail is not None:
            self.trail.close()
        os.close(self.stderr_fd)


def _pids_below(root):
    """Every live process below `root`, its adopted orphans included."""
    children = {}
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
        except OSError:              # gone since the listing
            continue
        children.setdefault(ppid, []).append(int(pid))
    below, todo = [], [root]
    while todo:
        kids = children.get(todo.pop(), [])
        below += kids
        todo += kids
    return below


@pytest.fixture
def rt_init():
    import ray_tpu
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    import jax
    return jax.devices("cpu")


def pytest_configure(config):
    config.pluginmanager.register(_TimeLimit(config), "time-limit")
    config.addinivalue_line(
        "markers", "slow: longer learning/convergence tests")
    config.addinivalue_line(
        "markers", "chaos: scripted fault-injection tests "
                   "(core/fault_injection.py); quick deterministic ones "
                   "run in tier-1, long kill-a-host flows are also "
                   "marked slow")
    config.addinivalue_line(
        "markers", "serve_fleet: fleet serving-layer tests "
                   "(serve/fleet/); quick deterministic ones run in "
                   "tier-1, trace-replay load runs are also marked "
                   "slow so tier-1 skips them")
