"""The suite's own time limit (`conftest._TimeLimit`), its trail and
what a run leaves behind.

Each run below is a subprocess `python -m pytest` on a small temp test
file under the repo's conftest (`-p tests.conftest`), with LIMIT / GRACE
cut to 2 s / 1 s by that temp directory's own conftest.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONFTEST = """
    import json, signal
    import tests.conftest as repo
    repo.LIMIT, repo.GRACE = 2.0, 1.0

    def theirs(signum, frame):
        pass

    def pytest_sessionstart(session):
        signal.signal(signal.SIGALRM, theirs)

    def pytest_sessionfinish(session):
        with open("after.json", "w") as f:
            json.dump({"timer": signal.getitimer(signal.ITIMER_REAL),
                       "handler_back":
                           signal.getsignal(signal.SIGALRM) is theirs}, f)
"""


def _pytest(tmp_path, test_file, *args):
    (tmp_path / "conftest.py").write_text(textwrap.dedent(CONFTEST))
    (tmp_path / "test_it.py").write_text(textwrap.dedent(test_file))
    # a file the same worker finishes first (the scheduler starts with
    # the file of most tests): finished, it must not go to a replacement
    (tmp_path / "test_earlier.py").write_text(
        "def test_a(): pass\ndef test_b(): pass\ndef test_c(): pass\n")
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-p", "tests.conftest",
         "test_earlier.py", "test_it.py", "-q", "-p", "no:cacheprovider",
         f"--basetemp={tmp_path / 'bt'}", *args],
        cwd=tmp_path, env=env, timeout=150,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    return proc.returncode, proc.stdout


@pytest.fixture(scope="module")
def soft(tmp_path_factory):
    """A test that sleeps past the limit, then one that passes."""
    tmp_path = tmp_path_factory.mktemp("soft")
    rc, out = _pytest(tmp_path, """
        import signal, time

        def test_sleeps_past_the_limit():
            time.sleep(20)

        def test_next_one_runs():
            left, _ = signal.getitimer(signal.ITIMER_REAL)
            open("during.json", "w").write(str(left))
    """)
    return tmp_path, rc, out


@pytest.fixture(scope="module")
def hard(tmp_path_factory):
    """A test blocked where no signal handler can raise, then one that
    passes, under the driver's scheduler."""
    tmp_path = tmp_path_factory.mktemp("hard")
    rc, out = _pytest(tmp_path, """
        import signal, time

        def test_blocked_where_no_handler_runs():
            signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})
            time.sleep(20)

        def test_next_one_runs():
            pass
    """, "-p", "xdist", "-n", "1", "--dist", "loadfile")
    return tmp_path, rc, out


def test_soft_stage_fails_the_test_under_its_name_and_the_run_goes_on(soft):
    _, rc, out = soft
    assert rc == 1, out
    assert ("FAILED test_it.py::test_sleeps_past_the_limit - Failed: "
            "timed out after 2 s") in out, out
    # every thread's stack, in the failure's captured stderr
    assert "Captured stderr call" in out, out
    assert "Current thread 0x" in out and "in test_sleeps_past_the_limit" \
        in out.split("Captured stderr call")[1], out
    assert "1 failed, 4 passed" in out, out


def test_hard_stage_ends_the_worker_and_the_run_goes_on(hard):
    _, rc, out = hard
    assert rc != 0, out
    # faulthandler's dump, on the run's real stderr
    assert "Timeout (0:00:03)!" in out, out
    assert "in test_blocked_where_no_handler_runs" in out, out
    assert ("worker 'gw0' crashed while running "
            "'test_it.py::test_blocked_where_no_handler_runs'") in out, out
    # xdist's loadfile scheduler hands it to the replacement: not run again
    assert out.count("Timeout (0:00:03)!") == 1, out
    assert "ended popen-gw0 at the hard stage (3 s)" in out, out
    assert "4 passed" in out, out


def test_trail_names_what_each_process_ran(soft, hard):
    lines = (soft[0] / "bt" / "trail.log").read_text().splitlines()[6:]
    assert [ln.rsplit(" ", 1)[0] if ln.startswith("END") else ln
            for ln in lines] == [
        "START test_it.py::test_sleeps_past_the_limit",
        "END test_it.py::test_sleeps_past_the_limit failed",
        "START test_it.py::test_next_one_runs",
        "END test_it.py::test_next_one_runs passed"], lines
    assert 2.0 <= float(lines[1].rsplit(" ", 1)[1]) < 10.0, lines
    # a worker that was ended leaves the test it was in as its last line
    bt = hard[0] / "bt"
    assert (bt / "popen-gw0" / "trail.log").read_text().splitlines()[6:] == [
        "START test_it.py::test_blocked_where_no_handler_runs"]
    lines = (bt / "popen-gw1" / "trail.log").read_text().splitlines()
    assert [ln.split(" ")[:3:2] for ln in lines] == [
        ["START"], ["END", "failed"], ["START"], ["END", "passed"]], lines


def test_timer_and_previous_handler_restored_after_a_test(soft):
    during = json.loads((soft[0] / "during.json").read_text())
    assert 0 < during <= 2.0             # armed anew for the second test
    after = json.loads((soft[0] / "after.json").read_text())
    assert after == {"timer": [0.0, 0.0], "handler_back": True}


def test_run_leaves_no_session_dir_and_no_process(tmp_path):
    """A runtime the tests never shut down, and a process a test
    orphaned: the session directory, the prefork template and the
    orphan are gone when the run ends."""
    rc, out = _pytest(tmp_path, """
        import ray_tpu

        def test_starts_a_runtime():
            rt = ray_tpu.init(num_cpus=1, num_tpus=0)
            f = ray_tpu.remote(lambda: 1)
            assert ray_tpu.get(f.remote(), timeout=60) == 1
            open("session_dir", "w").write(rt.session_dir)

        def test_runtime_still_up():
            assert ray_tpu.is_initialized()

        def test_orphans_a_grandchild():
            import subprocess, sys
            nap = "import time; time.sleep(600)"
            out = subprocess.run(
                [sys.executable, "-c",
                 "import subprocess as s, sys; print(s.Popen("
                 f"[sys.executable, '-c', {nap!r}], stdout=s.DEVNULL, "
                 "stderr=s.DEVNULL).pid)"],
                capture_output=True, text=True, timeout=60).stdout
            open("orphan_pid", "w").write(out)
    """)
    assert rc == 0, out
    # adopted by the run's process when its parent died, and ended with it
    orphan = int((tmp_path / "orphan_pid").read_text())
    try:       # a zombie is dead; burying it is its last parent's part
        with open(f"/proc/{orphan}/stat") as f:
            state = f.read().rsplit(")", 1)[1].split()[0]
    except OSError:
        state = "gone"
    assert state in ("Z", "gone")
    session_dir = (tmp_path / "session_dir").read_text()
    assert session_dir.startswith("/tmp/ray_tpu/session_")
    assert not os.path.exists(session_dir)
    alive = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/cmdline") as f:
                cmdline = f.read()
        except OSError:
            continue
        if session_dir in cmdline:
            alive.append((pid, cmdline))
    assert not alive
