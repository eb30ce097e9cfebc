"""chip_smoke.py's exit contract, as far as a machine without a chip can
show it: no accelerator -> non-zero exit and nothing on stdout; a phase
made to fail -> non-zero exit and ``"ok": false`` as the last line."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the rehearsal with its first phase's native build replaced by a raise
# (a monkeypatched check; the real build would rewrite the library other
# test processes have loaded)
_FAILING = """
import os, sys
import chip_smoke

def broken():
    raise RuntimeError("made to fail")

chip_smoke.build_native = broken
sys.argv = ["chip_smoke.py", "--rehearse"]
code = chip_smoke.main()
sys.stderr.flush()
os._exit(code)
"""


@pytest.mark.parametrize("argv, rc, last", [
    (["chip_smoke.py"], 2, None),
    (["-c", _FAILING], 1, False),
], ids=["off_chip_refuses", "failed_phase_fails_run"])
def test_chip_smoke_exit_contract(argv, rc, last):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    run = subprocess.run([sys.executable, *argv], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert run.returncode == rc, run.stderr[-2000:]
    lines = run.stdout.splitlines()
    if last is None:
        assert lines == []
        return
    result = json.loads(lines[-1])
    assert result["ok"] is last and set(result) == {"ok", "device"}
    assert set(result["device"]) == {"platform", "kind", "count"}
    assert any(json.loads(x).get("error", "").endswith("made to fail")
               for x in lines[:-1])
