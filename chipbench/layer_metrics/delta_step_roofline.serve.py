"""Share of its roofline the one-token form of the delta rule reaches in
a decode pass (%): the least time the chip could take to read and write
ONCE the matrix and convolution state of the rows that advance (the
engine's ``linear_state_rows_advanced`` over the window's decode passes;
``olmo_hybrid_bytes.step_work``, bound by ``peaks.json``'s bandwidth)
over the traced time of ``delta_step_ms_per_decode.serve``."""

from chipbench import olmo_hybrid_bytes as b
from chipbench import olmo_hybrid_trace as t


def read(obs):
    ms = t.ms_per_run(obs, "jit_step", t.DELTA_STEP)
    rows = b.per_decode(obs, "linear_state_rows_advanced")
    if ms is None or not rows or not obs.get("peaks"):
        return None
    least = b.least_seconds(b.step_work(obs["published"], rows),
                            obs["peaks"])
    return 100.0 * least / (ms / 1e3)
