"""Share of its roofline the window layers' one-token attention reaches
in a decode pass (%): the least time the chip could take to read ONCE,
from both pools of every window layer, the blocks that hold a key
inside a live row's window (the engine's ``window_blocks_attended`` over
the TRACED seconds' decode passes, ``afmoe_bytes.traced``; ``afmoe_bytes.swa_decode_work``, bound by
``peaks.json``'s bandwidth) over the traced time of
``swa_decode_ms_per_step.serve``.  Blocks behind the window are not
credited, read or not."""

from chipbench import afmoe_bytes as b
from chipbench import afmoe_trace as t


def read(obs):
    ms = t.ms_per_run(obs, "jit_step", t.SWA_DECODE)
    blocks = b.per_decode(b.traced(obs), "window_blocks_attended")
    if ms is None or not blocks or not obs.get("peaks"):
        return None
    least = b.least_seconds(b.swa_decode_work(
        obs["published"], obs["block_size"], blocks), obs["peaks"])
    return 100.0 * least / (ms / 1e3)
