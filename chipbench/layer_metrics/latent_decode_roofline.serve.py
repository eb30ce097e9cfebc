"""Share of its roofline the one-token latent kernel reaches in a decode
pass (%): the least time the chip could take for the blocks the pass's
live rows really hold (the engine's ``kv_blocks_attended`` over the
window's decode passes) — the larger of their bytes over ``peaks.json``'s
bandwidth and their products over its bf16 rate
(``deepseek_v2_bytes.decode_kernel_work``; 128 heads share one key, ~240
flop a byte, on the chip's ridge) — over the traced time of
``latent_decode_ms_per_step.serve``."""

from chipbench import deepseek_v2_bytes as b
from chipbench import deepseek_v2_trace as t


def read(obs):
    ms = t.ms_per_run(obs, "jit_step", t.DECODE_KERNEL)
    blocks = b.per_decode(obs, "kv_blocks_attended")
    if ms is None or blocks is None or not obs.get("peaks"):
        return None
    least = b.least_seconds(b.decode_kernel_work(
        obs["published"], obs["layers"], obs["block_size"], blocks),
        obs["peaks"])
    return 100.0 * least / (ms / 1e3)
