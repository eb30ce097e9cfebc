"""Share of its roofline the window form of the delta rule reaches in a
chunk pass (%): the least time the chip could take for the chunkwise
algorithm's products over the chunk's real tokens (the engine's
``linear_chunk_tokens`` over the window's chunk passes) — the larger of
their operations over ``peaks.json``'s bf16 rate and their bytes over
its bandwidth (``olmo_hybrid_bytes.window_work``) — over the traced time
of ``delta_prefill_ms_per_chunk.serve``."""

from chipbench import olmo_hybrid_bytes as b
from chipbench import olmo_hybrid_trace as t


def read(obs):
    ms = t.ms_per_run(obs, "jit_chunk_fn", t.DELTA_WINDOW)
    tokens = b.per_chunk(obs, "linear_chunk_tokens")
    if ms is None or not tokens or not obs.get("peaks"):
        return None
    least = b.least_seconds(b.window_work(obs["published"], tokens),
                            obs["peaks"])
    return 100.0 * least / (ms / 1e3)
