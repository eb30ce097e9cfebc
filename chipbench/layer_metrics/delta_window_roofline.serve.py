"""Share of its roofline the window form of the delta rule reaches in a
program that holds a chunk (%): the least time it could take, the
larger of its products over the peak arithmetic and its bytes over the
peak bandwidth (``olmo_hybrid_bytes.window_work`` of the engine's
``linear_chunk_tokens`` over the window's chunk passes, which count a
riding chunk too) over the traced time of
``delta_window_ms_per_chunk.serve``, the chunk program's runs and the
fused step+chunk program's together."""

from chipbench import olmo_hybrid_bytes as b
from chipbench import olmo_hybrid_chunks as c
from chipbench import olmo_hybrid_trace as t


def read(obs):
    ms = c.ms_per_chunk(obs, t.DELTA_WINDOW)
    tokens = b.per_chunk(obs, "linear_chunk_tokens")
    if ms is None or not tokens or not obs.get("peaks"):
        return None
    least = b.least_seconds(b.window_work(obs["published"], tokens),
                            obs["peaks"])
    return 100.0 * least / (ms / 1e3)
