"""Share of its roofline the head-wise window attention reaches in a
program that holds a chunk (%): the least time it could take, the
larger of the causal pairs' products over the peak arithmetic and the
reached keys' bytes over the peak bandwidth
(``olmo_hybrid_bytes.attention_work`` from the engine's
``chunk_query_keys`` and ``chunk_keys`` over the window's chunk passes,
which count a riding chunk too) over the traced time of
``head_window_attention_ms_per_chunk.serve``."""

from chipbench import olmo_hybrid_bytes as b
from chipbench import olmo_hybrid_chunks as c
from chipbench import olmo_hybrid_trace as t


def read(obs):
    ms = c.ms_per_chunk(obs, t.WINDOW_ATTENTION)
    keys = b.per_chunk(obs, "chunk_keys")
    pairs = b.per_chunk(obs, "chunk_query_keys")
    if ms is None or keys is None or pairs is None or not obs.get("peaks"):
        return None
    least = b.least_seconds(b.attention_work(obs["published"], keys, pairs),
                            obs["peaks"])
    return 100.0 * least / (ms / 1e3)
