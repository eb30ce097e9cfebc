"""Share of its roofline the window form reaches in a chunk pass (%): the
least time the chip could take to decompress the keys in reach of the
window ONCE and attend the causal (query, key) pairs of its real queries
(``deepseek_v2_bytes.window_work`` from the engine's ``chunk_keys`` and
``chunk_query_keys`` over the window's chunk passes; compute bound) over
the traced time of ``latent_prefill_ms_per_chunk.serve``."""

from chipbench import deepseek_v2_bytes as b
from chipbench import deepseek_v2_trace as t


def read(obs):
    ms = t.ms_per_run(obs, "jit_chunk_fn", t.WINDOW)
    keys = b.per_chunk(obs, "chunk_keys")
    pairs = b.per_chunk(obs, "chunk_query_keys")
    if ms is None or keys is None or pairs is None or not obs.get("peaks"):
        return None
    least = b.least_seconds(b.window_work(
        obs["published"], obs["layers"], keys, pairs), obs["peaks"])
    return 100.0 * least / (ms / 1e3)
