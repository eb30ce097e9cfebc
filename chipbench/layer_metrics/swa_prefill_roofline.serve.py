"""Share of its roofline the window layers' chunk attention reaches in a
chunk pass (%): the least time the chip could take to attend the (query,
key) pairs INSIDE the window of the chunk's real queries in every head
and read the keys in reach once (``afmoe_bytes.swa_prefill_work`` from
the engine's ``window_query_keys`` and ``window_chunk_keys`` over the
TRACED seconds' chunk passes; compute bound) over the traced time of
``swa_prefill_ms_per_chunk.serve``."""

from chipbench import afmoe_bytes as b
from chipbench import afmoe_trace as t


def read(obs):
    if not obs.get("published") or not obs.get("peaks"):
        return None
    swa = b.layers_of(obs["published"], "sliding_attention")
    ms = t.shared_out(obs, "jit_chunk_fn", t.SWA_PREFILL,
                      swa / obs["published"]["num_hidden_layers"])
    keys = b.per_chunk(b.traced(obs), "window_chunk_keys")
    pairs = b.per_chunk(b.traced(obs), "window_query_keys")
    if ms is None or keys is None or pairs is None:
        return None
    least = b.least_seconds(b.swa_prefill_work(obs["published"], keys, pairs),
                            obs["peaks"])
    return 100.0 * least / (ms / 1e3)
