"""Share of its roofline the short-convolution mixers' window form
reaches in a chunk pass (%): the least time the chip could take to
multiply the chunk's REAL tokens by both projections of every such
layer and to read the matrices once (``lfm2_bytes.short_conv_prefill_
work`` from the engine's ``prefill_tokens`` over ``chunk_passes`` of the
TRACED seconds; COMPUTE bound at this cell's 500-1,024 real tokens a
chunk) over the traced time of ``short_conv_prefill_ms_per_chunk.
serve``.  The decode rows' tokens a riding chunk shares its products
with are not counted as work."""

from chipbench import lfm2_bytes as b
from chipbench import lfm2_trace as t


def read(obs):
    ms = t.short_conv_ms(obs, t.CHUNK_PROGRAMS)[0]
    tokens = b.per_chunk(b.traced(obs), "prefill_tokens")
    if ms is None or tokens is None or not obs.get("peaks"):
        return None
    least = b.least_seconds(
        b.short_conv_prefill_work(obs["published"], tokens), obs["peaks"])
    return 100.0 * least / (ms / 1e3)
