"""Share of the HBM roofline the stream mix reaches in a chunk pass (%):
the least time the chip could take for the bytes the mix needs — four
passes over the four streams a sublayer and REAL token of the chunk
(``prefill_tokens`` over ``chunk_passes`` of the traced seconds; padding
is not work), ``Phi`` once a sublayer (``xing4_bytes.mhc_work``, the
same bytes whatever implements the mix) — over the traced time of
``mhc_ms_per_chunk.serve``.  Memory bound."""

from chipbench import xing4_bytes as b
from chipbench import xing4_trace as t


def read(obs):
    ms = t.ms_per_run(obs, "jit_chunk_fn", t.MHC)
    tokens = b.per_chunk(b.traced(obs), "prefill_tokens")
    if ms is None or tokens is None or not obs.get("peaks"):
        return None
    least = b.least_seconds(b.mhc_work(obs["published"], tokens),
                            obs["peaks"])
    return 100.0 * least / (ms / 1e3)
