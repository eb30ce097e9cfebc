"""Device time of the full-attention layer's chunk attention in one
chunk program of the ``afmoe`` layout (ms): self time of the ops
``afmoe_trace`` labels ``mixer_full_attention`` (the head-wise window
kernel over every key up to the query) and ``full_pool_ops`` (the commit
and the gathers on the full layers' pools), plus the full layers' share
by layer count of ``attention_walk``, inside ``jit_chunk_fn`` runs, over
their count.  It grows with the context where
``swa_prefill_ms_per_chunk.serve`` does not."""

from chipbench import afmoe_bytes as b
from chipbench import afmoe_trace as t


def read(obs):
    if not obs.get("published"):
        return None
    full = b.layers_of(obs["published"], "full_attention")
    return t.shared_out(obs, "jit_chunk_fn", t.FULL_PREFILL,
                        full / obs["published"]["num_hidden_layers"])
