"""Device time of the window layers' chunk attention in one chunk
program of the ``afmoe`` layout (ms): self time of the ops
``afmoe_trace`` labels ``mixer_swa_attention`` (the head-wise window
kernel with a window's lower bound) and ``swa_pool_ops`` (the commit of
the chunk's K/V and the gathers of key blocks from the window layers'
pools), plus the window layers' share by layer count of
``attention_walk`` (the arrays of the walk that full and window layers
have alike: the running softmax's division, the transposes), inside
``jit_chunk_fn`` runs, over their count."""

from chipbench import afmoe_bytes as b
from chipbench import afmoe_trace as t


def read(obs):
    if not obs.get("published"):
        return None
    swa = b.layers_of(obs["published"], "sliding_attention")
    return t.shared_out(obs, "jit_chunk_fn", t.SWA_PREFILL,
                        swa / obs["published"]["num_hidden_layers"])
