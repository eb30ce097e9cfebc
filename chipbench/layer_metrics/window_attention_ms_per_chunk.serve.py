"""Device time of the head-wise window attention in one chunk program of
the ``olmo_hybrid`` layout (ms): self time of the ops
``olmo_hybrid_trace`` labels ``window_attention`` (the walk over key
blocks: gather, the values' transposition, the kernel) inside
``jit_chunk_fn`` runs, over their count."""

from chipbench import olmo_hybrid_trace as t


def read(obs):
    return t.ms_per_run(obs, "jit_chunk_fn", t.WINDOW_ATTENTION)
