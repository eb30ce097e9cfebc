"""Device time of the window form of the gated delta rule in one chunk
program of the ``olmo_hybrid`` layout (ms): self time of the ops
``olmo_hybrid_trace`` labels ``mixer_linear_attention`` (the
convolution, the blockwise inverse and products, the scan over the
carried state, the gated output norm) inside ``jit_chunk_fn`` runs, over
their count."""

from chipbench import olmo_hybrid_trace as t


def read(obs):
    return t.ms_per_run(obs, "jit_chunk_fn", t.DELTA_WINDOW)
