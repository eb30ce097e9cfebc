"""Device time of the window form of the gated delta rule in one program
that holds a chunk of the ``olmo_hybrid`` layout (ms): self time of the
ops ``olmo_hybrid_trace`` labels ``mixer_linear_attention`` inside
``jit_chunk_fn`` AND ``jit_step_chunk`` runs, over their count
(``chipbench/olmo_hybrid_chunks.py``): the convolution, the blockwise
inverse and products, the scan over the carried state, the gated output
norm."""

from chipbench import olmo_hybrid_chunks as c
from chipbench import olmo_hybrid_trace as t


def read(obs):
    return c.ms_per_chunk(obs, t.DELTA_WINDOW)
