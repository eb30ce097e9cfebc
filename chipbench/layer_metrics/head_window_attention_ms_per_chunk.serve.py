"""Device time of the head-wise window attention in one program that
holds a chunk of the ``olmo_hybrid`` layout (ms): self time of the ops
``olmo_hybrid_trace`` labels ``window_attention`` inside ``jit_chunk_fn``
AND ``jit_step_chunk`` runs, over their count
(``chipbench/olmo_hybrid_chunks.py``): one Pallas kernel a key block of
1,024 keys, a grid step a head (``ops/attention.head_window_attention``)."""

from chipbench import olmo_hybrid_chunks as c
from chipbench import olmo_hybrid_trace as t


def read(obs):
    return c.ms_per_chunk(obs, t.WINDOW_ATTENTION)
