"""Device time of the stream mix in one chunk program of the ``xing4_0``
layout (ms): self time of the ops ``xing4_trace`` labels as the
manifold-constrained hyper-connections (the norm over 14,336 lanes and
the product with ``Phi``, the Sinkhorn-Knopp iterations, ``H_pre X``
with the sublayer's input norm the compiler fuses behind it, ``H_res X +
H_post^T F``) inside ``jit_chunk_fn`` runs, over their count: all
twelve sublayers of a pass."""

from chipbench import xing4_trace as t


def read(obs):
    return t.ms_per_run(obs, "jit_chunk_fn", t.MHC)
