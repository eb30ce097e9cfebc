"""Device time of the stream mix in one decode program of the
``xing4_0`` layout (ms): self time of the ops ``xing4_trace`` labels as
the manifold-constrained hyper-connections inside ``jit_step`` runs,
over their count.  At 5-30 rows the bytes are nothing: what this reads
is the latency of twelve sublayers' 40 dependent normalisations."""

from chipbench import xing4_trace as t


def read(obs):
    return t.ms_per_run(obs, "jit_step", t.MHC)
