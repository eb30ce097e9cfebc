"""Device time of the one-token form of the gated delta rule in one
decode program of the ``olmo_hybrid`` layout (ms): self time of the ops
``olmo_hybrid_trace`` labels ``delta_step`` (the kernel, one a linear
layer) and ``mixer_linear_attention`` (the convolution, the q / k
scaling, the gates, the gated output norm) inside ``jit_step`` runs,
over their count."""

from chipbench import olmo_hybrid_trace as t


def read(obs):
    return t.ms_per_run(obs, "jit_step", t.DELTA_STEP)
