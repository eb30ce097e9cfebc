"""Device time of the expert ops in one decode program of the
``lfm2_moe`` layout (ms): self time of the ops ``lfm2_trace`` labels
``routed_experts`` (the sigmoid router with its selection bias, the
sort, the two grouped matmuls over gated experts; there is no shared
expert) inside ``jit_step`` runs, over their count."""

from chipbench import lfm2_trace as t


def read(obs):
    return t.ms_per_run(obs, "jit_step", t.EXPERTS)
