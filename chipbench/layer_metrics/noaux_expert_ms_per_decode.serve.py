"""Device time of the expert ops in one decode program of the
``xing4_0`` layout (ms): self time of the ops ``xing4_trace`` labels
``routed_experts`` and ``shared_expert`` (the sigmoid router with its
selection bias, the sort, the two grouped matmuls over 64 held gated
experts, the shared expert) inside ``jit_step`` runs, over their
count."""

from chipbench import xing4_trace as t


def read(obs):
    return t.ms_per_run(obs, "jit_step", t.EXPERTS)
