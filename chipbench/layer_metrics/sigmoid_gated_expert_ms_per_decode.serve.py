"""Device time of the expert ops in one decode program of the ``afmoe``
layout (ms): self time of the ops ``afmoe_trace`` labels
``routed_experts`` and ``shared_expert`` (the sigmoid router with its
selection bias, the sort, the two grouped matmuls over gated experts,
the shared gated MLP) inside ``jit_step`` runs, over their count."""

from chipbench import afmoe_trace as t


def read(obs):
    return t.ms_per_run(obs, "jit_step", t.EXPERTS)
