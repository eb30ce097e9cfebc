"""Device time of the expert ops in one decode program of the
``nemotron_h`` layout (ms): self time of the ops ``nemotron_trace``
labels ``routed_experts`` and ``shared_expert`` (router, sort, the two
grouped matmuls, the shared relu^2 MLP) inside ``jit_step`` runs, over
their count."""

from chipbench import nemotron_trace


def read(obs):
    return nemotron_trace.ms_per_run(obs, "jit_step", nemotron_trace.EXPERTS)
