"""Device time of the expert ops in one decode program of the
``deepseek_v2`` layout (ms): self time of the ops ``deepseek_v2_trace``
labels ``routed_experts`` and ``shared_expert`` (the softmax router and
its group limit, the sort, the two grouped matmuls over the 20 held
experts, the shared gated MLP) inside ``jit_step`` runs, over their
count."""

from chipbench import deepseek_v2_trace as t


def read(obs):
    return t.ms_per_run(obs, "jit_step", t.EXPERTS)
