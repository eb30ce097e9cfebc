"""Device time of the expert ops in one decode program (ms): self time of
the ops under the ``routed_experts`` and ``shared_expert`` scopes of
``models/hybrid.py`` (router, sort, the two grouped matmuls, the shared
gated MLP) inside ``jit_step`` runs, over their count
(``chipbench/scoped_trace.py``)."""

from chipbench import scoped_trace

PROGRAM = "jit_step"
LABELS = ("routed_experts", "shared_expert")


def read(obs):
    return scoped_trace.ms_per_run(obs, PROGRAM, LABELS)
