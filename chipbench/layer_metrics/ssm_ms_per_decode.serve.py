"""Device time of the Mamba-2 convolution and state update in one decode
program (ms): self time of the ops under the ``mixer_ssm`` scope of
``models/hybrid.py`` (causal conv, the one-token recurrence on every
row's state, the gated norm; NOT the in/out projections, which are
``mixer_ssm_proj``) inside ``jit_step`` runs, over their count."""

from chipbench import scoped_trace

PROGRAM = "jit_step"
LABELS = ("mixer_ssm",)


def read(obs):
    return scoped_trace.ms_per_run(obs, PROGRAM, LABELS)
