"""Device time of the grouped Mamba-2 convolution and state update in one
decode program of the ``nemotron_h`` layout (ms): self time of the ops
``nemotron_trace`` labels ``grouped_ssm`` (causal conv, the ``ssd_step``
kernel on the ``[4096, 128]`` state with its 8 B/C groups, the
group-wise gated norm and the small ops that feed the kernel; NOT the
in/out projections) inside ``jit_step`` runs, over their count."""

from chipbench import nemotron_trace


def read(obs):
    return nemotron_trace.ms_per_run(obs, "jit_step",
                                     nemotron_trace.GROUPED_SSM)
