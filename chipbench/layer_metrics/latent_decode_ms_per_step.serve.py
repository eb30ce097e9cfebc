"""Device time of the one-token latent attention kernel in one decode
program of the ``deepseek_v2`` layout (ms): self time of the custom calls
``deepseek_v2_trace`` labels ``latent_decode_attention`` (one a layer)
inside ``jit_step`` runs, over their count."""

from chipbench import deepseek_v2_trace as t


def read(obs):
    return t.ms_per_run(obs, "jit_step", t.DECODE_KERNEL)
