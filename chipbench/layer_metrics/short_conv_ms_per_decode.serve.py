"""Device time of the short-convolution mixers in one decode program of
the ``lfm2_moe`` layout (ms): self time of the ops ``lfm2_trace`` labels
``short_conv_proj`` (the two products) and ``short_conv_taps`` (the
gates, the taps, the rows' state and the snapshots), plus the mixers'
share by layer count of ``square_proj`` (``[2048, 2048]`` products that
name neither ``out_proj`` nor the attention's ``wo``), inside
``jit_step`` runs, over their count."""

from chipbench import lfm2_trace as t


def read(obs):
    return t.short_conv_ms(obs, ("jit_step",))[0]
