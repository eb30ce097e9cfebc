"""Device time of the short-convolution mixers' window form in one
program that holds a prefill chunk of the ``lfm2_moe`` layout (ms): self
time of the ops ``lfm2_trace`` labels ``short_conv_proj`` and
``short_conv_taps``, plus the mixers' share by layer count of
``square_proj``, inside ``jit_chunk_fn`` AND ``jit_step_chunk`` runs (a
chunk that rides a decode step: one product over the rows' tokens and
the chunk's), over their count."""

from chipbench import lfm2_trace as t


def read(obs):
    return t.short_conv_ms(obs, t.CHUNK_PROGRAMS)[0]
