"""Device time of the window layers' one-token attention in one decode
program of the ``afmoe`` layout (ms): self time of the ops
``afmoe_trace`` labels ``swa_decode_attention`` (the table-walking
kernel on the window layers' pools, which starts its walk at the block
that holds the first key of a row's window) and ``swa_pool_ops`` (the
commit of the pass's K/V into those pools) inside ``jit_step`` runs,
over their count."""

from chipbench import afmoe_trace as t


def read(obs):
    return t.ms_per_run(obs, "jit_step", t.SWA_DECODE)
