"""Device time of the latent attention's window form in one chunk program
of the ``deepseek_v2`` layout (ms): self time of the ops
``deepseek_v2_trace`` labels ``latent_window`` (the walk over key blocks:
gather, scores, running softmax, values) and ``latent_kvb`` (the
decompression of each block) inside ``jit_chunk_fn`` runs, over their
count."""

from chipbench import deepseek_v2_trace as t


def read(obs):
    return t.ms_per_run(obs, "jit_chunk_fn", t.WINDOW)
