"""Operations the algorithm needs, from shapes (kept with the benchmark).

``train_flops_per_token`` is the usual model-FLOPs count (the repo's
first training bench had it; that file is gone since PR 47): 6 per
parameter per token for the matrix multiplications of forward and
backward, plus the attention score and value products, 12 * L * d * s.
Recomputed operations (remat) do not count.
"""

from __future__ import annotations


def gpt2_param_count(cfg: dict) -> int:
    d, L, f = cfg["n_embd"], cfg["n_layer"], cfg["n_inner"]
    v, s = cfg["vocab_size_padded"], cfg["n_positions"]
    per_layer = (2 * d) + (d * 3 * d) + (d * d + d) + (2 * d) \
        + (d * f + f) + (f * d + d)
    return v * d + s * d + 2 * d + L * per_layer


def train_flops_per_token(n_params: int, cfg: dict, seq: int) -> int:
    return 6 * n_params + 12 * cfg["n_layer"] * cfg["n_embd"] * seq
