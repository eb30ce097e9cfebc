"""Operations and bytes the ``olmo_hybrid`` layout's mechanisms must do,
from shapes and the window's counters (the roofline shares of
``layer_metrics/delta_step_roofline.serve.py``, ``delta_window_
roofline.serve.py`` and ``head_window_attention_roofline.serve.py`` divide
the least time they take at ``peaks.json``'s rates by the traced time).

Only what the ALGORITHM needs is counted, whatever implements it:

  * the one-token form, a decode pass: the matrix state (float32 ``[96,
    5760]``) and the convolution state (bfloat16 ``[3, 11520]``) of
    every row that ADVANCES (the engine's ``linear_state_rows_
    advanced``) read once and written once a linear layer; its
    arithmetic (a rank-one correction: ~6 flop a state element) is far
    under the chip's ridge and not counted;
  * the window form, a chunk: the chunkwise algorithm of arXiv:2412.06464
    in blocks of 64 tokens over the chunk's REAL tokens
    (``linear_chunk_tokens``), a head and block: ``K K^T`` and ``Q K^T``
    (2 x 2 x 64 x 64 x 96), the triangular solve applied to ``[K | V]``
    as forward substitution (64 x 64 x (96 + 192)), and four products
    against the carried state or the block's new values (``W S``, ``Q
    S``, ``K^T U``: 3 x 2 x 64 x 96 x 192; ``A U``: 2 x 64 x 64 x 192);
    counted as float32 products at ONE pass each (a form that spends
    three or six bfloat16 passes a float32 product, or inverts by
    doubling, does more and reads as a LOWER share); bytes: the block's
    q, k, v read and o written in bfloat16 and the row's state read and
    written once a chunk;
  * the head-wise window attention, a chunk: each causal (query, key)
    pair of the chunk's real queries (``chunk_query_keys``) attended in
    every head over 128 lanes twice (scores, values), and every key in
    reach (``chunk_keys``) read once from both pools at 3,840 lanes.
"""

from __future__ import annotations

# the roofline's rule and the window's counts a pass, as the latent
# cell's bytes file has them
from chipbench.deepseek_v2_bytes import (least_seconds,      # noqa: F401
                                         per_chunk, per_decode)

BF16, F32 = 2, 4
BLOCK = 64


def _linear(published: dict) -> tuple:
    return (published["linear_num_value_heads"],
            published["linear_key_head_dim"],
            published["linear_value_head_dim"],
            published["linear_conv_kernel_dim"])


def n_layers(published: dict, kind: str) -> int:
    return published["layer_types"][:published["num_hidden_layers"]].count(
        kind)


def state_bytes_a_row(published: dict) -> tuple:
    """-> (matrix state, convolution state) bytes of ONE row and layer."""
    H, K, V, taps = _linear(published)
    return H * V * K * F32, (taps - 1) * H * (2 * K + V) * BF16


def step_work(published: dict, rows_per_pass: float) -> tuple:
    """-> (flops, bytes) of the one-token form in ONE decode pass in
    which ``rows_per_pass`` rows advance."""
    matrix, conv = state_bytes_a_row(published)
    layers = n_layers(published, "linear_attention")
    return 0.0, float(layers * rows_per_pass * 2 * (matrix + conv))


def window_flops_a_block(published: dict) -> float:
    """Products of ONE head and block of 64 tokens."""
    _H, K, V, _ = _linear(published)
    c = BLOCK
    return float(2 * 2 * c * c * K              # K K^T, Q K^T
                 + c * c * (K + V)              # substitution on [K | V]
                 + 3 * 2 * c * K * V            # W S, Q S, K^T U
                 + 2 * c * c * V)               # A U


def window_work(published: dict, tokens_per_chunk: float) -> tuple:
    """-> (flops, bytes) of the window form in ONE chunk pass of
    ``tokens_per_chunk`` real tokens."""
    H, K, V, _ = _linear(published)
    layers = n_layers(published, "linear_attention")
    flops = tokens_per_chunk / BLOCK * H * window_flops_a_block(published)
    matrix, conv = state_bytes_a_row(published)
    bytes_ = tokens_per_chunk * H * (2 * K + 2 * V) * BF16 \
        + 2 * (matrix + conv)
    return layers * flops, float(layers * bytes_)


def attention_work(published: dict, keys_per_chunk: float,
                   pairs_per_chunk: float) -> tuple:
    """-> (flops, bytes) of the full-attention layers' window form in
    ONE chunk pass whose window reaches ``keys_per_chunk`` keys and
    attends ``pairs_per_chunk`` (query, key) pairs."""
    h = published["num_attention_heads"]
    hkv = published["num_key_value_heads"]
    hd = published["hidden_size"] // h
    layers = n_layers(published, "full_attention")
    flops = 2.0 * 2.0 * pairs_per_chunk * h * hd
    return layers * flops, float(layers * keys_per_chunk * 2 * hkv * hd
                                 * BF16)
