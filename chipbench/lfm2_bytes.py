"""Operations and bytes the ``lfm2_moe`` layout's mechanisms must do,
from shapes and the window's counters (the roofline shares of
``layer_metrics/short_conv_prefill_roofline.serve.py`` and
``bias_routed_expert_roofline.serve.py`` divide the least time they take
at ``peaks.json``'s rates by the traced time).

Only what the ALGORITHM needs is counted, whatever implements it:

  * a short-convolution layer's one-token step, a decode pass: both
    projections' matrices and the taps read once (``[d, 3 d]``, ``[d,
    d]``, ``[L, d]``), and each live row's state ``[L - 1, d]`` read and
    written; the arithmetic (2 flop a weight and live row) is far under
    the chip's ridge and is counted for the maximum all the same.  A
    snapshot written when a row closes a block is 1 / block_size of a
    state write and is not counted.  NO share is read from this count:
    the compiler prefetches a layer's 33.6 MB of projections into VMEM
    under the experts' grouped matmuls before it, so the ops a trace
    can label as this mechanism's run at 2.3 TB/s "of HBM" and their
    time leaves the streaming out (the share read 125.8 %: my chip run,
    PR 52, call 3); the count stays for a program-level roofline;
  * its window form, a chunk pass: 2 flop a weight of both projections
    and REAL token of the chunk (``prefill_tokens`` over ``chunk_
    passes``; padding is not work), the matrices read once: COMPUTE
    bound from ~240 real tokens a chunk (197 TFLOP/s over 819 GB/s), and
    the cell's chunks hold 500-1,024;
  * the experts sublayer, a decode pass: the three matrices of each
    expert that the pass's tokens really chose (``expert_touched_held_
    decode``) and the router with its float32 bias, once an experts
    layer; there is no shared expert.
"""

from __future__ import annotations

# the roofline's rule and the window's counts a pass, as the latent
# cell's bytes file has them; the traced seconds' counters in place of
# the window's, as the afmoe cell's
from chipbench.afmoe_bytes import traced                     # noqa: F401
from chipbench.deepseek_v2_bytes import (least_seconds,      # noqa: F401
                                         per_chunk, per_decode)
# experts touched a decode pass, from the engine's counters
from chipbench.nemotron_bytes import touched_per_decode      # noqa: F401

BF16, F32 = 2, 4


def conv_layers(published: dict) -> int:
    return published["layer_types"][:published["num_hidden_layers"]].count(
        "conv")


def expert_layers(published: dict) -> int:
    return published["num_hidden_layers"] - published["num_dense_layers"]


def _conv_weights(published: dict) -> int:
    d, L = published["hidden_size"], published["conv_L_cache"]
    return d * 3 * d + d * d + L * d


def short_conv_step_work(published: dict, rows_per_pass: float) -> tuple:
    """-> (flops, bytes) of the short-convolution layers' one-token
    step in ONE decode pass that advances ``rows_per_pass`` live rows."""
    d, L = published["hidden_size"], published["conv_L_cache"]
    n, w = conv_layers(published), _conv_weights(published)
    state = 2.0 * rows_per_pass * (L - 1) * d * BF16     # read and written
    return n * 2.0 * rows_per_pass * w, n * (w * BF16 + state)


def short_conv_prefill_work(published: dict,
                            tokens_per_chunk: float) -> tuple:
    """-> (flops, bytes) of the short-convolution layers' window form in
    ONE chunk pass of ``tokens_per_chunk`` real tokens: the matrices
    once, the window's activations in and out of each product."""
    d = published["hidden_size"]
    n, w = conv_layers(published), _conv_weights(published)
    activations = tokens_per_chunk * (d + 3 * d + d + d) * BF16
    return (n * 2.0 * tokens_per_chunk * w,
            n * (w * BF16 + activations))


def routed_expert_bytes_per_decode(published: dict,
                                   touched_per_pass: float) -> float:
    """``touched_per_pass``: experts with at least one assignment, summed
    over the pass's experts layers.  An expert is ``[W_1 | W_3] [d, 2
    f]`` and ``W_2 [f, d]``; the router ``[d, E]`` and its float32
    bias; no shared expert."""
    d, e = published["hidden_size"], published["num_experts"]
    f = published["moe_intermediate_size"]
    n = expert_layers(published)
    return BF16 * (touched_per_pass * 3 * d * f + n * d * e) + F32 * n * e
