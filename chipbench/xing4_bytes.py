"""Operations and bytes the ``xing4_0`` layout's own mechanisms must do,
from shapes and the window's counters (the roofline shares of
``layer_metrics/mhc_chunk_roofline.serve.py`` and ``noaux_expert_
roofline.serve.py`` divide the least time they take at ``peaks.json``'s
rates by the traced time).  The latent attention's two forms are
``deepseek_v2_bytes``'s functions at this configuration's numbers.

Only what the ALGORITHM needs is counted, whatever implements it:

  * the stream mix, a sublayer and token: the ``n`` streams of ``C``
    bfloat16 lanes read ONCE for the maps (the norm and the product with
    ``Phi`` are one pass), once for ``H_pre X``, once more and written
    once for ``X' = H_res X + H_post^T F``: 4 passes of ``n C`` lanes;
    ``F``'s output ``[C]`` read and the mixed stream ``[C]`` written;
    ``Phi`` ``[n + n + n n, n C]`` float32 and the norm's weight once a
    sublayer, however many tokens.  The products (2 flop a ``Phi``
    entry and token, ~100 flop a token for the 20 iterations on a 4 x 4
    matrix) are a thirtieth of the chip's ridge at these bytes: memory
    bound, and counted for the maximum all the same.  An implementation
    that keeps the streams in VMEM between the three passes, or between
    a sublayer's way out and the next one's way in, moves FEWER bytes
    than this and would read over 100 %: the count is then the thing to
    lower (to 2 passes: one read, one write), not the share to cap.
  * the experts sublayer, a decode pass: the three matrices of each
    expert that the pass's tokens really chose (``expert_touched_held_
    decode``), the shared expert's three, the router with its float32
    bias, once an experts layer.
"""

from __future__ import annotations

# the roofline's rule and the window's counts a pass, as the latent
# cell's bytes file has them; the traced seconds' counters in place of
# the window's, and experts touched a decode pass, as the hybrid
# family's first bytes file has them
from chipbench.deepseek_v2_bytes import (least_seconds,      # noqa: F401
                                         per_chunk, per_decode)
from chipbench.hybrid_bytes import (mean_active_rows, traced,  # noqa: F401
                                    touched_per_decode)

BF16, F32 = 2, 4


def sublayers(published: dict) -> int:
    """Residual sublayers, each with maps of its own: a mixer and a
    feed-forward a layer."""
    return 2 * published["num_hidden_layers"]


def expert_layers(published: dict) -> int:
    return published["num_hidden_layers"] - published["first_k_dense_replace"]


def mhc_work(published: dict, tokens: float) -> tuple:
    """-> (flops, bytes) of the stream mix of ALL sublayers in one pass
    over ``tokens`` real tokens."""
    n, c = published["hc_mult"], published["hidden_size"]
    m = 2 * n + n * n
    streams = 4 * n * c * BF16          # maps, H_pre X, X' read + written
    one = 2 * c * BF16                  # the mixed stream out, F(.) in
    fixed = m * n * c * F32 + n * c * BF16
    flops = 2.0 * m * n * c + 2.0 * n * c * (1 + n + 1) \
        + 6.0 * n * n * published["hc_sinkhorn_iters"]
    s = sublayers(published)
    return s * tokens * flops, float(s * (tokens * (streams + one) + fixed))


def routed_expert_bytes_per_decode(published: dict,
                                   touched_per_pass: float) -> float:
    """``touched_per_pass``: experts with at least one assignment, summed
    over the pass's experts layers.  An expert is ``[W_1 | W_3] [d, 2
    f]`` and ``W_2 [f, d]``; the shared expert the same at its own
    width; the router ``[d, E]`` and its float32 bias."""
    d, e = published["hidden_size"], published["n_routed_experts"]
    f = published["moe_intermediate_size"]
    shared = 3 * d * f * published["n_shared_experts"]
    n = expert_layers(published)
    return BF16 * (touched_per_pass * 3 * d * f + n * (shared + d * e)) \
        + F32 * n * e
