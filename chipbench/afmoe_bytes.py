"""Operations and bytes the ``afmoe`` layout's mechanisms must do, from
shapes and the window's counters (the roofline shares of
``layer_metrics/swa_decode_roofline.serve.py``, ``swa_prefill_roofline.
serve.py`` and ``sigmoid_gated_expert_roofline.serve.py`` divide the
least time they take at ``peaks.json``'s rates by the traced time).

Only what the ALGORITHM needs is counted, whatever implements it:

  * a window layer's one-token attention, a decode pass: the blocks that
    hold a key INSIDE a live row's window (the engine's
    ``window_blocks_attended``: from the block of key ``len - window``
    to the block of the row's newest key) read once from both pools at
    the K/V heads' lanes; blocks behind the window are not counted,
    whether or not an implementation reads them; its arithmetic (4 flop
    a key, query head and lane) is far under the chip's ridge and is
    counted for the maximum all the same;
  * a window layer's chunk attention, a chunk: each (query, key) pair
    INSIDE the window of the chunk's real queries (``window_query_
    keys``) attended in every query head over ``head_dim`` lanes twice
    (scores, values), and every key in the chunk's reach
    (``window_chunk_keys``: at most window + chunk) read once from both
    pools; compute bound;
  * the experts sublayer, a decode pass: the three matrices of each held
    expert that the pass's tokens really chose (``expert_touched_held_
    decode``), the shared expert and the router once an experts layer.
"""

from __future__ import annotations

# the roofline's rule and the window's counts a pass, as the latent
# cell's bytes file has them
from chipbench.deepseek_v2_bytes import (least_seconds,      # noqa: F401
                                         per_chunk, per_decode)
# held experts touched a decode pass, from the engine's counters
from chipbench.hybrid_bytes import traced                    # noqa: F401
from chipbench.nemotron_bytes import touched_per_decode      # noqa: F401

BF16, F32 = 2, 4


def layers_of(published: dict, kind: str) -> int:
    return published["layer_types"][:published["num_hidden_layers"]].count(
        kind)


def expert_layers(published: dict) -> int:
    return published["num_hidden_layers"] - published["num_dense_layers"]


def _heads(published: dict) -> tuple:
    return (published["num_attention_heads"],
            published["num_key_value_heads"], published["head_dim"])


def swa_decode_work(published: dict, block_size: int,
                    blocks_per_pass: float) -> tuple:
    """-> (flops, bytes) of the window layers' one-token attention in
    ONE decode pass whose live rows hold ``blocks_per_pass`` blocks with
    a key inside their window."""
    h, hkv, hd = _heads(published)
    layers = layers_of(published, "sliding_attention")
    keys = blocks_per_pass * block_size
    return (layers * 2.0 * 2.0 * keys * h * hd,
            float(layers * keys * 2 * hkv * hd * BF16))


def swa_prefill_work(published: dict, keys_per_chunk: float,
                     pairs_per_chunk: float) -> tuple:
    """-> (flops, bytes) of the window layers' chunk attention in ONE
    chunk pass that attends ``pairs_per_chunk`` in-window (query, key)
    pairs over ``keys_per_chunk`` keys in reach."""
    h, hkv, hd = _heads(published)
    layers = layers_of(published, "sliding_attention")
    return (layers * 2.0 * 2.0 * pairs_per_chunk * h * hd,
            float(layers * keys_per_chunk * 2 * hkv * hd * BF16))


def gated_expert_bytes_per_decode(published: dict,
                                  touched_per_pass: float) -> float:
    """``touched_per_pass``: held experts with at least one assignment,
    summed over the pass's experts layers.  An expert is ``[W_gate |
    W_up] [d, 2 f]`` and ``W_down [f, d]``; the shared expert the same at
    its own width; the router ``[d, E]`` and its float32 bias."""
    d, e = published["hidden_size"], published["num_experts"]
    f = published["moe_intermediate_size"]
    one = 3 * d * f
    shared = 3 * d * f * published["num_shared_experts"]
    n = expert_layers(published)
    return BF16 * (touched_per_pass * one + n * (shared + d * e)) \
        + F32 * n * e
