"""Bytes the ``nemotron_h`` layout's two memory-bound mechanisms must move
in ONE decode pass (``hybrid_bytes.py`` for the other widths and forms;
the roofline shares of ``layer_metrics/relu2_expert_roofline.serve.py``
and ``grouped_ssm_update_roofline.serve.py`` divide them by the traced
time and by ``peaks.json``'s HBM bandwidth).

Only what the ALGORITHM needs is counted: the two matrices of each held
expert that the pass's tokens really chose — from the engine's counter of
experts touched, not from an estimate of the routing — the shared expert
and the router once an expert layer, and the recurrent state of the rows
that really advance.  The zero columns ``w_in`` is stored with (1856 ->
1920) are read by the kernel and not credited.
"""

from __future__ import annotations

from chipbench.hybrid_bytes import (BF16, F32,            # noqa: F401
                                   mean_active_rows, touched_per_decode)


def relu2_expert_bytes_per_decode(published: dict, expert_layers: int,
                                  touched_per_pass: float) -> float:
    """``touched_per_pass``: held experts with at least one assignment,
    summed over the pass's ``expert_layers`` expert layers.  An expert is
    ``W_up [d, f]`` and ``W_down [f, d]``; the shared expert the same at
    its own width; the router ``[d, E]`` and its float32 bias."""
    d, e = published["hidden_size"], published["n_routed_experts"]
    one = 2 * d * published["moe_intermediate_size"]
    shared = 2 * d * published["moe_shared_expert_intermediate_size"]
    return BF16 * (touched_per_pass * one + expert_layers * (shared + d * e)) \
        + F32 * expert_layers * e


def grouped_ssm_state_bytes_per_decode(published: dict,
                                       active_rows: float) -> float:
    """Every advancing row's SSM state (float32) and convolution state
    (bfloat16) read once and written once, every ``M`` layer."""
    h, p, n = (published["mamba_num_heads"], published["mamba_head_dim"],
               published["ssm_state_size"])
    conv = (published["conv_kernel"] - 1) * (
        h * p + 2 * published["n_groups"] * n)
    pattern = published["hybrid_override_pattern"][
        :published["num_hidden_layers"]]
    per_row = pattern.count("M") * (h * p * n * F32 + conv * BF16)
    return 2.0 * active_rows * per_row
