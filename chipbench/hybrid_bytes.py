"""Bytes the hybrid model's two memory-bound mechanisms must move in ONE
decode pass, from shapes (kept with the benchmark; the roofline shares of
``layer_metrics/expert_roofline_share.serve.py`` and
``ssm_update_roofline_share.serve.py`` divide them by the traced time and
by ``peaks.json``'s HBM bandwidth).

Only what the ALGORITHM needs is counted: the weights of the experts that
the pass's tokens were routed to, and the recurrent state of the rows
that really advance.  What the program moves beyond that (the state of
idle rows, rewritten unchanged) lowers the share; it is not credited.
"""

from __future__ import annotations

BF16, F32 = 2, 4


def expert_bytes_per_decode(published: dict, held: tuple,
                            active_rows: float) -> float:
    """Routed + shared expert weights and the router, all layers, read
    once a pass.  A held expert's weights are needed only if one of the
    pass's ``active_rows`` tokens chose it: under near-uniform routing
    (seeded random weights) a row's top-k misses a given expert with
    probability 1 - k/E, so the expected share of held experts touched
    is 1 - (1 - k/E) ** active_rows."""
    d, e = published["hidden_size"], published["num_local_experts"]
    k = published["num_experts_per_tok"]
    one = 3 * d * published["intermediate_size"]          # in (2f) + out (f)
    shared = 3 * d * published["shared_intermediate_size"]
    touched = (held[1] - held[0]) * (1.0 - (1.0 - k / e) ** active_rows)
    layers = published["num_hidden_layers"]
    return layers * BF16 * (touched * one + shared + d * e)


def ssm_state_bytes_per_decode(published: dict, active_rows: float) -> float:
    """Every advancing row's SSM state (float32) and convolution state
    (bfloat16) read once and written once, every mamba layer."""
    h, p, n = (published["mamba_n_heads"], published["mamba_d_head"],
               published["mamba_d_state"])
    conv = (published["mamba_d_conv"] - 1) * (
        h * p + 2 * published["mamba_n_groups"] * n)
    kinds = published["layer_types"][:published["num_hidden_layers"]]
    per_row = kinds.count("mamba") * (h * p * n * F32 + conv * BF16)
    return 2.0 * active_rows * per_row


def mean_active_rows(obs: dict):
    """Mean decode rows advanced a pass in the window, from the engine's
    counters, or None."""
    c = obs.get("counters") or {}
    if not c.get("decode_iterations"):
        return None
    return c["row_steps"] / c["decode_iterations"]
