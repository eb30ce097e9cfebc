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


def expert_bytes_per_decode(published: dict,
                            touched_per_pass: float) -> float:
    """Routed + shared expert weights and the router, all layers, read
    once a pass.  A held expert's weights are needed only if one of the
    pass's tokens chose it: ``touched_per_pass`` is the held experts
    with at least one assignment, SUMMED over the pass's expert layers,
    as the engine counted them (``touched_per_decode``).  Counted, not
    estimated from the window's mean rows under uniform routing
    (``1 - (1 - k/E) ** rows``): that curve is concave, so the mean rows
    overcount the mean touched, and a share of the roofline then reads
    over 100 % when passes are thin."""
    d, e = published["hidden_size"], published["num_local_experts"]
    one = 3 * d * published["intermediate_size"]          # in (2f) + out (f)
    shared = 3 * d * published["shared_intermediate_size"]
    layers = published["num_hidden_layers"]
    return BF16 * (touched_per_pass * one + layers * (shared + d * e))


def touched_per_decode(obs: dict):
    """Held experts touched by a decode pass's rows, summed over the
    expert layers, from the engine's counters over the decode passes
    (``jit_step`` and the step part of ``jit_step_chunk``), or None."""
    c = obs.get("counters") or {}
    if not c.get("decode_iterations") or "expert_touched_held_decode" not in c:
        return None
    return c["expert_touched_held_decode"] / c["decode_iterations"]


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


def traced(obs: dict) -> dict:
    """``obs`` with the counters of the TRACED seconds in place of the
    whole window's, where the kind took them (``traced_counters``): the
    work a roofline share credits is then that of the passes whose time
    it divides by.  The traffic is one fixed cycle, so the 4 s of 51
    that a run traces hold the same requests every time, and their rows
    a pass are not the window's mean."""
    return {**obs, "counters": obs.get("traced_counters")
            or obs.get("counters")}


def mean_active_rows(obs: dict):
    """Mean decode rows advanced a pass in the window, from the engine's
    counters, or None."""
    c = obs.get("counters") or {}
    if not c.get("decode_iterations"):
        return None
    return c["row_steps"] / c["decode_iterations"]
