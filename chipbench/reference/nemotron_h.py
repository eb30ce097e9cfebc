"""The ``nemotron_h`` forward pass in plain ``jax.numpy``: the oracle.

Written from the published configuration's keys and the family's public
description (``modeling_nemotron_h.py``): token embedding, no multiplier
and no position embedding; for each character of
``hybrid_override_pattern`` ONE residual sublayer ``x = x +
mixer(RMSNorm(x) * w)``; logits ``(RMSNorm(x) * w_f) @ W_head`` with an
untied head ``[d, V]``.  The mixer by the pattern's character:

  * ``M`` (Mamba-2): inner width ``mamba_num_heads x mamba_head_dim``
    (NOT ``expand x hidden_size``), G = ``n_groups`` B/C groups, N =
    ``ssm_state_size``.  ``[z | xBC | dt] = in_proj(h)``; ``xBC =
    silu(causal_conv1d(xBC) + b)``; ``[x | B | C]`` with B, C ``[G,
    N]``; ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; head h
    (state ``[head_dim, N]``) reads group ``h // (heads / G)``: ``S_t =
    exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t`` —
    computed here TOKEN BY TOKEN, the definition, not a chunked form;
    ``y = GroupRMSNorm(y * silu(z)) * w`` with the mean square taken
    over each group's ``inner / G`` channels; ``out_proj``.
  * ``*`` (attention): ``num_attention_heads`` query heads over
    ``num_key_value_heads`` K/V heads of ``head_dim`` (each shared by a
    run of consecutive query heads), no bias, NO rotary; ``softmax(q k^T
    / sqrt(head_dim), causal) v``; output projection.
  * ``E`` (experts): scores ``s = sigmoid(h W_r)`` over all
    ``n_routed_experts`` in float32; the ``num_experts_per_tok`` largest
    of ``s + bias`` are chosen (``n_group`` = ``topk_group`` = 1: no
    group limit); weights ``g = s[chosen] / sum(s[chosen]) *
    routed_scaling_factor`` — from the UNBIASED scores; expert e is
    ``W_down_e relu(W_up_e h)^2``; plus one shared expert of the same
    form, ungated.  Computed here DENSELY: every held expert on every
    token, times a weight that is 0 where the token did not choose it.
  * ``-`` (a dense MLP layer) is not in this model's pattern and is
    refused.

The share: ``held = (lo, hi)`` names the experts whose weights
``ffn.w_in / w_out`` hold; the router keeps all its outputs and its
top-k, and what absent experts would add is left out (the model-configs
guide's cut; the tests add two shares up to the whole).  The vocabulary
is whatever ``wte`` and ``head`` hold.

float32 throughout, matrix products at the ``highest`` precision, no
kernel, no cache, no batching.  It imports nothing from ``ray_tpu``.
Parameters arrive as the plain dict the system under test holds them in
(that layout is data, not code): ``wte [V, d]``, ``norm_f [d]``, ``head
[d, V]`` and ``layers``, a list of one dict a pattern character: ``{"mixer":
...}`` for ``M`` and ``*``, ``{"ffn": ...}`` for ``E``.  A Mamba
``mixer``: ``norm``, ``in_proj [d, di + C + H]``, ``conv_w [K, C]`` with
tap K-1 on the current token, ``conv_b``, ``dt_bias``, ``A_log``, ``D``,
``gnorm [di]``, ``out_proj [di, d]``; an attention ``mixer``: ``norm``,
``wqkv [d, (h + 2 hkv) hd]`` q, k, v side by side and heads major,
``wo``.  ``ffn``: ``norm``, ``router [d, E]``, ``router_bias [E]``,
``shared_in [d, ws]``, ``shared_out [ws, d]``, ``w_in [E_held, d, we']``
(the system stores the stack with its columns zero-padded to whole
128-lane tiles, we' >= we; the first ``moe_intermediate_size`` columns
are the expert's, the rest is not read here), ``w_out [E_held, we, d]``.

``ASSUMED`` lists what the configuration does not say and this file
infers.  ``round_to`` (a dtype) rounds every matrix product's two inputs
to that dtype first: the same mathematics in a LOWER precision, which
the serving check must be tight enough to tell from the stated one.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
MAX_EMITTED = 2048     # margins() scores at most this many tokens a request

ASSUMED = {
    "rotary": "none: the family states that it uses no position embedding; "
              "rope_theta and partial_rotary_factor are in the config and "
              "unused",
    "attention_scale": "1 / sqrt(head_dim)",
    "norm_eps": "layer_norm_epsilon for every RMSNorm, the gated one too "
                "(norm_eps holds the same value)",
    "gated_norm": "gate first (y * silu(z)), then the norm, over each of "
                  "the n_groups groups of inner / n_groups channels",
    "conv": "tap order oldest first, with bias (use_conv_bias), silu after",
    "router": "float32 scores; the selection bias (e_score_correction_"
              "bias) enters the choice only, never the weights",
    "shared_expert": "one (n_shared_experts 1), width moe_shared_expert_"
                     "intermediate_size, no gate on its output",
}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mm(a, b, round_to):
    if round_to is not None:
        a, b = a.astype(round_to).astype(F32), b.astype(round_to).astype(F32)
    return a @ b


def _relu2_mlp(h, w_up, w_down, round_to):
    return _mm(jnp.square(jax.nn.relu(_mm(h, w_up, round_to))), w_down,
               round_to)


def _attention(c, ap, h, round_to):
    s, _ = h.shape
    nh, nkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    qkv = _mm(h, ap["wqkv"], round_to)
    q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], axis=-1)
    q = q.reshape(s, nh, hd).transpose(1, 0, 2)
    k = jnp.repeat(k.reshape(s, nkv, hd).transpose(1, 0, 2), nh // nkv, 0)
    v = jnp.repeat(v.reshape(s, nkv, hd).transpose(1, 0, 2), nh // nkv, 0)
    att = _mm(q, k.transpose(0, 2, 1), round_to) / math.sqrt(hd)
    att = jnp.where(jnp.tril(jnp.ones((s, s), bool)), att, -jnp.inf)
    o = _mm(jax.nn.softmax(att, axis=-1), v, round_to)
    return _mm(o.transpose(1, 0, 2).reshape(s, nh * hd), ap["wo"], round_to)


def _mamba(c, mp, h, round_to):
    s, _ = h.shape
    H, P, N, G, K = (c["mamba_num_heads"], c["mamba_head_dim"],
                     c["ssm_state_size"], c["n_groups"], c["conv_kernel"])
    di = H * P
    ch = di + 2 * G * N
    zxd = _mm(h, mp["in_proj"], round_to)
    z, xBC, dt = jnp.split(zxd, [di, di + ch], axis=-1)
    padded = jnp.concatenate([jnp.zeros((K - 1, ch), F32), xBC], axis=0)
    conv = mp["conv_b"] + sum(padded[j:j + s] * mp["conv_w"][j]
                              for j in range(K))
    x, B, C = jnp.split(jax.nn.silu(conv), [di, di + G * N], axis=-1)
    x = x.reshape(s, H, P)
    # head h reads group h // (H / G)
    B = jnp.repeat(B.reshape(s, G, N), H // G, axis=1)       # [s, H, N]
    C = jnp.repeat(C.reshape(s, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + mp["dt_bias"])                 # [s, H]
    A = -jnp.exp(mp["A_log"])

    def step(S, t):
        xt, dtt, Bt, Ct = t
        S = jnp.exp(dtt * A)[:, None, None] * S \
            + (dtt[:, None] * xt)[:, :, None] * Bt[:, None, :]
        return S, (S * Ct[:, None, :]).sum(-1) + mp["D"][:, None] * xt

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (x, dt, B, C))
    y = y.reshape(s, di) * jax.nn.silu(z)
    y = _rms_norm(y.reshape(s, G, di // G), 1.0,
                  c["layer_norm_epsilon"]).reshape(s, di) * mp["gnorm"]
    return _mm(y, mp["out_proj"], round_to)


def _choice(c, fp, h, round_to):
    """-> (scores [s, E] float32, the k experts a token is routed to)."""
    scores = jax.nn.sigmoid(_mm(h, fp["router"], round_to))  # [s, E]
    _, idx = jax.lax.top_k(scores + fp["router_bias"],
                           c["num_experts_per_tok"])
    return scores, idx


def _experts(c, fp, h, held, round_to, forced=None):
    """``forced`` [s, k]: experts to route to in place of the layer's
    own choice (``logits(.., forced=)`` says what for)."""
    lo, hi = held
    scores, idx = _choice(c, fp, h, round_to)
    if forced is not None:
        idx = forced
    chosen = jnp.take_along_axis(scores, idx, axis=-1)       # unbiased
    weights = chosen / chosen.sum(-1, keepdims=True) \
        * c["routed_scaling_factor"]
    # weight of expert e on each token, or 0
    dense = jnp.zeros((h.shape[0], c["n_routed_experts"]), F32).at[
        jnp.arange(h.shape[0])[:, None], idx].set(weights)

    def one(acc, e):
        w_up, w_down, g = e
        return acc + g[:, None] * _relu2_mlp(h, w_up, w_down, round_to), None

    w_up = fp["w_in"][:, :, :fp["w_out"].shape[1]]
    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             (w_up, fp["w_out"], dense[:, lo:hi].T))
    return routed + _relu2_mlp(h, fp["shared_in"], fp["shared_out"],
                               round_to)


MIXERS = {"M": _mamba, "*": _attention}


@partial(jax.jit, static_argnames=("kind", "c", "held", "round_to"))
def _layer(lp, x, kind, c, held, round_to, forced=None):
    """One pattern character on x [s, d] (one sequence)."""
    c = dict(c)
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        h = _rms_norm(x, lp["norm"], c["layer_norm_epsilon"])
        if kind == "E":
            return x + _experts(c, lp, h, held, round_to, forced)
        return x + MIXERS[kind](c, lp, h, round_to)


@partial(jax.jit, static_argnames=("c", "round_to"))
def _routed_to(lp, x, c, round_to):
    """The experts an ``E`` layer routes x [s, d] to, [s, k]."""
    c = dict(c)
    with jax.default_matmul_precision("highest"):
        lp = {k: lp[k].astype(F32) for k in ("norm", "router",
                                             "router_bias")}
        h = _rms_norm(x, lp["norm"], c["layer_norm_epsilon"])
        return _choice(c, lp, h, round_to)[1]


@partial(jax.jit, static_argnames=("c", "round_to"))
def _head(w_head, norm_f, x, c, round_to):
    c = dict(c)
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, norm_f.astype(F32), c["layer_norm_epsilon"])
        return _mm(h, w_head.astype(F32), round_to)


def _static(config: dict) -> tuple:
    """The published keys the layers read, hashable for ``jit``."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "layer_norm_epsilon", "mamba_num_heads",
            "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel",
            "n_routed_experts", "num_experts_per_tok",
            "routed_scaling_factor")
    return tuple((k, config[k]) for k in keys)


def pattern(config: dict) -> str:
    p = config["hybrid_override_pattern"][:config["num_hidden_layers"]]
    if set(p) - {"M", "E", "*"}:
        raise ValueError(f"pattern {p!r}: only M, E and * are written here")
    return p


def logits(params, tokens, config: dict, held: tuple, rows=None,
           round_to=None, forced=None, chosen=None):
    """tokens [s] int -> logits [s, V] float32 (``rows``: only those
    positions).  A Python loop over the layers, each converted to
    float32 on its own, so that it fits beside the served weights.

    ``chosen``: a list that receives, an ``E`` layer, the experts each
    token was routed to; ``forced``: such a list from another run, routed
    to in place of this run's own choices.  Together they tell an error
    of the arithmetic from a router's tie that a rounding flipped: a
    lower precision FORCED onto the float32 run's experts keeps the
    first and loses the second (``precision_reading_nemotron_h.py``)."""
    c = _static(config)
    forced = iter(forced or ())
    x = params["wte"][jnp.asarray(tokens)].astype(F32)
    for kind, lp in zip(pattern(config), params["layers"]):
        lp = lp["ffn" if kind == "E" else "mixer"]
        to = next(forced, None) if kind == "E" else None
        if kind == "E" and chosen is not None:
            chosen.append(to if to is not None
                          else _routed_to(lp, x, c, round_to))
        x = _layer(lp, x, kind, c, tuple(held), round_to, to)
    if rows is not None:
        x = x[rows]
    return _head(params["head"], params["norm_f"], x, c, round_to)


def margins(params, prompt, emitted, config: dict, held: tuple, width: int,
            round_to=None):
    """Teacher-forced check of one served request: for each emitted
    token, how far its logit lies below that position's maximum (0 = the
    argmax).  prompt + emitted is padded to ``width`` (causal, so the
    padding changes nothing before it) to keep one compiled shape.
    -> (margins [len(emitted)], argmax tokens [len(emitted)])."""
    import numpy as np
    seq = np.zeros((width,), np.int32)
    n_p, n = len(prompt), len(prompt) + len(emitted)
    seq[:n_p], seq[n_p:n] = prompt, emitted
    if len(emitted) > MAX_EMITTED:
        raise ValueError(f"{len(emitted)} emitted tokens > {MAX_EMITTED}")
    # the rows are padded to a fixed count too (one compiled head)
    rows = np.minimum(np.arange(n_p - 1, n_p - 1 + MAX_EMITTED), width - 1)
    step = np.asarray(logits(params, seq, config, held, rows=rows,
                             round_to=round_to))[:len(emitted)]
    chosen = step[np.arange(len(emitted)), np.asarray(emitted)]
    return step.max(-1) - chosen, step.argmax(-1)
