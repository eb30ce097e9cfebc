"""The ``deepseek_v2`` forward pass in plain ``jax.numpy``: the oracle.

Written from the published configuration's keys and the family's public
description (``modeling_deepseek.py``, arXiv:2405.04434): token
embedding, no multiplier; layer l is ``x = x + A(RMSNorm(x) w1)`` then
``x = x + F(RMSNorm(x) w2)``; logits ``(RMSNorm(x) w_f) W_head`` with an
untied head ``[d, V]``.

  * ``A``, latent attention over ``num_attention_heads`` heads, in its
    NON-absorbed form: ``c_q = RMSNorm(h W_qa) w`` [q_lora_rank]; ``q =
    c_q W_qb``, per head ``[q_nope | q_rope]`` (``qk_nope_head_dim`` |
    ``qk_rope_head_dim``); ``[c_kv | k_rope] = h W_kva`` (``kv_lora_rank``
    | rope); ``c_kv = RMSNorm(c_kv) w``; ``k_rope = rot(k_rope, pos)``,
    ONE for all heads; ``q_rope = rot(q_rope, pos)``; per head ``[k_nope
    | v] = c_kv W_kvb``; scores ``(q_nope . k_nope + q_rope . k_rope)
    scale``, causal softmax in float32, ``o = P v``, ``out = concat(o)
    W_o``.  ``scale = (nope + rope)^-0.5 m^2``, ``m = 0.1 mscale_all_dim
    ln(factor) + 1``.  Computed a block of queries at a time against ALL
    keys, every key decompressed: no cache, nothing absorbed.
  * ``rot``, YaRN rotary on the rope lanes: ``f_i = theta^(-2i / dim)``;
    ``low, high = floor, ceil`` of ``dim ln(orig / (2 pi beta)) / (2 ln
    theta)`` at ``beta_fast`` and ``beta_slow``; ``ramp_i = clip((i -
    low) / (high - low), 0, 1)``; ``inv_freq_i = f_i / factor ramp_i +
    f_i (1 - ramp_i)``; cos and sin times ``yarn_mscale(factor, mscale)
    / yarn_mscale(factor, mscale_all_dim)``.
  * ``F`` for l < ``first_k_dense_replace``: a gated MLP of width
    ``intermediate_size``, ``W_down (silu(W_gate h) * W_up h)``.
  * ``F`` else, experts: ``s = softmax(h W_g)`` over all
    ``n_routed_experts`` in float32; the experts lie in ``n_group``
    consecutive groups, a group's score is the MAXIMUM of its experts'
    (``group_limited_greedy``), the best ``topk_group`` groups are kept
    and the other groups' scores set to 0; the ``num_experts_per_tok``
    largest of what is left are chosen; gates = those ``s`` times
    ``routed_scaling_factor``, NOT normalised (``norm_topk_prob``
    false); expert e is the gated MLP of width ``moe_intermediate_size``;
    plus the shared experts: ONE gated MLP of width ``n_shared_experts x
    moe_intermediate_size``, ungated by the router.  Computed DENSELY:
    every held expert on every token, times a gate that is 0 where the
    token did not choose it.

The share: ``held = (lo, hi)`` names the experts whose weights
``ffn.w_in / w_out`` hold; the router keeps all its outputs, its groups
and its top-k, and what absent experts would add is left out (the
model-configs guide's cut; the tests add eight shares up to the whole).
The vocabulary is whatever ``wte`` and ``head`` hold.

float32 throughout, matrix products at the ``highest`` precision, no
kernel, no cache.  It imports nothing from ``ray_tpu``.  Parameters
arrive as the plain dict the system under test holds them in (that
layout is data, not code): ``wte [V, d]``, ``norm_f [d]``, ``head [d,
V]`` and ``layers``, a list of ``{"mixer": ..., "ffn": ...}``.
``mixer``: ``norm``, ``wq_a [d, rq]``, ``q_norm``, ``W_qb`` as its
no-position and rope columns apart, ``wq_nope [h, nope, rq]`` and
``wq_rope [rope, h, rq]`` (the rank minor), ``wkv_a [d, rkv + rope]``,
``kv_norm``, ``W_kvb`` a head, keys and values apart, ``w_uk [h, rkv,
nope]`` and ``w_uv [h, rkv, v]``, ``wo [h v, d]``.  A dense ``ffn``:
``norm``, ``w_in [d, 2 f]`` = [gate | up], ``w_out [f, d]``; an experts
``ffn``: ``norm``, ``router [d, E]``, ``shared_in [d, 2 ws]``,
``shared_out [ws, d]``, ``w_in [E_held, d, 2 we]``, ``w_out [E_held, we,
d]``.

``ASSUMED`` lists what the configuration does not say and this file
infers.  ``round_to`` (a dtype) rounds every matrix product's two inputs
to that dtype first: the same mathematics in a LOWER precision, which
the serving check must be tight enough to tell from the stated one.
``round_cache_to`` rounds only what a latent cache would hold (the
normed ``c_kv`` and the rotated ``k_rope``): the second control.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
MAX_EMITTED = 256      # margins() scores at most this many tokens a request
QUERY_BLOCK = 64       # queries attended at a time, against every key

ASSUMED = {
    "rotary_pairs": "half-split (lane j with j + rope / 2); the published "
                    "checkpoint's interleaved order is a column permutation "
                    "of W_qb / W_kva, immaterial under seeded weights",
    "norm_eps": "rms_norm_eps for every RMSNorm, the two inside the "
                "attention too",
    "router": "float32 scores; softmax over all experts; the masked "
              "groups' scores are 0, as the published implementation sets "
              "them",
    "shared_experts": "one gated MLP of width n_shared_experts x "
                      "moe_intermediate_size, no gate on its output",
    "unread": "seq_aux, aux_loss_alpha and the balance losses (training); "
              "ep_size; attention_dropout; num_key_value_heads (latent "
              "attention has no K/V heads)",
}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _round(a, round_to):
    return a if round_to is None else a.astype(round_to).astype(F32)


def _mm(a, b, round_to):
    return _round(a, round_to) @ _round(b, round_to)


def _gated_mlp(h, w_in, w_out, round_to):
    a, b = jnp.split(_mm(h, w_in, round_to), 2, axis=-1)
    return _mm(jax.nn.silu(a) * b, w_out, round_to)


def yarn_inv_freq(c: dict):
    """[rope / 2] float32: the closed form of the module's text."""
    rs, dim, theta = c["rope_scaling"], c["qk_rope_head_dim"], c["rope_theta"]
    i = jnp.arange(dim // 2, dtype=F32)
    f = theta ** (-2.0 * i / dim)

    def turn(beta):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (beta * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(turn(rs["beta_fast"])), 0)
    high = min(math.ceil(turn(rs["beta_slow"])), dim - 1)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return f / rs["factor"] * ramp + f * (1.0 - ramp)


def _yarn_mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _rot(x, pos, c):
    """x [s, ..., rope] at positions pos [s]."""
    rs = c["rope_scaling"]
    ang = pos.astype(F32)[:, None] * yarn_inv_freq(c)          # [s, r/2]
    m = _yarn_mscale(rs["factor"], rs["mscale"]) \
        / _yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    if x.ndim == 3:
        cos, sin = cos[:, None], sin[:, None]
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(c, ap, h, round_to, round_cache_to=None):
    s, _ = h.shape
    nh, dn, dr, dv, rkv = (c["num_attention_heads"], c["qk_nope_head_dim"],
                           c["qk_rope_head_dim"], c["v_head_dim"],
                           c["kv_lora_rank"])
    eps = c["rms_norm_eps"]
    pos = jnp.arange(s)
    cq = _rms_norm(_mm(h, ap["wq_a"], round_to), ap["q_norm"], eps)
    q_nope = _mm(cq, ap["wq_nope"].reshape(nh * dn, -1).T,
                 round_to).reshape(s, nh, dn)
    q_rope = _rot(_mm(cq, ap["wq_rope"].reshape(dr * nh, -1).T, round_to)
                  .reshape(s, dr, nh).transpose(0, 2, 1), pos, c)
    ckv = _mm(h, ap["wkv_a"], round_to)
    c_kv = _round(_rms_norm(ckv[:, :rkv], ap["kv_norm"], eps),
                  round_cache_to)
    k_rope = _round(_rot(ckv[:, rkv:], pos, c), round_cache_to)  # [s, dr]
    k_nope = _mm(c_kv[None], ap["w_uk"], round_to)           # [h, s, dn]
    v = _mm(c_kv[None], ap["w_uv"], round_to)                # [h, s, dv]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[None], (nh, s, dr))], -1).transpose(0, 2, 1)
    rs = c["rope_scaling"]
    scale = (dn + dr) ** -0.5 * _yarn_mscale(rs["factor"],
                                             rs["mscale_all_dim"]) ** 2
    qf = jnp.concatenate([q_nope, q_rope], -1)               # [s, h, dn+dr]
    n_blocks = -(-s // QUERY_BLOCK)
    qf = jnp.pad(qf, ((0, n_blocks * QUERY_BLOCK - s), (0, 0), (0, 0)))

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qf, i * QUERY_BLOCK, QUERY_BLOCK)
        att = _mm(qb.transpose(1, 0, 2), k, round_to) * scale  # [h, Q, s]
        q_pos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        att = jnp.where(pos[None, None, :] <= q_pos[None, :, None], att,
                        -jnp.inf)
        o = _mm(jax.nn.softmax(att, axis=-1), v, round_to)   # [h, Q, dv]
        return o.transpose(1, 0, 2).reshape(QUERY_BLOCK, nh * dv)

    o = jax.lax.map(block, jnp.arange(n_blocks)).reshape(-1, nh * dv)[:s]
    return _mm(o, ap["wo"], round_to)


def _choice(c, fp, h, round_to):
    """-> (scores [s, E] float32, the k experts a token is routed to)."""
    scores = jax.nn.softmax(_mm(h, fp["router"], round_to), axis=-1)
    e, g = c["n_routed_experts"], c["n_group"]
    limited = scores
    if c["topk_method"] == "group_limited_greedy":
        best = scores.reshape(-1, g, e // g).max(-1)             # [s, g]
        _, keep = jax.lax.top_k(best, c["topk_group"])
        kept = jnp.zeros_like(best).at[
            jnp.arange(best.shape[0])[:, None], keep].set(1.0)
        limited = scores * jnp.repeat(kept, e // g, axis=1)
    _, idx = jax.lax.top_k(limited, c["num_experts_per_tok"])
    return scores, idx


def _experts(c, fp, h, held, round_to, forced=None):
    """``forced`` [s, k]: experts to route to in place of the layer's
    own choice (``logits(.., forced=)`` says what for)."""
    lo, hi = held
    scores, idx = _choice(c, fp, h, round_to)
    if forced is not None:
        idx = forced
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    if c["norm_topk_prob"]:
        gates = gates / gates.sum(-1, keepdims=True)
    gates = gates * c["routed_scaling_factor"]
    dense = jnp.zeros((h.shape[0], c["n_routed_experts"]), F32).at[
        jnp.arange(h.shape[0])[:, None], idx].set(gates)

    def one(acc, e):
        w_in, w_out, g = e          # an expert's matrices, as stored
        return acc + g[:, None] * _gated_mlp(
            h, w_in.astype(F32), w_out.astype(F32), round_to), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             (fp["w_in"], fp["w_out"], dense[:, lo:hi].T))
    return routed + _gated_mlp(h, fp["shared_in"], fp["shared_out"],
                               round_to)


@partial(jax.jit, static_argnames=("c", "held", "round_to",
                                   "round_cache_to"))
def _layer(lp, x, c, held, round_to, round_cache_to=None, forced=None):
    """One published layer on x [s, d] (one sequence)."""
    c = _dict(c)
    with jax.default_matmul_precision("highest"):
        # the stacked experts are converted one at a time, where used
        lp = jax.tree.map(lambda a: a if a.ndim == 3 else a.astype(F32), lp)
        ap, fp = lp["mixer"], lp["ffn"]
        eps = c["rms_norm_eps"]
        x = x + _attention(c, ap, _rms_norm(x, ap["norm"], eps), round_to,
                           round_cache_to)
        h = _rms_norm(x, fp["norm"], eps)
        if "router" in fp:
            return x + _experts(c, fp, h, held, round_to, forced)
        return x + _gated_mlp(h, fp["w_in"], fp["w_out"], round_to)


@partial(jax.jit, static_argnames=("c", "round_to", "round_cache_to"))
def _routed_to(lp, x, c, round_to, round_cache_to=None):
    """The experts a layer's ``F`` routes to, [s, k], given the layer's
    INPUT x [s, d]."""
    c = _dict(c)
    with jax.default_matmul_precision("highest"):
        ap = jax.tree.map(lambda a: a.astype(F32), lp["mixer"])
        fp = {k: lp["ffn"][k].astype(F32) for k in ("norm", "router")}
        eps = c["rms_norm_eps"]
        x = x + _attention(c, ap, _rms_norm(x, ap["norm"], eps), round_to,
                           round_cache_to)
        return _choice(c, fp, _rms_norm(x, fp["norm"], eps), round_to)[1]


@partial(jax.jit, static_argnames=("c", "round_to"))
def _head(w_head, norm_f, x, c, round_to):
    c = _dict(c)
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, norm_f.astype(F32), c["rms_norm_eps"])
        return _mm(h, w_head.astype(F32), round_to)


KEYS = ("hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rms_norm_eps",
        "rope_theta", "n_routed_experts", "n_group", "topk_group",
        "topk_method", "num_experts_per_tok", "norm_topk_prob",
        "routed_scaling_factor")


def _static(config: dict) -> tuple:
    """The published keys the layers read, hashable for ``jit``."""
    return tuple((k, config[k]) for k in KEYS) + (
        ("rope_scaling", tuple(sorted(config["rope_scaling"].items()))),)


def _dict(c: tuple) -> dict:
    d = dict(c)
    d["rope_scaling"] = dict(d["rope_scaling"])
    return d


def logits(params, tokens, config: dict, held: tuple, rows=None,
           round_to=None, round_cache_to=None, forced=None, chosen=None):
    """tokens [s] int -> logits [s, V] float32 (``rows``: only those
    positions).  A Python loop over the layers, each converted to
    float32 on its own, so that it fits beside the served weights.

    ``chosen``: a list that receives, an experts layer, the experts each
    token was routed to; ``forced``: such a list from another run, routed
    to in place of this run's own choices (an error of the arithmetic
    told from a router's tie that a rounding flipped)."""
    if config["scoring_func"] != "softmax" or config["rope_scaling"][
            "type"] != "yarn":
        raise ValueError("only softmax scores and yarn rotary are written "
                         "here")
    c = _static(config)
    forced = iter(forced or ())
    x = params["wte"][jnp.asarray(tokens)].astype(F32)
    for lp in params["layers"]:
        routed = "router" in lp["ffn"]
        to = next(forced, None) if routed else None
        if routed and chosen is not None:
            chosen.append(to if to is not None else _routed_to(
                lp, x, c, round_to, round_cache_to))
        x = _layer(lp, x, c, tuple(held), round_to, round_cache_to, to)
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
