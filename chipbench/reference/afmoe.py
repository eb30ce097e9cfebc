"""The ``afmoe`` forward pass (Arcee Trinity) in plain ``jax.numpy``: the
oracle.

Written from the published configuration's keys and the catalog row's
description of the family.  For one sequence of ``s`` tokens:

    x0      = wte[ids] * sqrt(hidden_size)        (mup_enabled; no position table)
    layer i:  a = RMSNorm_in(x) ;  q, k, v = a Wq, a Wk, a Wv   (no bias)
              g = a Wg                             (hidden -> heads * head_dim)
              q = RMSNorm_hd(q) * wq_n ; k = RMSNorm_hd(k) * wk_n   (per head)
              layer_types[i] == "sliding_attention":
                  q, k = rope(q, pos), rope(k, pos)   (rope_theta, ALL head_dim
                         lanes, half-split pairs (j, j + head_dim / 2), no scaling)
                  key j visible to query t  iff  t - sliding_window < j <= t
              "full_attention": no positions at all; j <= t
              o = softmax(q k^T / sqrt(head_dim)) v   (float32; query head h
                         reads K/V head h // (heads / kv_heads))
              x = x + RMSNorm_post(  (o * sigmoid(g)) Wo  )
              m = RMSNorm_pre_mlp(x)
              i < num_dense_layers:  f = Wd (silu(m Wg') * (m Wu))   (intermediate_size)
              else:  f = shared(m) + sum_{e in top-k} gate_e expert_e(m)
                     s = sigmoid(float32(m Wr))   (num_experts outputs)
                     top-k = the k largest of s + bias
                     gate = s[top-k] / (sum s[top-k] + 1e-20) * route_scale
                     experts and the shared expert: Wd (silu(m Wg') * (m Wu)),
                     width moe_intermediate_size (x num_shared_experts)
              x = x + RMSNorm_post_mlp(f)
    logits  = RMSNorm_f(x) W_head                  (untied)

The share: ``held = (lo, hi)`` names the experts whose weights ``ffn.w_in
/ w_out`` hold; the router keeps all ``num_experts`` outputs and its
top-k, and what absent experts would add is left out (the model-configs
guide's cut; the tests add the shares up to the whole).  The vocabulary
is whatever ``wte`` and ``head`` hold.  ``config["layer_types"]`` lists
the layers this parameter set holds, in order (its first
``num_hidden_layers`` entries); the first ``num_dense_layers`` of them
have the dense MLP.

float32 throughout, matrix products at the ``highest`` precision, no
kernel, no cache, no batching.  Computed in BLOCKS so that 33 k tokens
fit beside the served weights: attention a block of ``Q_BLOCK`` queries
at a time over all the keys (the whole score row of a query is present:
no running softmax), the MLPs and experts ``T_BLOCK`` tokens at a time,
the experts DENSELY (every held expert on every token, times a weight
that is 0 where the token did not choose it).  It imports nothing from
``ray_tpu``.  Parameters arrive as the plain dict the system under test
holds them in (that layout is data, not code): ``wte [V, d]``, ``norm_f
[d]``, ``head [d, V]`` and ``layers``, one ``{"mixer", "ffn"}`` a layer.
``mixer``: ``norm``, ``post_norm`` [d], ``wqkv [d, (h + 2 hkv + h) hd]``
(Wq, Wk, Wv and the gate's Wg side by side, heads major: four matrices
of one input held as one array), ``q_norm``, ``k_norm`` [hd], ``wo [h
hd, d]``.  A dense ``ffn``: ``norm``, ``post_norm``,
``w_in [d, 2 f]`` (gate | up), ``w_out [f, d]``; an experts ``ffn``:
``norm``, ``post_norm``, ``router [d, E]``, ``router_bias [E]``,
``shared_in [d, 2 ws]``, ``shared_out [ws, d]``, ``w_in [E_held, d, 2
we]``, ``w_out [E_held, we, d]``.

``ASSUMED`` lists what the catalog's row does not carry and this file
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
MAX_EMITTED = 512      # margins() scores at most this many tokens a request
Q_BLOCK = 256          # queries attended at a time
T_BLOCK = 2048         # tokens through an MLP or the experts at a time

ASSUMED = {
    "attention_scale": "1 / sqrt(head_dim)",
    "window": "inclusive of the query itself: sliding_window keys, "
              "t - sliding_window < j <= t",
    "rotary": "on the sliding_attention layers only, all head_dim lanes, "
              "half-split pairs, theta rope_theta, no scaling (rope_scaling "
              "null); full_attention layers see no position at all",
    "qk_norm": "RMSNorm over each head's head_dim lanes, one weight "
               "[head_dim] for q and one for k, before the rotation",
    "gate": "o_proj(attention output * sigmoid(x W_gate)), W_gate "
            "[hidden, heads * head_dim], on the normed input",
    "sandwich_norm": "a norm on each sublayer's input AND on its output, "
                     "inside the residual; 'depth-scaled' is the "
                     "initialisation of the gains, not an equation",
    "router": "float32 sigmoid scores; the selection bias enters the "
              "choice only; gates the unbiased scores over (their sum + "
              "1e-20) times route_scale; n_group = topk_group = 1: no "
              "group limit; load_balance_coeff and the bias's update are "
              "training's",
    "mup": "mup_enabled: the embedding times sqrt(hidden_size)",
    "norm_eps": "rms_norm_eps for every RMSNorm",
}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _round(a, round_to):
    return a if round_to is None else a.astype(round_to).astype(F32)


def _mm(a, b, round_to):
    return _round(a, round_to) @ _round(b, round_to)


def _in_blocks(fn, x, block: int):
    """``fn`` over x [s, ...] a block of rows at a time -> [s, ...]."""
    s = x.shape[0]
    if s <= block:
        return fn(x)
    pad = -s % block
    xb = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    out = jax.lax.map(fn, xb.reshape(-1, block, *x.shape[1:]))
    return out.reshape(-1, *out.shape[2:])[:s]


def _gated_mlp(h, w_in, w_out, round_to):
    gate, up = jnp.split(_mm(h, w_in, round_to), 2, axis=-1)
    return _mm(jax.nn.silu(gate) * up, w_out, round_to)


def _rope(x, pos, theta: float):
    """x [s, heads, hd] turned at positions ``pos`` [s]: pair j is lanes
    (j, j + hd / 2)."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None] * inv                   # [s, hd / 2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(c, ap, h, sliding: bool, round_to):
    s = h.shape[0]
    nh, nkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    eps = c["rms_norm_eps"]
    qkv = _mm(h, ap["wqkv"], round_to)
    q, k, v, gate = jnp.split(
        qkv, [nh * hd, (nh + nkv) * hd, (nh + 2 * nkv) * hd], axis=-1)
    q = _rms_norm(q.reshape(s, nh, hd), ap["q_norm"], eps)
    k = _rms_norm(k.reshape(s, nkv, hd), ap["k_norm"], eps)
    v = v.reshape(s, nkv, hd)
    pos = jnp.arange(s)
    if sliding:
        q = _rope(q, pos, c["rope_theta"])
        k = _rope(k, pos, c["rope_theta"])
    k, v = _round(k, round_to), _round(v, round_to)
    rep = nh // nkv

    def block(qp):
        qb, p = qp                            # [B, nh, hd], [B]
        qg = _round(qb, round_to).reshape(-1, nkv, rep, hd)
        att = jnp.einsum("bgrd,kgd->grbk", qg, k) / math.sqrt(hd)
        seen = pos[None, :] <= p[:, None]                   # [B, s]
        if sliding:
            seen &= pos[None, :] > p[:, None] - c["sliding_window"]
        att = jnp.where(seen[None, None], att, -jnp.inf)
        prob = _round(jax.nn.softmax(att, axis=-1), round_to)
        return jnp.einsum("grbk,kgd->bgrd", prob, v).reshape(-1, nh * hd)

    if s <= Q_BLOCK:
        o = block((q, pos))
    else:
        pad = -s % Q_BLOCK
        qb = jnp.pad(q, [(0, pad), (0, 0), (0, 0)]).reshape(
            -1, Q_BLOCK, nh, hd)
        pb = jnp.pad(pos, (0, pad)).reshape(-1, Q_BLOCK)
        o = jax.lax.map(block, (qb, pb)).reshape(-1, nh * hd)[:s]
    return _mm(o * jax.nn.sigmoid(gate), ap["wo"], round_to)


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
    weights = chosen / (chosen.sum(-1, keepdims=True) + 1e-20) \
        * c["route_scale"]
    # weight of expert e on each token, or 0
    dense = jnp.zeros((h.shape[0], c["num_experts"]), F32).at[
        jnp.arange(h.shape[0])[:, None], idx].set(weights)

    def tokens(hg):
        hb, gb = hg                           # [B, d], [B, E_held]

        def one(acc, e):
            w_in, w_out, g = e            # one expert's, as stored
            return acc + g[:, None] * _gated_mlp(
                hb, w_in.astype(F32), w_out.astype(F32), round_to), None

        routed, _ = jax.lax.scan(one, jnp.zeros_like(hb),
                                 (fp["w_in"], fp["w_out"], gb.T))
        return routed + _gated_mlp(hb, fp["shared_in"], fp["shared_out"],
                                   round_to)

    s = h.shape[0]
    if s <= T_BLOCK:
        return tokens((h, dense[:, lo:hi]))
    pad = -s % T_BLOCK
    hb = jnp.pad(h, [(0, pad), (0, 0)]).reshape(-1, T_BLOCK, h.shape[1])
    gb = jnp.pad(dense[:, lo:hi], [(0, pad), (0, 0)]).reshape(
        -1, T_BLOCK, hi - lo)
    return jax.lax.map(tokens, (hb, gb)).reshape(-1, h.shape[1])[:s]


@partial(jax.jit, static_argnames=("kind", "c", "held", "round_to"))
def _sublayer(lp, x, kind, c, held, round_to, forced=None):
    """One residual sublayer on x [s, d] (one sequence): ``kind`` is
    ``sliding_attention`` / ``full_attention`` / ``dense`` /
    ``experts``."""
    c = dict(c)
    eps = c["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        # (the experts' stacks are converted an expert at a time, where
        # they are used: 32 experts of 28 M parameters in float32 at once
        # would not fit beside the served weights)
        lp = {k: a if k in ("w_in", "w_out") and kind == "experts"
              else a.astype(F32) for k, a in lp.items()}
        h = _rms_norm(x, lp["norm"], eps)
        if kind == "experts":
            f = _experts(c, lp, h, held, round_to, forced)
        elif kind == "dense":
            f = _in_blocks(lambda hb: _gated_mlp(hb, lp["w_in"], lp["w_out"],
                                                 round_to), h, T_BLOCK)
        else:
            f = _attention(c, lp, h, kind == "sliding_attention", round_to)
        return x + _rms_norm(f, lp["post_norm"], eps)


@partial(jax.jit, static_argnames=("c", "round_to"))
def _routed_to(lp, x, c, round_to):
    """The experts an experts sublayer routes x [s, d] to, [s, k]."""
    c = dict(c)
    with jax.default_matmul_precision("highest"):
        lp = {k: lp[k].astype(F32) for k in ("norm", "router",
                                             "router_bias")}
        h = _rms_norm(x, lp["norm"], c["rms_norm_eps"])
        return _choice(c, lp, h, round_to)[1]


@partial(jax.jit, static_argnames=("c", "round_to"))
def _head(w_head, norm_f, x, c, round_to):
    c = dict(c)
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, norm_f.astype(F32), c["rms_norm_eps"])
        return _mm(h, w_head.astype(F32), round_to)


def _static(config: dict) -> tuple:
    """The published keys the layers read, hashable for ``jit``."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "num_experts", "num_experts_per_tok",
            "route_scale", "sliding_window", "rope_theta")
    return tuple((k, config[k]) for k in keys)


def sublayers(config: dict) -> list:
    """(layer index, slot, kind) of every residual sublayer, in order."""
    types = config["layer_types"][:config["num_hidden_layers"]]
    unknown = set(types) - {"sliding_attention", "full_attention"}
    if unknown:
        raise ValueError(f"layer_types {sorted(unknown)}: only sliding_"
                         f"attention and full_attention are written here")
    out = []
    for i, kind in enumerate(types):
        out.append((i, "mixer", kind))
        out.append((i, "ffn", "dense" if i < config["num_dense_layers"]
                    else "experts"))
    return out


def logits(params, tokens, config: dict, held: tuple, rows=None,
           round_to=None, forced=None, chosen=None):
    """tokens [s] int -> logits [s, V] float32 (``rows``: only those
    positions).  A Python loop over the sublayers, each converted to
    float32 on its own, so that it fits beside the served weights.

    ``chosen``: a list that receives, an experts sublayer, the experts
    each token was routed to; ``forced``: such a list from another run,
    routed to in place of this run's own choices.  Together they tell an
    error of the arithmetic from a router's tie that a rounding flipped
    (``precision_reading_afmoe.py``)."""
    c = _static(config)
    forced = iter(forced or ())
    scale = math.sqrt(config["hidden_size"]) if config.get("mup_enabled") \
        else 1.0
    x = params["wte"][jnp.asarray(tokens)].astype(F32) * scale
    for i, slot, kind in sublayers(config):
        lp = params["layers"][i][slot]
        to = next(forced, None) if kind == "experts" else None
        if kind == "experts" and chosen is not None:
            chosen.append(to if to is not None
                          else _routed_to(lp, x, c, round_to))
        x = _sublayer(lp, x, kind, c, tuple(held), round_to, to)
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
