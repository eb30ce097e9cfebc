"""The ``lfm2_moe`` forward pass (Liquid AI LFM2-8B-A1B) in plain
``jax.numpy``: the oracle.

Written from the published configuration's keys and the catalog row's
description of the family.  For one sequence of ``s`` tokens:

    x   = wte[ids]                              (no multiplier, no position table)
    layer l:  h = RMSNorm(x) * w_op             (eps norm_eps)
      layer_types[l] == "conv":
              [B | C | u] = h W_in              (W_in [d, 3 d], no bias)
              v_t = B_t * u_t                   (elementwise)
              c_t = sum_{j=0..L-1} k[j] * v_{t-(L-1)+j}   (depthwise causal,
                       L = conv_L_cache, no bias, NO activation; v_t = 0, t < 0)
              y_t = (C_t * c_t) W_out           (W_out [d, d])
      "full_attention":
              q, k, v = h W_q, h W_k, h W_v     (heads / kv heads of hd, no bias)
              q, k = RMSNorm_hd(q) * w_q, RMSNorm_hd(k) * w_k   (one weight [hd])
              q, k = rope(q, pos), rope(k, pos) (rope_theta, ALL hd lanes,
                       half-split pairs (j, j + hd / 2), no scaling)
              y = softmax(q k^T / sqrt(hd), causal) v W_o   (float32; query head
                       h reads K/V head h // (heads / kv_heads))
      x = x + y ;  m = RMSNorm(x) * w_ffn
      l < num_dense_layers:  x = x + W_2 (silu(m W_1) * (m W_3))   (intermediate_size)
      else:   s = sigmoid(float32(m W_r))       (num_experts outputs)
              chosen = the k largest of s + bias ;  g = s[chosen]
              g = g / (sum g + 1e-6)            (norm_topk_prob)
              g = g * routed_scaling_factor
              x = x + sum_{e in chosen} g_e W_2e (silu(m W_1e) * (m W_3e))
                       (moe_intermediate_size; NO shared expert)
    logits = (RMSNorm(x) * w_f) wte^T           (tied)

The share: ``held = (lo, hi)`` names the experts whose weights ``ffn.w_in
/ w_out`` hold; the router keeps all ``num_experts`` outputs and its
top-k, and what absent experts would add is left out.  The vocabulary is
whatever ``wte`` holds.  ``config["layer_types"]`` lists the layers this
parameter set holds, in order (its first ``num_hidden_layers`` entries);
the first ``num_dense_layers`` of them have the dense MLP.

float32 throughout, matrix products at the ``highest`` precision, no
kernel, no cache, no state, no batching.  Computed in BLOCKS so that
4,608 tokens at the published widths fit beside the served weights:
attention ``Q_BLOCK`` queries at a time over all the keys, the MLPs and
experts ``T_BLOCK`` tokens at a time, the experts DENSELY (every held
expert on every token, times a weight that is 0 where the token did not
choose it).  It imports nothing from ``ray_tpu``.  Parameters arrive as
the plain dict the system under test holds them in (that layout is data,
not code): ``wte [V, d]``, ``norm_f [d]`` and ``layers``, one
``{"mixer", "ffn"}`` a layer.  A conv ``mixer``: ``norm`` [d],
``in_proj [d, 3 d]`` (B | C | u), ``conv_w [L, d]`` (tap L - 1
multiplies the current token), ``out_proj [d, d]``; an attention one:
``norm``, ``wqkv [d, (h + 2 hkv) hd]``, ``q_norm``, ``k_norm`` [hd],
``wo [h hd, d]``.  A dense ``ffn``: ``norm``, ``w_in [d, 2 f]`` (gate |
up), ``w_out [f, d]``; an experts ``ffn``: ``norm``, ``router [d, E]``,
``router_bias [E]``, ``w_in [E_held, d, 2 we]``, ``w_out [E_held, we,
d]``.

``ASSUMED`` lists what the catalog's row does not carry and this file
infers.  ``round_to`` (a dtype) rounds every matrix product's two inputs
to that dtype first: the same mathematics in a LOWER precision, which
the serving check must be tight enough to tell from the stated one.
``round_state`` rounds only what a serving cache would KEEP of a
convolution layer — the past inputs ``v_{t-1} .. v_{t-L+1}`` its taps
read, never the current one — with every product in float32: a state
(and its snapshots) held in that dtype.
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
    "tied_embedding": "the head is wte^T: 18 conv + 6 attention mixers, 2 "
                      "dense and 22 expert layers and ONE 65,536 x 2,048 "
                      "matrix are 8.34 B parameters, the published '8.3B'; "
                      "a second matrix would make 8.47 B",
    "head_dim": "hidden_size / num_attention_heads = 64 (the catalog's "
                "head_dim is null)",
    "gate_eps": "1e-6 added to the chosen scores' sum before the division",
    "rotary": "on every full_attention layer, all head_dim lanes, "
              "half-split pairs, theta rope_theta, no scaling",
    "qk_norm": "RMSNorm over each head's head_dim lanes, one weight "
               "[head_dim] for q and one for k, before the rotation",
    "norms": "pre-norm sublayers (operator_norm, ffn_norm) and a final "
             "norm before the head, eps norm_eps; no norm on an output",
    "convolution": "depthwise, causal, conv_L_cache taps, no bias "
                   "(conv_bias false), NO activation; the gates B and C "
                   "are plain products, no sigmoid",
    "router": "float32 sigmoid scores; the selection bias (use_expert_"
              "bias) enters the choice only; gates the unbiased scores, "
              "normalised (norm_topk_prob) and times routed_scaling_"
              "factor; no shared expert",
    "attention_scale": "1 / sqrt(head_dim)",
}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _round(a, round_to):
    if round_to is None:
        return a
    if round_to == jnp.float8_e4m3fn:       # e4m3 has no infinity: 448
        a = jnp.clip(a, -448.0, 448.0)      # is its largest, not a NaN
    return a.astype(round_to).astype(F32)


def _round_kept(a, round_to):
    """``_round`` for a value no product consumes: through
    ``reduce_precision``, which the compiler keeps where it drops a pair
    of converts from an elementwise chain (the first reading of this
    control was 0.0 at every position: my chip run, PR 52, call 3; the
    olmo reference met the same)."""
    if round_to is None:
        return a
    info = jnp.finfo(round_to)
    return jax.lax.reduce_precision(
        jnp.clip(a, -float(info.max), float(info.max)), info.nexp,
        info.nmant)


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


def _conv(c, mp, h, round_to, round_state):
    s, L = h.shape[0], c["conv_L_cache"]
    B, C, u = jnp.split(_in_blocks(
        lambda hb: _mm(hb, mp["in_proj"], round_to), h, T_BLOCK), 3, axis=-1)
    v = B * u
    kept = _round_kept(v, round_state)  # what a cache holds of the past
    out = mp["conv_w"][L - 1] * v
    for back in range(1, L):            # tap L - 1 - back reads v_{t-back}
        out = out + mp["conv_w"][L - 1 - back] * jnp.pad(
            kept, [(back, 0), (0, 0)])[:s]
    return _in_blocks(lambda yb: _mm(yb, mp["out_proj"], round_to), C * out,
                      T_BLOCK)


def _attention(c, ap, h, round_to):
    s = h.shape[0]
    nh, nkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    eps = c["norm_eps"]
    q, k, v = jnp.split(_mm(h, ap["wqkv"], round_to),
                        [nh * hd, (nh + nkv) * hd], axis=-1)
    pos = jnp.arange(s)
    q = _rope(_rms_norm(q.reshape(s, nh, hd), ap["q_norm"], eps), pos,
              c["rope_theta"])
    k = _rope(_rms_norm(k.reshape(s, nkv, hd), ap["k_norm"], eps), pos,
              c["rope_theta"])
    k, v = _round(k, round_to), _round(v.reshape(s, nkv, hd), round_to)
    rep = nh // nkv

    def block(qp):
        qb, p = qp                            # [B, nh, hd], [B]
        qg = _round(qb, round_to).reshape(-1, nkv, rep, hd)
        att = jnp.einsum("bgrd,kgd->grbk", qg, k) / math.sqrt(hd)
        att = jnp.where((pos[None, :] <= p[:, None])[None, None], att,
                        -jnp.inf)
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
    return _mm(o, ap["wo"], round_to)


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
    weights = chosen / (chosen.sum(-1, keepdims=True) + 1e-6) \
        * c["routed_scaling_factor"]
    # weight of expert e on each token, or 0
    dense = jnp.zeros((h.shape[0], c["num_experts"]), F32).at[
        jnp.arange(h.shape[0])[:, None], idx].set(weights)

    def tokens(hg):
        hb, gb = hg                           # [B, d], [B, E_held]

        def one(acc, e):
            w_in, w_out, g = e            # one expert's, as stored
            return acc + g[:, None] * _gated_mlp(
                hb, w_in.astype(F32), w_out.astype(F32), round_to), None

        return jax.lax.scan(one, jnp.zeros_like(hb),
                            (fp["w_in"], fp["w_out"], gb.T))[0]

    s = h.shape[0]
    if s <= T_BLOCK:
        return tokens((h, dense[:, lo:hi]))
    pad = -s % T_BLOCK
    hb = jnp.pad(h, [(0, pad), (0, 0)]).reshape(-1, T_BLOCK, h.shape[1])
    gb = jnp.pad(dense[:, lo:hi], [(0, pad), (0, 0)]).reshape(
        -1, T_BLOCK, hi - lo)
    return jax.lax.map(tokens, (hb, gb)).reshape(-1, h.shape[1])[:s]


@partial(jax.jit, static_argnames=("kind", "c", "held", "round_to",
                                   "round_state"))
def _sublayer(lp, x, kind, c, held, round_to, round_state=None,
              forced=None):
    """One residual sublayer on x [s, d] (one sequence): ``kind`` is
    ``conv`` / ``full_attention`` / ``dense`` / ``experts``."""
    c = dict(c)
    with jax.default_matmul_precision("highest"):
        # (the experts' stacks are converted an expert at a time, where
        # they are used: 32 experts of 11 M parameters in float32 at once
        # would crowd the served weights)
        lp = {k: a if k in ("w_in", "w_out") and kind == "experts"
              else a.astype(F32) for k, a in lp.items()}
        h = _rms_norm(x, lp["norm"], c["norm_eps"])
        if kind == "experts":
            f = _experts(c, lp, h, held, round_to, forced)
        elif kind == "dense":
            f = _in_blocks(lambda hb: _gated_mlp(hb, lp["w_in"], lp["w_out"],
                                                 round_to), h, T_BLOCK)
        elif kind == "conv":
            f = _conv(c, lp, h, round_to, round_state)
        else:
            f = _attention(c, lp, h, round_to)
        return x + f


@partial(jax.jit, static_argnames=("c", "round_to"))
def _routed_to(lp, x, c, round_to):
    """The experts an experts sublayer routes x [s, d] to, [s, k]."""
    c = dict(c)
    with jax.default_matmul_precision("highest"):
        lp = {k: lp[k].astype(F32) for k in ("norm", "router",
                                             "router_bias")}
        h = _rms_norm(x, lp["norm"], c["norm_eps"])
        return _choice(c, lp, h, round_to)[1]


@partial(jax.jit, static_argnames=("c", "round_to"))
def _head(wte, norm_f, x, c, round_to):
    c = dict(c)
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, norm_f.astype(F32), c["norm_eps"])
        return _mm(h, wte.astype(F32).T, round_to)


def _static(config: dict) -> tuple:
    """The published keys the layers read, hashable for ``jit``."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "norm_eps", "num_experts", "num_experts_per_tok",
            "routed_scaling_factor", "rope_theta", "conv_L_cache")
    head_dim = config.get("head_dim") or (
        config["hidden_size"] // config["num_attention_heads"])
    return tuple((k, config[k]) for k in keys) + (("head_dim", head_dim),)


def sublayers(config: dict) -> list:
    """(layer index, slot, kind) of every residual sublayer, in order."""
    types = config["layer_types"][:config["num_hidden_layers"]]
    unknown = set(types) - {"conv", "full_attention"}
    if unknown:
        raise ValueError(f"layer_types {sorted(unknown)}: only conv and "
                         f"full_attention are written here")
    out = []
    for i, kind in enumerate(types):
        out.append((i, "mixer", kind))
        out.append((i, "ffn", "dense" if i < config["num_dense_layers"]
                    else "experts"))
    return out


def logits(params, tokens, config: dict, held: tuple, rows=None,
           round_to=None, round_state=None, forced=None, chosen=None):
    """tokens [s] int -> logits [s, V] float32 (``rows``: only those
    positions).  A Python loop over the sublayers, each converted to
    float32 on its own, so that it fits beside the served weights.

    ``chosen``: a list that receives, an experts sublayer, the experts
    each token was routed to; ``forced``: such a list from another run,
    routed to in place of this run's own choices.  Together they tell an
    error of the arithmetic from a router's tie that a rounding flipped
    (``precision_reading_lfm2.py``)."""
    c = _static(config)
    forced = iter(forced or ())
    x = params["wte"][jnp.asarray(tokens)].astype(F32)
    for i, slot, kind in sublayers(config):
        lp = params["layers"][i][slot]
        to = next(forced, None) if kind == "experts" else None
        if kind == "experts" and chosen is not None:
            chosen.append(to if to is not None
                          else _routed_to(lp, x, c, round_to))
        x = _sublayer(lp, x, kind, c, tuple(held), round_to, round_state, to)
    if rows is not None:
        x = x[rows]
    return _head(params["wte"], params["norm_f"], x, c, round_to)


def margins(params, prompt, emitted, config: dict, held: tuple, width: int,
            round_to=None, round_state=None):
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
                             round_to=round_to, round_state=round_state)
                      )[:len(emitted)]
    chosen = step[np.arange(len(emitted)), np.asarray(emitted)]
    return step.max(-1) - chosen, step.argmax(-1)
