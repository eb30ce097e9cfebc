"""The ``xing4_0`` forward pass in plain ``jax.numpy``: the oracle.

Written from the published configuration's keys (``https://huggingface.
co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json``) and the public
descriptions of what they name: latent attention and the experts' router
as ``modeling_deepseek.py`` has them (arXiv:2405.04434, 2412.19437),
the residual of ``hc_mult`` streams as manifold-constrained
hyper-connections state it (arXiv:2512.24880).  With ``n = hc_mult``,
``C = hidden_size``, a token's streams ``X`` in ``R^{n x C}`` and ``F`` a
sublayer with its own input RMSNorm:

    X_0    = [wte[id]] repeated n times                       (ASSUMED)
    v      = RMSNorm_{nC}(vec(X)) * w_hc     one norm over all n C lanes,
                                             eps ``hc_eps``   (ASSUMED)
    A_pre  = alpha_pre  * (v Phi_pre)  + b_pre                  in R^n
    A_post = alpha_post * (v Phi_post) + b_post                 in R^n
    A_res  = alpha_res  * mat_{n x n}(v Phi_res) + b_res, clamped to
             [mhc_h_res_clamp_min, mhc_h_res_clamp_max]
    H_pre  = sigmoid(A_pre);   H_post = 2 sigmoid(A_post)
    H_res  = SK(exp(A_res)): ``hc_sinkhorn_iters`` times (every row to
             sum 1, then every column to sum 1)
    X'     = H_res X + H_post^T F(RMSNorm(H_pre X) w)
    logits = (RMSNorm(sum of the n streams of X_last) w_f) W_head
                                                              (ASSUMED)

every sublayer with maps of its own (``Phi`` = ``[n + n + n n, n C]``,
held maps-major; ``vec`` is stream-major: stream 0's C lanes first).

  * ``F`` of a layer's first sublayer: ``deepseek_v2``'s latent
    attention at this configuration's numbers — the function of
    ``reference/deepseek_v2.py`` itself, imported (its text states the
    equations; YaRN at ``factor`` 64 with ``mscale`` = ``mscale_all_dim``
    = 1, softmax scale ``(128 + 64)^-0.5 (0.1 ln 64 + 1)^2``).
  * ``F`` of its second, for l < ``first_k_dense_replace``: the gated MLP
    of width ``intermediate_size``, ``W_down (silu(W_gate h) * W_up h)``.
  * else experts: ``s = sigmoid(h W_r)`` over all ``n_routed_experts`` in
    float32; the ``num_experts_per_tok`` largest of ``s +
    e_score_correction_bias`` are chosen (``noaux_tc`` with ``n_group``
    1: no group limit); gates = the UNBIASED ``s`` of the chosen over
    (their sum + 1e-20) (``norm_topk_prob``) times
    ``routed_scaling_factor``; expert e is the gated MLP of width
    ``moe_intermediate_size``; plus ONE gated shared expert of width
    ``n_shared_experts x moe_intermediate_size``, ungated by the router.
    Computed DENSELY: every held expert on every token, times a gate
    that is 0 where the token did not choose it.
  * the multi-token-prediction module k (``num_nextn_predict_layers``;
    DeepSeek-V3's form): ``h' = [RMSNorm_e(wte[id_{t+k}]) ; RMSNorm_h(
    h_t)] W_eh`` (``W_eh`` ``[2 C, C]``; ``h_t`` the summed streams the
    module before it — the model, for k = 1 — left at position t, BEFORE
    any final norm), repeated n times, one more whole layer (latent
    attention, then experts, maps of its own) over the ``s - k``
    positions, then ``(RMSNorm(sum of the streams) w_k) W_head`` with the
    SAME head: position t's guess at token t + k + 1.

float32 throughout, matrix products at the ``highest`` precision, no
kernel, no cache.  It imports nothing from ``ray_tpu``.  Parameters
arrive as the plain dict the system under test holds them in (that
layout is data, not code): as ``reference/deepseek_v2.py`` lists them,
an experts ``ffn`` with ONE more array, ``router_bias [E]`` float32, and
every sublayer's dict with ``hc``: ``w [n C]``, ``phi [n + n + n n, n
C]``, ``alpha [3]`` and ``b [n + n + n n]`` (pre | post | res, the
matrix row-major: entry (i, j) weighs stream j in new stream i); where
the model has prediction modules, ``mtp``: a list of ``{"enorm",
"hnorm", "eh_proj" [2 C, C], "mixer", "ffn", "norm"}``.

``ASSUMED`` lists what the configuration does not say and this file
infers.  ``round_to`` (a dtype) rounds every matrix product's two inputs
to that dtype first (the maps' product with ``Phi`` included): the same
mathematics in a LOWER precision, which the serving check must be tight
enough to tell from the stated one.  ``round_maps_to`` computes only the
three maps' arithmetic — the norm over n C lanes, the product with
``Phi``, the exponentials and every Sinkhorn-Knopp iteration — in that
dtype, each result rounded to it: the second control.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from chipbench.reference.deepseek_v2 import (F32, MAX_EMITTED,  # noqa: F401
                                             _attention, _gated_mlp, _mm,
                                             _rms_norm, _round)

ASSUMED = {
    "stream_entry": "X_0: the token's embedding repeated hc_mult times "
                    "(every stream starts as the embedding)",
    "stream_exit": "the n streams are SUMMED, then the final RMSNorm "
                   "(norm_f) and the head; a prediction module reads the "
                   "same sum before any final norm",
    "map_norm": "ONE RMSNorm over all n C lanes of vec(X) (stream-major), "
                "with a weight [n C] and eps hc_eps; hc_eps enters nowhere "
                "else (the Sinkhorn-Knopp divisions have no eps: exp() of "
                "a clamped entry is positive)",
    "map_order": "norm, product with Phi, times alpha, plus b, the clamp "
                 "(residual map only), then sigmoid / 2 sigmoid / exp and "
                 "the iterations; rows are normalised before columns in "
                 "every iteration, so H_res's columns sum to 1 exactly and "
                 "its rows to 1 within the iterations' own convergence",
    "map_direction": "new stream i = sum_j H_res[i, j] stream j + "
                     "H_post[i] F(sum_j H_pre[j] stream j)",
    "sublayer_norm": "F's own input RMSNorm (rms_norm_eps) is applied to "
                     "the mixed stream H_pre X, after the mix",
    "seeded_maps": "alpha 1, b 0, Phi N(0, 0.02): v Phi has a standard "
                   "deviation of 0.02 sqrt(n C) = 2.4 at 4 x 3,584, well "
                   "inside the clamp of +-30 and of order 1 for all three "
                   "maps, so a wrong projection or a missing iteration "
                   "moves the logits",
    "router": "float32 sigmoid scores; the selection bias "
              "(e_score_correction_bias) only chooses; 1e-20 added to the "
              "chosen scores' sum (the published implementation's own "
              "constant)",
    "shared_experts": "one gated MLP of width n_shared_experts x "
                      "moe_intermediate_size, no gate on its output",
    "mtp": "DeepSeek-V3's module: the embedding's norm FIRST in the "
           "concatenation; the module's layer is an experts layer; its "
           "output norm is its own, embedding and head are the model's",
    "rotary_pairs": "half-split, as reference/deepseek_v2.py",
    "unread": "ep_size, moe_layer_freq 1, num_key_value_heads (latent "
              "attention has one cached head), hidden_act (silu is the only "
              "form written), attention_bias false",
}


def sinkhorn(m, iters: int, round_to=None):
    """m [.., n, n] positive -> ``iters`` times: rows to sum 1, then
    columns."""
    for _ in range(iters):
        m = _round(m / m.sum(-1, keepdims=True), round_to)
        m = _round(m / m.sum(-2, keepdims=True), round_to)
    return m


def maps(c: dict, hp, X, round_to=None, round_maps_to=None):
    """X [s, n, C] -> (H_pre [s, n], H_post [s, n], H_res [s, n, n])."""
    n = c["hc_mult"]
    low = round_maps_to
    flat = _round(X.reshape(X.shape[0], -1), low)
    ms = _round(jnp.mean(flat * flat, -1, keepdims=True), low)
    v = _round(_round(flat * jax.lax.rsqrt(ms + c["hc_eps"]), low)
               * hp["w"], low)
    a = _round(_mm(v, hp["phi"].T, low or round_to), low)
    pre, post, res = a[:, :n], a[:, n:2 * n], a[:, 2 * n:]
    alpha, b = hp["alpha"], hp["b"]
    h_pre = jax.nn.sigmoid(_round(alpha[0] * pre + b[:n], low))
    h_post = 2.0 * jax.nn.sigmoid(_round(alpha[1] * post + b[n:2 * n], low))
    a_res = jnp.clip(_round(alpha[2] * res + b[2 * n:], low),
                     c["mhc_h_res_clamp_min"], c["mhc_h_res_clamp_max"])
    h_res = sinkhorn(_round(jnp.exp(a_res), low).reshape(-1, n, n),
                     c["hc_sinkhorn_iters"], low)
    return _round(h_pre, low), _round(h_post, low), h_res


def _seen(c, sp, X, round_to, round_maps_to):
    """-> (what a sublayer's ``F`` is handed, ``RMSNorm(H_pre X) w`` [s,
    C]; H_post [s, n]; H_res [s, n, n]); X [s, n, C]."""
    h_pre, h_post, h_res = maps(c, sp["hc"], X, round_to, round_maps_to)
    mixed = jnp.einsum("sn,snc->sc", h_pre, X)
    return (_rms_norm(mixed, sp["norm"], c["rms_norm_eps"]), h_post, h_res)


def _sublayer(c, sp, X, F, round_to, round_maps_to):
    """``X' = H_res X + H_post^T F(RMSNorm(H_pre X) w)``; X [s, n, C]."""
    h, h_post, h_res = _seen(c, sp, X, round_to, round_maps_to)
    return (jnp.einsum("sij,sjc->sic", h_res, X)
            + h_post[:, :, None] * F(h)[:, None, :])


def _choice(c, fp, h, round_to):
    """-> (scores [s, E] float32, the k experts a token is routed to)."""
    scores = jax.nn.sigmoid(_mm(h, fp["router"], round_to))
    _, idx = jax.lax.top_k(scores + fp["router_bias"],
                           c["num_experts_per_tok"])
    return scores, idx


def _experts(c, fp, h, held, round_to, forced=None):
    """``forced`` [s, k]: experts to route to in place of the layer's
    own choice."""
    lo, hi = held
    scores, idx = _choice(c, fp, h, round_to)
    if forced is not None:
        idx = forced
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    gates = chosen / (chosen.sum(-1, keepdims=True) + 1e-20) \
        * c["routed_scaling_factor"]
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


def _f32(tree):
    # the stacked experts are converted one at a time, where used
    return jax.tree.map(lambda a: a if a.ndim == 3 else a.astype(F32), tree)


@partial(jax.jit, static_argnames=("c", "held", "round_to",
                                   "round_maps_to"))
def _layer(lp, X, c, held, round_to, round_maps_to=None, forced=None):
    """One published layer on X [s, n, C] (one sequence)."""
    c = _dict(c)
    with jax.default_matmul_precision("highest"):
        ap = jax.tree.map(lambda a: a.astype(F32), lp["mixer"])
        fp = _f32(lp["ffn"])
        X = _sublayer(c, ap, X, lambda h: _attention(c, ap, h, round_to),
                      round_to, round_maps_to)
        if "router" in fp:
            return _sublayer(
                c, fp, X, lambda h: _experts(c, fp, h, held, round_to,
                                             forced),
                round_to, round_maps_to)
        return _sublayer(
            c, fp, X, lambda h: _gated_mlp(h, fp["w_in"], fp["w_out"],
                                           round_to),
            round_to, round_maps_to)


@partial(jax.jit, static_argnames=("c", "round_to", "round_maps_to"))
def _routed_to(lp, X, c, round_to, round_maps_to=None):
    """The experts a layer's second sublayer routes to, [s, k], given
    the layer's INPUT X [s, n, C]."""
    c = _dict(c)
    with jax.default_matmul_precision("highest"):
        ap = jax.tree.map(lambda a: a.astype(F32), lp["mixer"])
        fp = {k: jax.tree.map(lambda a: a.astype(F32), lp["ffn"][k])
              for k in ("norm", "router", "router_bias", "hc")}
        X = _sublayer(c, ap, X, lambda h: _attention(c, ap, h, round_to),
                      round_to, round_maps_to)
        return _choice(c, fp, _seen(c, fp, X, round_to, round_maps_to)[0],
                       round_to)[1]


@partial(jax.jit, static_argnames=("c", "round_to"))
def _head(w_head, norm, X, c, round_to):
    """X [s, n, C] -> logits: the streams' sum, a norm, the head."""
    c = _dict(c)
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(X.sum(1), norm.astype(F32), c["rms_norm_eps"])
        return _mm(h, w_head.astype(F32), round_to)


@partial(jax.jit, static_argnames=("c", "round_to"))
def _mtp_entry(wte_rows, mp, X, c, round_to):
    """[RMSNorm_e(e) ; RMSNorm_h(sum of X's streams)] W_eh, n times."""
    c = _dict(c)
    eps = c["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        both = jnp.concatenate(
            [_rms_norm(wte_rows.astype(F32), mp["enorm"].astype(F32), eps),
             _rms_norm(X.sum(1), mp["hnorm"].astype(F32), eps)], axis=-1)
        h = _mm(both, mp["eh_proj"].astype(F32), round_to)
        return jnp.broadcast_to(h[:, None], (h.shape[0], c["hc_mult"],
                                             h.shape[1]))


KEYS = ("hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rms_norm_eps",
        "rope_theta", "n_routed_experts", "num_experts_per_tok",
        "routed_scaling_factor", "hc_mult", "hc_sinkhorn_iters", "hc_eps",
        "mhc_h_res_clamp_min", "mhc_h_res_clamp_max")


def _static(config: dict) -> tuple:
    """The published keys the layers read, hashable for ``jit``."""
    return tuple((k, config[k]) for k in KEYS) + (
        ("rope_scaling", tuple(sorted(config["rope_scaling"].items()))),)


def _dict(c: tuple) -> dict:
    d = dict(c)
    d["rope_scaling"] = dict(d["rope_scaling"])
    return d


def logits(params, tokens, config: dict, held: tuple, rows=None,
           round_to=None, round_maps_to=None, forced=None, chosen=None,
           mtp: bool = False):
    """tokens [s] int -> logits [s, V] float32 (``rows``: only those
    positions).  A Python loop over the layers, each converted to
    float32 on its own, so that it fits beside the served weights.

    ``chosen`` / ``forced``: as ``reference/deepseek_v2.logits`` takes
    them.  ``mtp``: -> (logits, [module k's logits [s - k, V]]), with
    no ``rows``."""
    if (config["scoring_func"], config["topk_method"], config["n_group"],
            config["norm_topk_prob"], config["rope_scaling"]["type"]) != (
            "sigmoid", "noaux_tc", 1, True, "yarn"):
        raise ValueError("only sigmoid scores chosen by score + bias in one "
                         "group, normalised gates and yarn rotary are "
                         "written here")
    c = _static(config)
    forced = iter(forced or ())
    tokens = jnp.asarray(tokens)
    x = params["wte"][tokens].astype(F32)
    X = jnp.broadcast_to(x[:, None], (x.shape[0], config["hc_mult"],
                                      x.shape[1]))
    for lp in params["layers"]:
        routed = "router" in lp["ffn"]
        to = next(forced, None) if routed else None
        if routed and chosen is not None:
            chosen.append(to if to is not None else _routed_to(
                lp, X, c, round_to, round_maps_to))
        X = _layer(lp, X, c, tuple(held), round_to, round_maps_to, to)
    out = _head(params["head"], params["norm_f"],
                X if rows is None else X[rows], c, round_to)
    if not mtp:
        return out
    more, s = [], tokens.shape[0]
    for k, mp in enumerate(params["mtp"], 1):
        X = _mtp_entry(params["wte"][tokens[k:]], mp, X[:s - k], c, round_to)
        X = _layer({"mixer": mp["mixer"], "ffn": mp["ffn"]}, X, c,
                   tuple(held), round_to, round_maps_to)
        more.append(_head(params["head"], mp["norm"], X, c, round_to))
    return out, more


def margins(params, prompt, emitted, config: dict, held: tuple, width: int,
            round_to=None):
    """Teacher-forced check of one served request, as ``reference/
    deepseek_v2.margins``: for each emitted token, how far its logit
    lies below that position's maximum.
    -> (margins [len(emitted)], argmax tokens [len(emitted)])."""
    import numpy as np
    seq = np.zeros((width,), np.int32)
    n_p, n = len(prompt), len(prompt) + len(emitted)
    seq[:n_p], seq[n_p:n] = prompt, emitted
    if len(emitted) > MAX_EMITTED:
        raise ValueError(f"{len(emitted)} emitted tokens > {MAX_EMITTED}")
    rows = np.minimum(np.arange(n_p - 1, n_p - 1 + MAX_EMITTED), width - 1)
    step = np.asarray(logits(params, seq, config, held, rows=rows,
                             round_to=round_to))[:len(emitted)]
    chosen = step[np.arange(len(emitted)), np.asarray(emitted)]
    return step.max(-1) - chosen, step.argmax(-1)
