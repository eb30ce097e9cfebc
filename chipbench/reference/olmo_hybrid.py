"""The ``olmo_hybrid`` forward pass in plain ``jax.numpy``: the oracle.

Written from the published configuration's keys, the Gated DeltaNet
layer those keys are named after (arXiv:2412.06464, as the
flash-linear-attention library writes it) and the Olmo 2 / Olmo 3
family's published block (arXiv:2501.00656): token embedding, no
multiplier and no position embedding; every layer

    x = x + RMSNorm(mixer(x)) * w            the norm on the OUTPUT
    x = x + RMSNorm(W_down(silu(W_gate x) * W_up x)) * w

and logits ``(RMSNorm(x) * w_f) @ W_head`` with an untied head ``[d,
V]``.  The mixer by the layer's entry in ``layer_types``:

  * ``linear_attention`` (H = ``linear_num_value_heads`` heads, K =
    ``linear_key_head_dim``, V = ``linear_value_head_dim``)::

        [q~ | k~ | v~] = x [W_q | W_k | W_v]
        [q | k | v]    = silu(causal_conv(q~ | k~ | v~))   depthwise,
                         ``linear_conv_kernel_dim`` taps, no bias
        q_h = q_h / |q_h| / sqrt(K);   k_h = k_h / |k_h|
        beta_h  = 2 sigmoid(x w_b,h)
        alpha_h = exp(-exp(A_log_h) softplus(x w_a,h + dt_bias_h))
        S_h <- alpha_h S_h (I - beta_h k_h k_h^T) + beta_h v_h k_h^T
        o_h = S_h q_h
        y   = W_o concat_h(RMSNorm_V(o_h) * w_norm * silu((x W_g)_h))

    with ``S_h`` float32 ``[V, K]`` from zero — computed here TOKEN BY
    TOKEN (a ``lax.scan``), the definition, not a chunked form.
  * ``full_attention``: ``num_attention_heads`` query heads over
    ``num_key_value_heads`` K/V heads of ``hidden_size /
    num_attention_heads`` lanes, no bias, NO rotary; RMSNorm over the
    WHOLE query projection and over the whole key projection before the
    heads are split; ``softmax(q k^T / sqrt(head_dim), causal) v``;
    output projection.  Computed a block of ``QUERY_BLOCK`` queries at a
    time, so that no score array has more than one block's rows.

float32 throughout, matrix products at the ``highest`` precision, no
kernel, no cache, no batching.  It imports nothing from ``ray_tpu``.
Parameters arrive as the plain dict the system under test holds them in
(that layout is data, not code): ``wte [V, d]``, ``norm_f [d]``, ``head
[d, V]`` and ``layers``, a list of ``{"mixer": ..., "ffn": ...}``.  A
linear ``mixer``: ``norm`` (of the sublayer's output), ``wqkv [d, H (2K
+ V)]`` q, k, v side by side and heads major, ``wg [d, H V]``, ``wab
[d, 2H]`` a then b, ``conv_w [taps, H (2K + V)]`` with the last tap on
the current token, ``dt_bias``, ``A_log`` [H], ``gnorm [V]``, ``wo [H
V, d]``; an attention ``mixer``: ``norm``, ``wqkv [d, (h + 2 hkv)
hd]``, ``q_norm [h hd]``, ``k_norm [hkv hd]``, ``wo``.  ``ffn``:
``norm``, ``w_in [d, 2 w]`` gate then up, ``w_out [w, d]``.

``ASSUMED`` lists what the configuration does not say and this file
takes from the family's convention.  ``round_to`` (a dtype) rounds every
matrix product's two inputs to that dtype first: the same mathematics in
a LOWER precision, which the serving check must be tight enough to tell
from the stated one.  ``state_round_to`` rounds the matrix state to that
dtype after every token: a state kept in a lower precision than the
configuration's float32.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
MAX_EMITTED = 512      # margins() scores at most this many tokens a request
QUERY_BLOCK = 512      # queries a step of the attention takes

ASSUMED = {
    "norm_place": "the RMSNorm of a sublayer is on its OUTPUT, x + "
                  "norm(f(x)) (Olmo 2 / Olmo 3, arXiv:2501.00656); the "
                  "final norm before the head is on the stream",
    "qk_norm": "full attention: RMSNorm with a weight over the whole q "
               "projection and over the whole k projection, before the "
               "heads are split (the same family)",
    "rotary": "none: rope_parameters.rope_theta is null",
    "attention_scale": "1 / sqrt(hidden_size / num_attention_heads)",
    "norm_eps": "rms_norm_eps for every RMSNorm, the gated one too; the "
                "unit-length scaling of q and k adds 1e-6 to the sum of "
                "squares",
    "conv": "depthwise over [q | k | v], oldest tap first, NO bias, silu "
            "after (the layer's reference implementation)",
    "gated_norm": "norm first, RMSNorm over each head's values with one "
                  "weight [V], then times silu(gate)",
    "beta": "2 sigmoid(.): linear_allow_neg_eigval true",
    "decay": "alpha = exp(-exp(A_log) softplus(x w_a + dt_bias)), a "
             "number a head and token",
    "query_scale": "1 / sqrt(linear_key_head_dim) after the unit-length "
                   "scaling",
    "mlp": "W_down(silu(W_gate x) * W_up x), no bias (hidden_act silu)",
}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _round(x, dtype):
    """x rounded to ``dtype`` and back, SATURATING at its largest finite
    number (float8 e4m3 has no infinity: an overflow would be a NaN, and
    the control would read a NaN's argmax, not a rounding).  A 16-bit
    type goes through ``reduce_precision``: a pair of converts is what
    the compiler is free to drop from an elementwise chain (on the chip
    it dropped the matrix state's, and the control read 0.0)."""
    info = jnp.finfo(dtype)
    x = jnp.clip(x, -float(info.max), float(info.max))
    if info.bits >= 16:
        return jax.lax.reduce_precision(x, info.nexp, info.nmant)
    return x.astype(dtype).astype(F32)


def _mm(a, b, round_to):
    if round_to is not None:
        a, b = _round(a, round_to), _round(b, round_to)
    return a @ b


def _attention(c, ap, x, round_to):
    s, _ = x.shape
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["hidden_size"] // nh
    qkv = _mm(x, ap["wqkv"], round_to)
    q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], axis=-1)
    q = _rms_norm(q, ap["q_norm"], c["rms_norm_eps"])
    k = _rms_norm(k, ap["k_norm"], c["rms_norm_eps"])
    k = jnp.repeat(k.reshape(s, nkv, hd).transpose(1, 0, 2), nh // nkv, 0)
    v = jnp.repeat(v.reshape(s, nkv, hd).transpose(1, 0, 2), nh // nkv, 0)
    qb = min(QUERY_BLOCK, s)
    pad = -s % qb
    q = jnp.pad(q, ((0, pad), (0, 0))).reshape(-1, qb, nh, hd)

    def block(args):
        i, qi = args                                    # [qb, nh, hd]
        att = _mm(qi.transpose(1, 0, 2), k.transpose(0, 2, 1),
                  round_to) / math.sqrt(hd)             # [nh, qb, s]
        seen = jnp.arange(s)[None, :] <= (i * qb + jnp.arange(qb))[:, None]
        att = jnp.where(seen, att, -jnp.inf)
        o = _mm(jax.nn.softmax(att, axis=-1), v, round_to)
        return o.transpose(1, 0, 2).reshape(qb, nh * hd)

    o = jax.lax.map(block, (jnp.arange(q.shape[0]), q))
    return _mm(o.reshape(-1, nh * hd)[:s], ap["wo"], round_to)


def _linear(c, lp, x, round_to, state_round_to):
    s, _ = x.shape
    H, K, V, taps = (c["linear_num_value_heads"], c["linear_key_head_dim"],
                     c["linear_value_head_dim"], c["linear_conv_kernel_dim"])
    ch = H * (2 * K + V)
    qkv = _mm(x, lp["wqkv"], round_to)
    padded = jnp.concatenate([jnp.zeros((taps - 1, ch), F32), qkv], axis=0)
    conv = sum(padded[j:j + s] * lp["conv_w"][j] for j in range(taps))
    q, k, v = jnp.split(jax.nn.silu(conv), [H * K, 2 * H * K], axis=-1)
    q = _unit(q.reshape(s, H, K)) / math.sqrt(K)
    k = _unit(k.reshape(s, H, K))
    v = v.reshape(s, H, V)
    ab = _mm(x, lp["wab"], round_to)
    beta = 2.0 * jax.nn.sigmoid(ab[:, H:])                      # [s, H]
    alpha = jnp.exp(-jnp.exp(lp["A_log"])
                    * jax.nn.softplus(ab[:, :H] + lp["dt_bias"]))

    def step(S, t):                                             # [H, V, K]
        qt, kt, vt, at, bt = t
        Sk = jnp.einsum("hvk,hk->hv", S, kt)
        S = at[:, None, None] * (S - bt[:, None, None] * Sk[:, :, None]
                                 * kt[:, None, :]) \
            + bt[:, None, None] * vt[:, :, None] * kt[:, None, :]
        if state_round_to is not None:
            S = _round(S, state_round_to)
        return S, jnp.einsum("hvk,hk->hv", S, qt)

    _, o = jax.lax.scan(step, jnp.zeros((H, V, K), F32),
                        (q, k, v, alpha, beta))
    gate = _mm(x, lp["wg"], round_to)
    y = _rms_norm(o, lp["gnorm"], c["rms_norm_eps"]).reshape(s, H * V) \
        * jax.nn.silu(gate)
    return _mm(y, lp["wo"], round_to)


def _mlp(fp, x, round_to):
    a, b = jnp.split(_mm(x, fp["w_in"], round_to), 2, axis=-1)
    return _mm(jax.nn.silu(a) * b, fp["w_out"], round_to)


@partial(jax.jit, static_argnames=("kind", "c", "round_to",
                                   "state_round_to"))
def _layer(lp, x, kind, c, round_to, state_round_to):
    """One layer (mixer, then the MLP) on x [s, d] (one sequence)."""
    c = dict(c)
    eps = c["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        mp, fp = lp["mixer"], lp["ffn"]
        mix = _linear(c, mp, x, round_to, state_round_to) \
            if kind == "linear_attention" else _attention(c, mp, x, round_to)
        x = x + _rms_norm(mix, mp["norm"], eps)
        return x + _rms_norm(_mlp(fp, x, round_to), fp["norm"], eps)


@partial(jax.jit, static_argnames=("c", "round_to"))
def _head(w_head, norm_f, x, c, round_to):
    c = dict(c)
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, norm_f.astype(F32), c["rms_norm_eps"])
        return _mm(h, w_head.astype(F32), round_to)


def _static(config: dict) -> tuple:
    """The published keys the layers read, hashable for ``jit``."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "rms_norm_eps", "linear_num_value_heads", "linear_key_head_dim",
            "linear_value_head_dim", "linear_conv_kernel_dim")
    return tuple((k, config[k]) for k in keys)


def layer_types(config: dict) -> list:
    types = config["layer_types"][:config["num_hidden_layers"]]
    if set(types) - {"linear_attention", "full_attention"}:
        raise ValueError(f"layer_types {sorted(set(types))}: only linear_"
                         f"attention and full_attention are written here")
    return types


def logits(params, tokens, config: dict, rows=None, round_to=None,
           state_round_to=None):
    """tokens [s] int -> logits [s, V] float32 (``rows``: only those
    positions).  A Python loop over the layers, each converted to
    float32 on its own, so that it fits beside the served weights."""
    c = _static(config)
    x = params["wte"][jnp.asarray(tokens)].astype(F32)
    for kind, lp in zip(layer_types(config), params["layers"]):
        x = _layer(lp, x, kind, c, round_to, state_round_to)
    if rows is not None:
        x = x[rows]
    return _head(params["head"], params["norm_f"], x, c, round_to)


def margins(params, prompt, emitted, config: dict, width: int,
            round_to=None, state_round_to=None):
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
    step = np.asarray(logits(params, seq, config, rows=rows,
                             round_to=round_to,
                             state_round_to=state_round_to))[:len(emitted)]
    chosen = step[np.arange(len(emitted)), np.asarray(emitted)]
    return step.max(-1) - chosen, step.argmax(-1)
