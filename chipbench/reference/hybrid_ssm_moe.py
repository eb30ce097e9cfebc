"""The ``granitemoehybrid`` forward pass in plain ``jax.numpy``: the oracle.

Written from the published configuration's keys and the family's public
description (``modeling_granitemoehybrid.py``): token embedding times
``embedding_multiplier``, no position embedding; per layer ``x = x +
residual_multiplier * mixer(RMSNorm(x))`` then ``x = x +
residual_multiplier * (routed(h) + shared(h))``, ``h = RMSNorm(x)``;
logits ``RMSNorm(x) @ wte^T / logits_scaling`` (tied).

  * attention layer: q ``num_attention_heads`` heads, k/v
    ``num_key_value_heads`` heads (each shared by a run of consecutive
    query heads), no bias, no rotary, ``softmax(q k^T *
    attention_multiplier, causal) v``, output projection.
  * mamba layer (Mamba-2): ``[z | xBC | dt] = in_proj(h)``; ``xBC =
    silu(causal_conv1d(xBC) + b)``; ``[x | B | C]``; ``dt = softplus(dt
    + dt_bias)``; ``A = -exp(A_log)``; per head ``S_t = exp(dt_t A)
    S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t`` — computed
    here TOKEN BY TOKEN, the definition, not a chunked form; ``y =
    RMSNorm(y * silu(z)) * w`` over the whole inner width (one group);
    ``out_proj``.
  * routed experts: router logits ``h W_r``; the ``num_experts_per_tok``
    largest; softmax over those logits gives the gates; expert ``e``:
    ``W_out_e (silu(a) * b)``, ``[a | b] = W_in_e h``.  Computed here
    DENSELY: every held expert on every token, times a gate that is 0
    where the token did not choose it.  shared expert: the same gated
    MLP, added ungated.

The share: ``held = (lo, hi)`` names the experts whose weights
``ffn.w_in / w_out`` hold; the router keeps all its outputs and its
top-k, and what absent experts would add is left out (the
model-configs guide's cut; tests add two shares up to the whole).  The
vocabulary is whatever ``wte`` holds.

float32 throughout, matrix products at the ``highest`` precision, no
kernel, no cache, no batching.  It imports nothing from ``ray_tpu``.
Parameters arrive as the plain dict the system under test holds them in
(that layout is data, not code): ``wte [V, d]``, ``norm_f [d]``, and
``layers``, a list of one ``{"mixer", "ffn"}`` a layer.  A mamba layer's
``mixer``: ``norm``, ``in_proj [d, di + C + H]``, ``conv_w [K, C]`` with
tap K-1 on the current token, ``conv_b``, ``dt_bias``, ``A_log``, ``D``,
``gnorm [di]``, ``out_proj [di, d]``; an attention layer's: ``norm``,
``wqkv [d, (h + 2 hkv) hd]`` q, k, v side by side and heads major,
``wo``.  ``ffn``: ``norm``, ``router [d, E]``, ``shared_in [d, 2 ws]``,
``shared_out``, ``w_in [E_held, d, 2 we]``, ``w_out``.

``round_to`` (a dtype) rounds every matrix product's two inputs to that
dtype first: the same mathematics in a LOWER precision, which the
serving check must be tight enough to tell from the stated one.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
MAX_EMITTED = 256      # margins() scores at most this many tokens a request


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mm(a, b, round_to):
    if round_to is not None:
        a, b = a.astype(round_to).astype(F32), b.astype(round_to).astype(F32)
    return a @ b


def _gated_mlp(h, w_in, w_out, round_to):
    a, b = jnp.split(_mm(h, w_in, round_to), 2, axis=-1)
    return _mm(jax.nn.silu(a) * b, w_out, round_to)


def _attention(c, ap, h, round_to):
    s, _ = h.shape
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["hidden_size"] // nh
    qkv = _mm(h, ap["wqkv"], round_to)
    q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], axis=-1)
    q = q.reshape(s, nh, hd).transpose(1, 0, 2)
    k = jnp.repeat(k.reshape(s, nkv, hd).transpose(1, 0, 2), nh // nkv, 0)
    v = jnp.repeat(v.reshape(s, nkv, hd).transpose(1, 0, 2), nh // nkv, 0)
    att = _mm(q, k.transpose(0, 2, 1), round_to) * c["attention_multiplier"]
    att = jnp.where(jnp.tril(jnp.ones((s, s), bool)), att, -jnp.inf)
    o = _mm(jax.nn.softmax(att, axis=-1), v, round_to)
    return _mm(o.transpose(1, 0, 2).reshape(s, nh * hd), ap["wo"], round_to)


def _mamba(c, mp, h, round_to):
    s, _ = h.shape
    H, P, N = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
    K, di = c["mamba_d_conv"], c["mamba_n_heads"] * c["mamba_d_head"]
    ch = di + 2 * c["mamba_n_groups"] * N
    zxd = _mm(h, mp["in_proj"], round_to)
    z, xBC, dt = jnp.split(zxd, [di, di + ch], axis=-1)
    padded = jnp.concatenate([jnp.zeros((K - 1, ch), F32), xBC], axis=0)
    conv = mp["conv_b"] + sum(padded[j:j + s] * mp["conv_w"][j]
                              for j in range(K))
    x, B, C = jnp.split(jax.nn.silu(conv), [di, di + N], axis=-1)
    x = x.reshape(s, H, P)
    dt = jax.nn.softplus(dt + mp["dt_bias"])                 # [s, H]
    A = -jnp.exp(mp["A_log"])

    def step(S, t):
        xt, dtt, Bt, Ct = t
        S = jnp.exp(dtt * A)[:, None, None] * S \
            + (dtt[:, None] * xt)[:, :, None] * Bt[None, None, :]
        return S, (S * Ct[None, None, :]).sum(-1) + mp["D"][:, None] * xt

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (x, dt, B, C))
    y = y.reshape(s, di) * jax.nn.silu(z)
    y = _rms_norm(y, mp["gnorm"], c["rms_norm_eps"])
    return _mm(y, mp["out_proj"], round_to)


def _experts(c, fp, h, held, round_to):
    lo, hi = held
    k = c["num_experts_per_tok"]
    top, idx = jax.lax.top_k(_mm(h, fp["router"], round_to), k)
    gates = jax.nn.softmax(top, axis=-1)                     # [s, k]
    # gate of expert e on each token: its softmax weight, or 0
    dense = jnp.zeros((h.shape[0], c["num_local_experts"]), F32).at[
        jnp.arange(h.shape[0])[:, None], idx].set(gates)

    def one(acc, e):
        w_in, w_out, g = e
        return acc + g[:, None] * _gated_mlp(h, w_in, w_out, round_to), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             (fp["w_in"], fp["w_out"], dense[:, lo:hi].T))
    return routed + _gated_mlp(h, fp["shared_in"], fp["shared_out"],
                               round_to)


@partial(jax.jit, static_argnames=("kind", "c", "held", "round_to"))
def _layer(mp, fp, x, kind, c, held, round_to):
    """One layer on x [s, d] (one sequence)."""
    c = dict(c)
    with jax.default_matmul_precision("highest"):
        mp, fp = (jax.tree.map(lambda a: a.astype(F32), t) for t in (mp, fp))
        h = _rms_norm(x, mp["norm"], c["rms_norm_eps"])
        mix = (_mamba if kind == "mamba" else _attention)(c, mp, h, round_to)
        x = x + c["residual_multiplier"] * mix
        h = _rms_norm(x, fp["norm"], c["rms_norm_eps"])
        return x + c["residual_multiplier"] * _experts(c, fp, h, held,
                                                       round_to)


@partial(jax.jit, static_argnames=("c", "round_to"))
def _head(wte, norm_f, x, c, round_to):
    c = dict(c)
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, norm_f.astype(F32), c["rms_norm_eps"])
        return _mm(h, wte.astype(F32).T, round_to) / c["logits_scaling"]


def _static(config: dict) -> tuple:
    """The published keys the layers read, hashable for ``jit``."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "attention_multiplier", "residual_multiplier",
            "embedding_multiplier", "logits_scaling", "rms_norm_eps",
            "mamba_n_heads", "mamba_d_head", "mamba_d_state",
            "mamba_n_groups", "mamba_d_conv", "num_local_experts",
            "num_experts_per_tok")
    return tuple((k, config[k]) for k in keys)


def logits(params, tokens, config: dict, held: tuple, rows=None,
           round_to=None):
    """tokens [s] int -> logits [s, V] float32 (``rows``: only those
    positions).  A Python loop over the layers, each converted to
    float32 on its own, so that it fits beside the served weights."""
    c = _static(config)
    x = params["wte"][jnp.asarray(tokens)].astype(F32) \
        * config["embedding_multiplier"]
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    for kind, lp in zip(kinds, params["layers"]):
        x = _layer(lp["mixer"], lp["ffn"], x, kind, c, tuple(held),
                   round_to)
    if rows is not None:
        x = x[rows]
    return _head(params["wte"], params["norm_f"], x, c, round_to)


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
