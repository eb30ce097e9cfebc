"""GPT-2's forward pass and loss in plain ``jax.numpy``: the oracle.

Written from the published block (Radford et al. 2019; the layout of
``openai-community/gpt2``): learned token and position embeddings, N
pre-LayerNorm blocks of causal multi-head attention and a GELU (tanh
form, ``gelu_new``) feed-forward, a final LayerNorm, and a head tied to
the token embedding.  float32 throughout, matrix products at the
``highest`` precision (on a TPU a float32 product otherwise runs in
bfloat16 passes), no kernel, no cache, no scan, no sharding.

It imports nothing from ``ray_tpu``.  Parameters arrive as the plain
dict the system under test holds them in (that layout is data, not
code): ``wte [V, d]``, ``wpe [S, d]``, ``ln_f_scale/bias [d]`` and, per
layer along a leading axis, ``ln1_*``, ``wqkv [d, 3d]`` (q, k, v side
by side, heads major inside each), ``wo [d, d]``, ``bo``, ``ln2_*``,
``w_up [d, f]``, ``b_up``, ``w_down [f, d]``, ``b_down``.

Departure from the published model, shared with the system under test
and noted in the configuration files: no bias on the q/k/v projection.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _layer_norm(x, scale, bias):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * scale + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


@partial(jax.jit, static_argnames=("n_head",))
def _block(lp, x, n_head: int):
    """One pre-LN block on x [b, s, d] with one layer's parameters."""
    with jax.default_matmul_precision("highest"):
        b, s, d = x.shape
        hd = d // n_head
        y = _layer_norm(x, lp["ln1_scale"], lp["ln1_bias"])
        q, k, v = jnp.split(y @ lp["wqkv"], 3, axis=-1)
        q, k, v = (t.reshape(b, s, n_head, hd).transpose(0, 2, 1, 3)
                   for t in (q, k, v))
        att = (q @ k.transpose(0, 1, 3, 2)) / math.sqrt(hd)
        att = jnp.where(jnp.tril(jnp.ones((s, s), bool)), att, -jnp.inf)
        att = jax.nn.softmax(att, axis=-1)
        o = (att @ v).transpose(0, 2, 1, 3).reshape(b, s, d)
        x = x + o @ lp["wo"] + lp["bo"]
        y = _layer_norm(x, lp["ln2_scale"], lp["ln2_bias"])
        u = _gelu_new(y @ lp["w_up"] + lp["b_up"])
        return x + u @ lp["w_down"] + lp["b_down"]


@jax.jit
def _embed(wte, wpe, tokens):
    return wte[tokens] + wpe[:tokens.shape[1]][None]


@jax.jit
def _head(wte, scale, bias, x):
    with jax.default_matmul_precision("highest"):
        return _layer_norm(x, scale, bias) @ wte.T


def forward(params, tokens, n_head: int):
    """tokens [b, s] int32 -> logits [b, s, V] float32.  A Python loop
    over the layers: one small compiled block, called once per layer."""
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    x = _embed(p["wte"], p["wpe"], tokens)
    for i in range(p["layers"]["wqkv"].shape[0]):
        x = _block(jax.tree.map(lambda a: a[i], p["layers"]), x, n_head)
    return _head(p["wte"], p["ln_f_scale"], p["ln_f_bias"], x)


def loss(params, tokens, n_head: int):
    """Mean next-token cross-entropy of ``tokens [b, s+1]``."""
    logits = forward(params, tokens[:, :-1], n_head)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def margins(params, prompt, emitted, n_head: int, width: int):
    """Teacher-forced check of one served request: for each emitted
    token, how far its logit lies below that position's maximum (0 =
    the argmax).  prompt + emitted is padded to ``width`` (causal, so
    the padding changes nothing before it) to keep one compiled shape."""
    import numpy as np
    seq = np.zeros((1, width), np.int32)
    n_p, n = len(prompt), len(prompt) + len(emitted)
    seq[0, :n_p], seq[0, n_p:n] = prompt, emitted
    logits = np.asarray(forward(params, jnp.asarray(seq), n_head)[0])
    step = logits[n_p - 1:n - 1]
    chosen = step[np.arange(len(emitted)), np.asarray(emitted)]
    return step.max(-1) - chosen
