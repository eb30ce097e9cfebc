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

``round_to`` (a dtype) rounds every matrix product's two inputs to that
dtype first: the same mathematics in a LOWER precision, the control
that the serving check must be tight enough to tell from the stated one
(``chipbench/precision_reading.py``).
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


def _mm(a, b, round_to):
    if round_to is not None:
        a = a.astype(round_to).astype(jnp.float32)
        b = b.astype(round_to).astype(jnp.float32)
    return a @ b


@partial(jax.jit, static_argnames=("n_head", "round_to"))
def _block(lp, x, n_head: int, round_to=None):
    """One pre-LN block on x [b, s, d] with one layer's parameters."""
    with jax.default_matmul_precision("highest"):
        b, s, d = x.shape
        hd = d // n_head
        y = _layer_norm(x, lp["ln1_scale"], lp["ln1_bias"])
        q, k, v = jnp.split(_mm(y, lp["wqkv"], round_to), 3, axis=-1)
        q, k, v = (t.reshape(b, s, n_head, hd).transpose(0, 2, 1, 3)
                   for t in (q, k, v))
        att = _mm(q, k.transpose(0, 1, 3, 2), round_to) / math.sqrt(hd)
        att = jnp.where(jnp.tril(jnp.ones((s, s), bool)), att, -jnp.inf)
        att = jax.nn.softmax(att, axis=-1)
        o = _mm(att, v, round_to).transpose(0, 2, 1, 3).reshape(b, s, d)
        x = x + _mm(o, lp["wo"], round_to) + lp["bo"]
        y = _layer_norm(x, lp["ln2_scale"], lp["ln2_bias"])
        u = _gelu_new(_mm(y, lp["w_up"], round_to) + lp["b_up"])
        return x + _mm(u, lp["w_down"], round_to) + lp["b_down"]


@jax.jit
def _embed(wte, wpe, tokens):
    return wte[tokens] + wpe[:tokens.shape[1]][None]


@partial(jax.jit, static_argnames=("round_to",))
def _head(wte, scale, bias, x, round_to=None):
    with jax.default_matmul_precision("highest"):
        return _mm(_layer_norm(x, scale, bias), wte.T, round_to)


def forward(params, tokens, n_head: int, round_to=None):
    """tokens [b, s] int32 -> logits [b, s, V] float32.  A Python loop
    over the layers: one small compiled block, called once per layer,
    on that layer's parameters alone cast to float32 (the weights may
    arrive in the type they are served in)."""
    f32 = partial(jnp.asarray, dtype=jnp.float32)
    x = _embed(f32(params["wte"]), f32(params["wpe"]), tokens)
    layers = params["layers"]
    for i in range(layers["wqkv"].shape[0]):
        x = _block(jax.tree.map(lambda a: f32(a[i]), layers), x, n_head,
                   round_to)
    return _head(f32(params["wte"]), f32(params["ln_f_scale"]),
                 f32(params["ln_f_bias"]), x, round_to)


def loss(params, tokens, n_head: int, round_to=None):
    """Mean next-token cross-entropy of ``tokens [b, s+1]``."""
    logits = forward(params, tokens[:, :-1], n_head, round_to)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def served_logits(params, prompt, emitted, n_head: int, width: int,
                  round_to=None):
    """The float32 logits [len(emitted), V] at the positions that chose
    each emitted token, teacher-forced.  prompt + emitted is padded to
    ``width`` (causal, so the padding changes nothing before it) to keep
    one compiled shape."""
    import numpy as np
    seq = np.zeros((1, width), np.int32)
    n_p, n = len(prompt), len(prompt) + len(emitted)
    seq[0, :n_p], seq[0, n_p:n] = prompt, emitted
    logits = forward(params, jnp.asarray(seq), n_head, round_to)[0]
    return np.asarray(logits[n_p - 1:n - 1])


def margins(params, prompt, emitted, n_head: int, width: int):
    """Teacher-forced check of one served request: for each emitted
    token, how far its logit lies below that position's maximum (0 =
    the argmax)."""
    import numpy as np
    step = served_logits(params, prompt, emitted, n_head, width)
    chosen = step[np.arange(len(emitted)), np.asarray(emitted)]
    return step.max(-1) - chosen
