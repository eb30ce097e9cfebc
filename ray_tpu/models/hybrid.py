"""Hybrid language model: Mamba-2 mixers, attention mixers and routed
experts in a periodic layer pattern.  ONE layer function serves the two
layouts public configs of the kind have:

    x0 = wte[ids] * embedding_multiplier            (no position embedding)
    x  = x + residual_multiplier * mixer(RMSNorm(x) * w)     per sublayer
    logits = RMSNorm(x) @ W_head / logits_scaling   (W_head = wte^T if tied)

``mixer`` is one of three kinds:

  * attention: grouped queries (``n_heads`` query heads over
    ``n_kv_heads`` K/V heads), no bias, no rotary; softmax(q k^T *
    attention_multiplier, causal) v; output projection.
  * Mamba-2 (ops/ssm.py): ``[z | xBC | dt] = in_proj(h)``; ``xBC =
    silu(causal_conv(xBC) + b)``; ``[x | B | C]`` with B, C in
    ``ssm_groups`` groups; ``dt = softplus(dt + dt_bias)``; ``A =
    -exp(A_log)``; the selective recurrence per head; ``y = RMSNorm(y *
    silu(z)) * w``, the mean square taken over each group's channels
    (one group: the whole inner width); ``out_proj``.
  * experts (ops/routed_experts.py): dropless top-k over ALL
    ``n_experts`` router outputs, of which this parameter set holds the
    range ``experts_held``, plus a shared expert of the same form,
    ungated.  ``gated_experts``: ``W_out (silu(a) * b)`` experts behind
    a softmax over the chosen logits; else ``W_out relu(W_in h)^2``
    experts behind sigmoid scores, chosen by ``score + bias``, weighted
    by the chosen scores normalised and times ``routed_scale``.

A published layer is one such sublayer (``nemotron_h``: the pattern
string's ``M`` / ``*`` / ``E``), or, with ``experts_in_every_layer``
(``granitemoehybrid``), a Mamba or attention sublayer FOLLOWED by an
experts sublayer with its own norm and residual: the same function twice.

``block`` takes a window of tokens per row and what the row's mixer needs
from the past — for a Mamba sublayer the convolution and SSM state the
row arrives with, for an attention sublayer a function that attends the
window's queries over the row's keys.  The full-sequence ``forward``
(zero state, keys = the window's own), the serving engine's
chunk-prefill program ([1 row, chunk], state and K/V blocks from the
pools) and its decode program ([rows, 1]) are that one function at three
shapes (inference/recurrent.py builds the latter two).

Parameters are ``{"wte", "norm_f", "layers": [one dict a layer]}`` (and
``"head"`` [d, V] where it is not tied), each layer ``{"mixer": {...}}``,
``{"ffn": {...}}`` or both, with arrays of its own, and the layer loop is
unrolled: the pattern mixes three layer bodies, a pool update indexed by
a static layer number stays in place, and no layer's weights are ever
sliced out of a stack (on the chip a slice of a stacked expert tensor
handed to the grouped matmul is a 432 MB copy a layer).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ray_tpu.ops import ssm
from ray_tpu.ops.routed_experts import lanes, mlp, routed_experts

MAMBA, ATTENTION, EXPERTS = "mamba", "attention", "experts"
N_LOAD = 4      # numbers in ``run_layers``' load vector (its text names them)
# the sublayer kinds of a ``nemotron_h`` pattern string
PATTERN_KINDS = {"M": MAMBA, "*": ATTENTION, "E": EXPERTS}


@dataclass(frozen=True)
class HybridConfig:
    vocab_size: int = 100352         # embedding rows HELD (a slice, if cut)
    d_model: int = 4096
    layer_types: tuple = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4
    # attention mixer
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    # Mamba-2 mixer
    ssm_heads: int = 128
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1
    conv_width: int = 4
    ssm_chunk: int = 256
    # experts
    n_experts: int = 72              # router width, as published
    experts_per_token: int = 10
    expert_width: int = 768
    shared_width: int = 1536
    experts_held: tuple = (0, 72)    # [lo, hi) of the router's outputs
    # the layout and the forms that differ between published families
    experts_in_every_layer: bool = True   # mixer THEN experts, a layer
    gated_experts: bool = True       # False: relu^2 MLPs, sigmoid router
    routed_scale: float = 1.0        # on the sigmoid router's weights
    tied_head: bool = True
    # the first family's four multipliers
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 1.0 / 128
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    rms_eps: float = 1e-5
    max_seq: int = 131072            # no position table bounds it
    dtype: Any = jnp.bfloat16        # activations and K/V
    param_dtype: Any = jnp.bfloat16  # as the published checkpoint

    def __post_init__(self):
        bad = set(self.layer_types) - {MAMBA, ATTENTION, EXPERTS}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if self.experts_in_every_layer and EXPERTS in self.layer_types:
            raise ValueError("experts_in_every_layer: a layer is its "
                             "mixer then experts; it cannot be experts")
        if self.ssm_heads % self.ssm_groups:
            raise ValueError("ssm_heads must be a multiple of ssm_groups")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.n_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of {self.n_experts} experts")

    @classmethod
    def from_published(cls, config: dict, **overrides) -> "HybridConfig":
        """From a public ``config.json``'s own keys: ``nemotron_h``'s
        where it has a ``hybrid_override_pattern``, else
        ``granitemoehybrid``'s."""
        c = config
        if "hybrid_override_pattern" in c:
            return cls(**{**_nemotron_h_keys(c), **overrides})
        kw = dict(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"],
            layer_types=tuple(c["layer_types"][:c["num_hidden_layers"]]),
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"],
            head_dim=c["hidden_size"] // c["num_attention_heads"],
            ssm_heads=c["mamba_n_heads"], ssm_head_dim=c["mamba_d_head"],
            ssm_state=c["mamba_d_state"], ssm_groups=c["mamba_n_groups"],
            conv_width=c["mamba_d_conv"], ssm_chunk=c["mamba_chunk_size"],
            n_experts=c["num_local_experts"],
            experts_per_token=c["num_experts_per_tok"],
            expert_width=c["intermediate_size"],
            shared_width=c["shared_intermediate_size"],
            experts_held=(0, c["num_local_experts"]),
            embedding_multiplier=c["embedding_multiplier"],
            attention_multiplier=c["attention_multiplier"],
            residual_multiplier=c["residual_multiplier"],
            logits_scaling=c["logits_scaling"],
            rms_eps=c["rms_norm_eps"],
            max_seq=c["max_position_embeddings"])
        return cls(**{**kw, **overrides})

    @staticmethod
    def tiny(**kw) -> "HybridConfig":
        """Test-sized config: mamba-attention-mamba, 8 experts top-3."""
        return HybridConfig(**{**dict(
            vocab_size=256, d_model=64,
            layer_types=(MAMBA, ATTENTION, MAMBA), n_heads=4, n_kv_heads=2,
            head_dim=16, ssm_heads=8, ssm_head_dim=16, ssm_state=16,
            conv_width=4, ssm_chunk=8, n_experts=8, experts_per_token=3,
            expert_width=32, shared_width=48, experts_held=(0, 8),
            attention_multiplier=1.0 / 16, max_seq=128,
            dtype=jnp.float32, param_dtype=jnp.float32), **kw})

    # -- derived sizes ---------------------------------------------------
    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_mamba(self) -> int:
        return self.layer_types.count(MAMBA)

    @property
    def n_attention(self) -> int:
        return self.layer_types.count(ATTENTION)

    @property
    def sublayers(self) -> tuple:
        """(index into ``params["layers"]``, kind) of every residual
        sublayer, in the order they run."""
        out = []
        for i, kind in enumerate(self.layer_types):
            out.append((i, kind))
            if self.experts_in_every_layer:
                out.append((i, EXPERTS))
        return tuple(out)

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_channels(self) -> int:
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    # -- what a serving cache holds for this model (inference/cache.py) --
    @property
    def kv_geometry(self) -> tuple:
        """(layers that keep K/V, K/V heads, head size)."""
        return (self.n_attention, self.n_kv_heads, self.head_dim)

    @property
    def state_geometry(self) -> tuple:
        """Per row and recurrent layer: (layers, conv state shape, SSM
        state shape).  The SSM state ``[heads, head width, state]`` is
        STORED with heads and head width folded into one dim: a pool
        whose trailing dims are ``(8192, 128)`` has one natural tiling,
        so no program re-lays the whole pool out to suit its own
        products (the chunk program did, a 2.4 GB copy, when the three
        dims were kept apart)."""
        return (self.n_mamba, (self.conv_width - 1, self.conv_channels),
                (self.ssm_heads * self.ssm_head_dim, self.ssm_state))


def _nemotron_h_keys(c: dict) -> dict:
    """``HybridConfig`` fields from ``nemotron_h`` keys.  What the layer
    function has no form for is refused here, by name."""
    pattern = c["hybrid_override_pattern"][:c["num_hidden_layers"]]
    unknown = sorted(set(pattern) - set(PATTERN_KINDS))
    if unknown:
        raise ValueError(
            f"hybrid_override_pattern {pattern!r} has layers {unknown}: "
            f"only {sorted(PATTERN_KINDS)} are implemented ('-', a dense "
            f"MLP layer, is not)")
    for key, want in (("n_shared_experts", 1), ("n_group", 1),
                      ("topk_group", 1), ("norm_topk_prob", True),
                      ("tie_word_embeddings", False)):
        if c.get(key, want) != want:
            raise ValueError(f"{key} = {c[key]!r} is not implemented "
                             f"(only {want!r})")
    return dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        layer_types=tuple(PATTERN_KINDS[k] for k in pattern),
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        ssm_heads=c["mamba_num_heads"], ssm_head_dim=c["mamba_head_dim"],
        ssm_state=c["ssm_state_size"], ssm_groups=c["n_groups"],
        conv_width=c["conv_kernel"], ssm_chunk=c["chunk_size"],
        n_experts=c["n_routed_experts"],
        experts_per_token=c["num_experts_per_tok"],
        expert_width=c["moe_intermediate_size"],
        shared_width=c["moe_shared_expert_intermediate_size"],
        experts_held=(0, c["n_routed_experts"]),
        experts_in_every_layer=False, gated_experts=False,
        routed_scale=c["routed_scaling_factor"], tied_head=False,
        embedding_multiplier=1.0,
        attention_multiplier=1.0 / math.sqrt(c["head_dim"]),
        residual_multiplier=1.0, logits_scaling=1.0,
        rms_eps=c["layer_norm_epsilon"],
        max_seq=c["max_position_embeddings"])


# -- params ----------------------------------------------------------------

def init_params(cfg: HybridConfig, rng: jax.Array):
    """N(0, 0.02) matrices; the Mamba-2 reference initialisation of what
    sets the recurrence's time scale, so that states neither vanish nor
    blow up: ``A_log = log U(1, 16)``, ``dt_bias = softplus^-1`` of a
    log-uniform dt in [1e-3, 1e-1], ``D = 1``, convolution taps and bias
    U(+-1/sqrt(K)); norm weights 1; a sigmoid router's selection bias
    N(0, 0.02) (not zeros: the choice by ``score + bias`` then differs
    from the choice by score).  A tied embedding is N(0, 0.02 /
    embedding_multiplier): at N(0, 0.02) the multiplier puts a token's
    own embedding 12 sigma above every other logit of the tied head, and
    every greedy stream just repeats its prompt's last token (read on
    the chip, PR 29) — a model on which no output depends on the
    mixers."""
    d, pd = cfg.d_model, cfg.param_dtype
    di, H, K, C = (cfg.ssm_inner, cfg.ssm_heads, cfg.conv_width,
                   cfg.conv_channels)
    hq, hkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    bound = 1.0 / math.sqrt(K)
    halves = 2 if cfg.gated_experts else 1      # [a | b] or one product
    # a stacked one-product w_in is stored with its columns zero-padded
    # to whole lane tiles (``routed_experts.lanes`` says why)
    pad = 0 if cfg.gated_experts else lanes(cfg.expert_width) \
        - cfg.expert_width

    def sublayer(kind, k):
        """``k``: the layer's iterator of keys (a layer's sublayers draw
        from one, the mixer first)."""
        def norm(shape):
            return (jax.random.normal(next(k), shape) * 0.02).astype(pd)

        def unif(shape, lo, hi):
            return jax.random.uniform(next(k), shape, minval=lo, maxval=hi)

        if kind == MAMBA:
            dt = jnp.exp(unif((H,), math.log(1e-3), math.log(1e-1)))
            return {
                "norm": jnp.ones((d,), pd),
                "in_proj": norm((d, di + C + H)),
                "conv_w": unif((K, C), -bound, bound).astype(pd),
                "conv_b": unif((C,), -bound, bound).astype(pd),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(unif((H,), 1.0, 16.0)),
                "D": jnp.ones((H,), jnp.float32),
                "gnorm": jnp.ones((di,), pd),
                "out_proj": norm((di, d)),
            }
        if kind == ATTENTION:
            return {
                "norm": jnp.ones((d,), pd),
                "wqkv": norm((d, hq + 2 * hkv)),
                "wo": norm((hq, d)),
            }
        ffn = {
            "norm": jnp.ones((d,), pd),
            "router": norm((d, cfg.n_experts)),
            "shared_in": norm((d, halves * cfg.shared_width)),
            "shared_out": norm((cfg.shared_width, d)),
            "w_in": jnp.pad(
                norm((cfg.n_held, d, halves * cfg.expert_width)),
                [(0, 0), (0, 0), (0, pad)]),
            "w_out": norm((cfg.n_held, cfg.expert_width, d)),
        }
        if not cfg.gated_experts:
            ffn["router_bias"] = jax.random.normal(
                next(k), (cfg.n_experts,)) * 0.02
        return ffn

    keys = jax.random.split(rng, cfg.n_layers + 1)
    streams = [iter(jax.random.split(key, 12)) for key in keys[1:]]
    layers = [{} for _ in cfg.layer_types]
    for i, kind in cfg.sublayers:
        layers[i]["ffn" if kind == EXPERTS else "mixer"] = sublayer(
            kind, streams[i])
    params = {
        "wte": (jax.random.normal(keys[0], (cfg.vocab_size, d))
                * (0.02 / cfg.embedding_multiplier)).astype(pd),
        "norm_f": jnp.ones((d,), pd),
        "layers": layers,
    }
    if not cfg.tied_head:
        params["head"] = (jax.random.normal(jax.random.fold_in(rng, 1),
                                            (d, cfg.vocab_size))
                          * 0.02).astype(pd)
    return params


def num_params(params) -> int:
    return sum(int(math.prod(p.shape)) for p in jax.tree.leaves(params))


def cast_at_use(params) -> list:
    """The leaves a program casts to the activations' dtype where it
    multiplies by them: every matrix (the convolution's taps are used in
    float32).  The family is published and held in that dtype, so the
    casts are no-ops and the tree is served as it is."""
    out = [params[n] for n in ("wte", "head") if n in params]
    names = {"mixer": ("in_proj", "out_proj", "wqkv", "wo"),
             "ffn": ("router", "shared_in", "shared_out", "w_in", "w_out")}
    for lp in params["layers"]:
        out += [lp[sub][n] for sub in names if sub in lp
                for n in names[sub] if n in lp[sub]]
    return out


# -- the layer ---------------------------------------------------------------

def _rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * jnp.asarray(w, jnp.float32)).astype(x.dtype)


def _mamba_mixer(cfg, mp, h, state, n_valid):
    """h [b, w, d]; state (conv [b, K-1, C], ssm): the rows' SSM state
    as ``ops/ssm.ssd`` addresses it, (pool [L, b, H * P, N] f32, layer).
    -> (out [b, w, d], state)."""
    conv_state, (ssm_pool, ssm_layer) = state
    b, w, _ = h.shape
    di, H, P, N, G = (cfg.ssm_inner, cfg.ssm_heads, cfg.ssm_head_dim,
                      cfg.ssm_state, cfg.ssm_groups)
    with jax.named_scope("mixer_ssm_proj"):
        zxd = jnp.dot(h, mp["in_proj"].astype(h.dtype))
        z, xBC, dt = jnp.split(zxd, [di, di + cfg.conv_channels], axis=-1)
    with jax.named_scope("mixer_ssm"):
        xBC, conv_state = ssm.causal_conv(xBC, conv_state, mp["conv_w"],
                                          mp["conv_b"], n_valid)
        x, B, C = jnp.split(xBC, [di, di + G * N], axis=-1)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + mp["dt_bias"])
        y, ssm_pool = ssm.ssd(x.reshape(b, w, H, P), dt,
                              -jnp.exp(mp["A_log"]), B.reshape(b, w, G, N),
                              C.reshape(b, w, G, N), mp["D"],
                              ssm_pool, ssm_layer, n_valid, cfg.ssm_chunk)
        y = y.reshape(b, w, di) * jax.nn.silu(z.astype(jnp.float32))
        # the mean square over each group's channels, the weight over all
        y = _rms_norm(y.reshape(b, w, G, di // G), 1.0, cfg.rms_eps)
        y = (y.reshape(b, w, di)
             * mp["gnorm"].astype(jnp.float32)).astype(h.dtype)
    with jax.named_scope("mixer_ssm_proj"):
        out = jnp.dot(y, mp["out_proj"].astype(h.dtype))
    return out, (conv_state, (ssm_pool, ssm_layer))


def _attention_mixer(cfg, ap, h, attend):
    """h [b, w, d]; ``attend(q [b, h, w, hd], k, v [b, w, hkv, hd]) ->
    o [b, h, w, hd]`` supplies the keys of the past."""
    b, w, _ = h.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("mixer_attention"):
        qkv = jnp.dot(h, ap["wqkv"].astype(h.dtype))
        q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], axis=-1)
        o = attend(q.reshape(b, w, nh, hd).transpose(0, 2, 1, 3),
                   k.reshape(b, w, nkv, hd), v.reshape(b, w, nkv, hd))
        o = o.transpose(0, 2, 1, 3).reshape(b, w, nh * hd)
        return jnp.dot(o, ap["wo"].astype(h.dtype))


def _experts(cfg, fp, h, valid):
    """h [b, w, d] -> (routed + shared [b, w, d], counts [E_held],
    total)."""
    b, w, d = h.shape
    flat = h.reshape(b * w, d)
    with jax.named_scope("routed_experts"):
        routed, counts, total = routed_experts(
            flat, fp["router"], fp["w_in"], fp["w_out"],
            top_k=cfg.experts_per_token, held=cfg.experts_held,
            valid=valid.reshape(b * w), gated=cfg.gated_experts,
            bias=fp.get("router_bias"), scale=cfg.routed_scale)
    with jax.named_scope("shared_expert"):
        shared = mlp(flat, fp["shared_in"], fp["shared_out"],
                     cfg.gated_experts)
    return (routed + shared).reshape(b, w, d), counts, total


def block(cfg: HybridConfig, kind: str, lp, x, past, n_valid):
    """ONE residual sublayer on a window: x [b, w, d], ``n_valid`` [b]
    real tokens a row; ``lp`` its parameters.  ``past`` is the row's
    state for a Mamba sublayer (returned updated), the ``attend``
    function for an attention sublayer (returned as it came) and unused
    by experts.
    -> (x, past, (counts [E_held], total) of an experts sublayer, else
        None)."""
    h = _rms_norm(x, lp["norm"], cfg.rms_eps)
    load = None
    if kind == MAMBA:
        mix, past = _mamba_mixer(cfg, lp, h, past, n_valid)
    elif kind == ATTENTION:
        mix = _attention_mixer(cfg, lp, h, past)
    else:
        valid = jnp.arange(x.shape[1])[None, :] < n_valid[:, None]
        mix, counts, total = _experts(cfg, lp, h, valid)
        load = (counts, total)
    return x + cfg.residual_multiplier * mix, past, load


def embed(cfg: HybridConfig, params, tokens):
    return (params["wte"][tokens].astype(jnp.float32)
            * cfg.embedding_multiplier).astype(cfg.dtype)


def head(cfg: HybridConfig, params, x):
    """x [..., d] -> logits [..., V] float32 over the held vocabulary."""
    h = _rms_norm(x, params["norm_f"], cfg.rms_eps)
    w = params["wte"].T if cfg.tied_head else params["head"]
    logits = jnp.dot(h, w.astype(h.dtype),
                     preferred_element_type=jnp.float32)
    return logits / cfg.logits_scaling


def run_layers(cfg: HybridConfig, params, x, n_valid, state_in: Callable,
               state_out: Callable, attend_for: Callable):
    """The unrolled layer loop over a window.  ``state_in(mi)`` gives
    Mamba layer ``mi``'s state for the window's rows, (conv, (ssm pool,
    layer)) as ``_mamba_mixer`` takes it, and ``state_out(mi, state)``
    takes it back; ``attend_for(ai)`` gives
    attention layer ``ai``'s ``attend``.
    -> (x, load [N_LOAD] int32: held assignments, all assignments, the
        busiest held expert's assignments and the held experts with at
        least one assignment, each summed over the experts sublayers;
        real tokens only)."""
    mi = ai = 0
    load = jnp.zeros((N_LOAD,), jnp.int32)
    for i, kind in cfg.sublayers:
        lp = params["layers"][i]
        if kind == MAMBA:
            x, state, _ = block(cfg, kind, lp["mixer"], x, state_in(mi),
                                n_valid)
            state_out(mi, state)
            mi += 1
        elif kind == ATTENTION:
            x, _, _ = block(cfg, kind, lp["mixer"], x, attend_for(ai),
                            n_valid)
            ai += 1
        else:
            x, _, (counts, total) = block(cfg, kind, lp["ffn"], x, None,
                                          n_valid)
            load = load + jnp.stack([counts.sum(), total, counts.max(),
                                     (counts > 0).sum(dtype=jnp.int32)])
    return x, load


def zero_state(cfg: HybridConfig, rows: int):
    """(conv, ssm) state of ``rows`` rows that have seen nothing, the
    SSM state as a pool of this one layer."""
    _, conv, ssm_shape = cfg.state_geometry
    return (jnp.zeros((rows, *conv), cfg.dtype),
            (jnp.zeros((1, rows, *ssm_shape), jnp.float32), 0))


def causal_attend(cfg: HybridConfig):
    """``attend`` over the window's own keys (a full sequence)."""
    rep = cfg.n_heads // cfg.n_kv_heads

    def attend(q, k, v):
        b, nh, w, hd = q.shape
        qg = q.reshape(b, cfg.n_kv_heads, rep, w, hd)
        logits = jnp.einsum("bgrqd,bkgd->bgrqk", qg, k,
                            preferred_element_type=jnp.float32) \
            * cfg.attention_multiplier
        mask = jnp.tril(jnp.ones((w, w), bool))
        probs = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)
        o = jnp.einsum("bgrqk,bkgd->bgrqd", probs.astype(v.dtype), v)
        return o.reshape(b, nh, w, hd)
    return attend


def forward(params, tokens, cfg: HybridConfig):
    """tokens [b, s] -> logits [b, s, V] float32: the layer function on
    one window of the whole sequence, from zero state."""
    b, s = tokens.shape
    attend = causal_attend(cfg)
    x, _ = run_layers(
        cfg, params, embed(cfg, params, tokens),
        jnp.full((b,), s, jnp.int32),
        state_in=lambda mi: zero_state(cfg, b),
        state_out=lambda mi, state: None,
        attend_for=lambda ai: attend)
    return head(cfg, params, x)
