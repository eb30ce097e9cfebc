"""Hybrid language model: Mamba-2 mixers, gated-delta-rule linear
attention mixers, gated short-convolution mixers, attention mixers,
latent attention mixers, routed experts and dense gated MLPs in a layer
pattern.  ONE layer function serves the six layouts public configs of
the kind have:

    x0 = wte[ids] * embedding_multiplier            (no position embedding)
    x  = x + residual_multiplier * mixer(RMSNorm(x) * w)     per sublayer
    x  = x + RMSNorm(mixer(x)) * w              ... with ``norm_output``
    x  = x + RMSNorm(mixer(RMSNorm(x) * w)) * w'  ... with ``sandwich_norm``
    logits = RMSNorm(x) @ W_head / logits_scaling   (W_head = wte^T if tied)

``mixer`` is one of eight kinds:

  * attention: grouped queries (``n_heads`` query heads over
    ``n_kv_heads`` K/V heads), no bias, no rotary; softmax(q k^T *
    attention_multiplier, causal) v; output projection.  With
    ``qk_norm`` an RMSNorm over the WHOLE query projection and one over
    the whole key projection, before the heads are split; with
    ``qk_norm`` "head" one over each head's lanes (one weight
    ``[head_dim]`` for q, one for k); with ``attn_gate`` the output is
    ``o_proj(attention * sigmoid(x W_gate))``, W_gate's columns held
    behind q, k and v in ``wqkv``.
  * window attention (``afmoe``'s ``sliding_attention`` layers): the
    same sublayer whose query t sees keys ``t - window < j <= t`` only,
    and whose q and k are turned by rotary positions (``rope_theta``,
    all ``head_dim`` lanes, half-split pairs, no scaling) where the
    full-attention layers beside it use none.  Its K/V is a SECOND
    group of layers to a cache (``window_geometry``): what lies behind
    the window is never read again, and given back.
  * linear attention (ops/delta_rule.py, ``olmo_hybrid``): ``[q | k |
    v] = silu(causal_conv(x [W_q | W_k | W_v]))`` (no bias), per head
    ``q / |q| / sqrt(K)`` and ``k / |k|``; ``beta = 2 sigmoid(x w_b)``,
    ``log alpha = -exp(A_log) softplus(x w_a + dt_bias)``; the gated
    delta rule over a matrix state ``[V, K]`` a head; ``y = RMSNorm_V(
    o) * w * silu(x W_g)`` per head; output projection.
  * the gated short convolution (ops/short_conv.py, ``lfm2_moe``): ``[B
    | C | u] = h W_in`` (no bias), ``v = B * u``, a depthwise causal
    convolution of ``conv_width`` taps over ``v`` with no bias and NO
    activation, ``y = (C * conv) W_out``.  Its whole past is the last
    ``conv_width - 1`` values of ``v``: a state of ``[L - 1, d]`` a row
    and NOTHING else (``state_geometry``'s third entry is None), which
    is the state after any token of a window for the price of a slice —
    so a cache may keep it at every block boundary (inference/cache.py).
    The full-attention layers beside it turn q and k by rotary positions
    (``rotary_full``) after a per-head RMSNorm.
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
    by the chosen scores normalised and times ``routed_scale``.  With
    ``route_groups`` (n_group, topk_group) the third published form:
    scores the softmax over ALL router outputs in float32, the choice
    limited to the ``topk_group`` groups with the largest maximum score,
    gates the chosen scores times ``routed_scale``, normalised only if
    ``norm_topk``.  The router's form follows the experts' unless
    ``sigmoid_router`` says otherwise (``afmoe``: sigmoid scores and a
    selection bias over GATED experts and a gated shared expert,
    ``route_eps`` added to the chosen scores' sum).
    ``shared_width`` is the width of ONE ungated MLP,
    however many shared experts the config counts (they are published
    as one MLP of their summed width); 0: no shared expert, and no
    product or parameter stands in for one (``lfm2_moe``).
  * latent attention (``q_rank`` / ``kv_rank``): queries through a
    normed low-rank bottleneck, per head ``[nope | rope]`` lanes; ONE
    normed latent ``c_kv`` [kv_rank] and ONE rotated key ``k_rope``
    [rope_dim] a token, shared by all heads — what a cache keeps (see
    ``kv_geometry`` / ``value_lanes``); per-head keys and values are
    ``c_kv W_uk`` and ``c_kv W_uv``.  Rotary positions (YaRN-scaled,
    ``Yarn``) turn the rope lanes of q and k, half-split pairs; ``attention_multiplier``
    holds the softmax scale, YaRN's ``mscale`` squared included.  The
    ``attend`` it is handed chooses the product's form: decompressed
    keys a block at a time for a window, ``W_uk`` absorbed into the
    query and ``W_uv`` into the output for one token (ops/attention.py).
  * a dense gated MLP (``dense_width``): ``W_out (silu(a) * b)``.

A published layer is one such sublayer (``nemotron_h``: the pattern
string's ``M`` / ``*`` / ``E``), or, with ``experts_in_every_layer``
(``granitemoehybrid``, ``deepseek_v2``, ``olmo_hybrid``, ``afmoe``,
``lfm2_moe``), a Mamba, linear, short-convolution, attention, window or
latent sublayer FOLLOWED by a feed-forward sublayer
with its own norm and residual — experts, or the dense MLP in the first
``dense_layers`` layers (``olmo_hybrid``: all of them): the same
function twice.

``block`` takes a window of tokens per row and what the row's mixer needs
from the past — for a Mamba or linear sublayer the convolution and
SSM (or matrix) state the row arrives with, for an attention or latent
sublayer a function that attends the window's queries over the row's
keys (and, latent, the rotary tables at the window's positions).  The full-sequence ``forward``
(zero state, keys = the window's own), the serving engine's
chunk-prefill program ([1 row, chunk], state and K/V blocks from the
pools) and its decode program ([rows, 1]) are that one function at three
shapes (inference/recurrent.py builds the latter two).  A fourth is the
two serving shapes as ONE window in two parts, [1, rows + chunk]
(``block``'s ``rows``; the sublayer kinds of ``TWO_PART``): the pass
that holds both then multiplies by every matrix once.

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

from ray_tpu.ops import delta_rule, short_conv, ssm
from ray_tpu.ops.attention import KEY_BLOCK, latent_window_attention
from ray_tpu.ops.routed_experts import lanes, mlp, routed_experts

MAMBA, ATTENTION, EXPERTS = "mamba", "attention", "experts"
LATENT, DENSE, LINEAR = "latent", "dense", "linear_attention"
WINDOW = "window_attention"     # attention over the last ``window`` keys
SHORT_CONV = "short_conv"       # the gated short convolution
N_LOAD = 4      # numbers in ``run_layers``' load vector (its text names them)
# the sublayer kinds that take a window in two parts (``block``'s ``rows``)
TWO_PART = frozenset({MAMBA, SHORT_CONV, LINEAR, ATTENTION, EXPERTS, DENSE})
# the sublayer kinds that carry a per-row state (``state_geometry``)
RECURRENT = (MAMBA, LINEAR, SHORT_CONV)
# the sublayer kinds of a ``nemotron_h`` pattern string
PATTERN_KINDS = {"M": MAMBA, "*": ATTENTION, "E": EXPERTS}


@dataclass(frozen=True)
class Yarn:
    """YaRN-scaled rotary positions, a published ``rope_scaling`` of
    type ``yarn`` under its own keys."""
    theta: float = 10000.0
    factor: float = 40.0
    original_max: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @staticmethod
    def _mscale(factor: float, m: float) -> float:
        return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0

    @property
    def softmax_mscale(self) -> float:
        """What the softmax scale is multiplied by, squared."""
        return self._mscale(self.factor, self.mscale_all_dim)

    @property
    def table_mscale(self) -> float:
        """What the cos / sin tables are multiplied by."""
        return (self._mscale(self.factor, self.mscale)
                / self._mscale(self.factor, self.mscale_all_dim))

    def inv_freq(self, dim: int):
        """[dim / 2] float64: a pair's frequency, interpolated (divided
        by ``factor``) where its wavelength exceeds the original
        context, kept where it turns ``beta_fast`` times within it, a
        linear ramp between."""
        import numpy as np
        f = self.theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

        def turn(beta):         # the pair that turns beta times in the
            return (dim * math.log(self.original_max       # original
                                   / (beta * 2 * math.pi))  # context
                    / (2 * math.log(self.theta)))
        low = max(math.floor(turn(self.beta_fast)), 0)
        high = min(math.ceil(turn(self.beta_slow)), dim - 1)
        ramp = np.clip((np.arange(dim // 2) - low)
                       / max(high - low, 1e-3), 0.0, 1.0)
        return f / self.factor * ramp + f * (1.0 - ramp)


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
    # linear attention mixer (the gated delta rule; ``conv_width`` too)
    lin_heads: int = 0
    lin_key_dim: int = 0
    lin_value_dim: int = 0
    # latent attention mixer (``head_dim`` is its no-position lanes)
    q_rank: int = 0
    kv_rank: int = 0
    rope_dim: int = 0
    v_head_dim: int = 0
    yarn: Any = None                 # a ``Yarn``
    # experts
    n_experts: int = 72              # router width, as published
    experts_per_token: int = 10
    expert_width: int = 768
    shared_width: int = 1536
    experts_held: tuple = (0, 72)    # [lo, hi) of the router's outputs
    # the layout and the forms that differ between published families
    experts_in_every_layer: bool = True   # mixer THEN experts, a layer
    gated_experts: bool = True       # False: relu^2 MLPs, sigmoid router
    routed_scale: float = 1.0        # on the chosen scores' gates
    route_groups: tuple = ()         # (n_group, topk_group): third form
    norm_topk: bool = True           # ... its gates normalised to sum 1
    dense_layers: int = 0            # leading layers: dense MLP, no experts
    dense_width: int = 0
    tied_head: bool = True
    # attention: RMSNorm of whole q and k (True), or of each head's
    # lanes with ONE weight [head_dim] ("head")
    qk_norm: Any = False
    norm_output: bool = False        # a sublayer's norm on its OUTPUT
    # the ``afmoe`` layout's forms
    sandwich_norm: bool = False      # ... a norm before AND after
    attn_gate: bool = False          # o_proj(attn * sigmoid(x W_gate))
    window: int = 0                  # keys a WINDOW layer attends
    rope_theta: float = 0.0          # rotary q, k of the WINDOW layers
    rotary_full: bool = False        # ... and of the ATTENTION layers
    sigmoid_router: Any = None       # None: the relu^2 experts' (above)
    route_eps: float = 0.0           # added to the chosen scores' sum
    # the ``xing4_0`` layout's residual of ``hc_mult`` streams round every
    # sublayer (ops/hyper_connections.py; 0: the one-stream residual) and
    # its multi-token-prediction modules (``forward`` alone runs them)
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 0
    hc_eps: float = 0.0
    hc_res_clamp: tuple = ()         # (min, max) of the residual map's A
    mtp_layers: int = 0
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
        bad = set(self.layer_types) - {MAMBA, ATTENTION, EXPERTS, LATENT,
                                       LINEAR, WINDOW, SHORT_CONV}
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
        if self.dense_layers and not self.experts_in_every_layer:
            raise ValueError("dense_layers: only where a layer is its "
                             "mixer then a feed-forward sublayer")
        if LATENT in self.layer_types and set(self.layer_types) != {LATENT}:
            raise ValueError("latent and head-lane attention layers keep "
                             "different things: one pool holds one kind")
        if len(set(self.layer_types) & set(RECURRENT)) > 1:
            raise ValueError("Mamba, linear attention and short "
                             "convolution layers keep different states: "
                             "one pool holds one kind")
        if self.rotary_full and not self.rope_theta:
            raise ValueError("rotary_full needs a rope_theta")
        if (WINDOW in self.layer_types) != (self.window > 0):
            raise ValueError("window layers and a window > 0 go together")
        if self.n_window and (self.n_latent or self.state_geometry):
            raise ValueError("window layers beside latent or recurrent "
                             "layers: no cache holds the three kinds")
        if self.mtp_layers and set(self.layer_types) != {LATENT}:
            raise ValueError("mtp_layers: a prediction module's layer is "
                             "latent attention then experts")
        if self.route_groups and self.n_experts % self.route_groups[0]:
            raise ValueError(f"{self.n_experts} experts in "
                             f"{self.route_groups[0]} groups")

    @classmethod
    def from_published(cls, config: dict, **overrides) -> "HybridConfig":
        """From a public ``config.json``'s own keys: ``nemotron_h``'s
        where it has a ``hybrid_override_pattern``, ``deepseek_v2``'s
        where it has a ``kv_lora_rank``, ``olmo_hybrid``'s where it has
        a ``linear_key_head_dim``, ``lfm2_moe``'s where it has a
        ``conv_L_cache``, ``afmoe``'s where it has a
        ``global_attn_every_n_layers`` or a ``sliding_attention`` layer,
        else ``granitemoehybrid``'s."""
        c = config
        if "hybrid_override_pattern" in c:
            return cls(**{**_nemotron_h_keys(c), **overrides})
        if "kv_lora_rank" in c:
            return cls(**{**_latent_keys(c), **overrides})
        if "linear_key_head_dim" in c:
            return cls(**{**_olmo_hybrid_keys(c), **overrides})
        if "conv_L_cache" in c or c.get("model_type") == "lfm2_moe":
            return cls(**{**_lfm2_keys(c), **overrides})
        if ("global_attn_every_n_layers" in c
                or "sliding_attention" in c.get("layer_types", ())):
            return cls(**{**_afmoe_keys(c), **overrides})
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
    def n_linear(self) -> int:
        return self.layer_types.count(LINEAR)

    @property
    def n_short_conv(self) -> int:
        return self.layer_types.count(SHORT_CONV)

    @property
    def n_attention(self) -> int:
        return self.layer_types.count(ATTENTION)

    @property
    def n_latent(self) -> int:
        return self.layer_types.count(LATENT)

    @property
    def n_window(self) -> int:
        return self.layer_types.count(WINDOW)

    @property
    def routes_by_sigmoid(self) -> bool:
        """Sigmoid scores chosen by ``score + bias`` (a held
        ``router_bias``); else a softmax form."""
        return (not self.gated_experts if self.sigmoid_router is None
                else bool(self.sigmoid_router))

    @property
    def sublayers(self) -> tuple:
        """(index into ``params["layers"]``, kind) of every residual
        sublayer, in the order they run."""
        out = []
        for i, kind in enumerate(self.layer_types):
            out.append((i, kind))
            if self.experts_in_every_layer:
                out.append((i, DENSE if i < self.dense_layers else EXPERTS))
        return tuple(out)

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_channels(self) -> int:
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def lin_conv_channels(self) -> int:
        """[q | k | v] of every linear-attention head."""
        return self.lin_heads * (2 * self.lin_key_dim + self.lin_value_dim)

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    # -- what a serving cache holds for this model (inference/cache.py) --
    @property
    def kv_geometry(self) -> tuple:
        """(layers that keep K/V, K/V heads, head size).  Latent
        attention keeps ONE head a token: the normed latent then the
        rotated key, ``kv_rank + rope_dim`` lanes (``value_lanes`` says
        which of them are the values)."""
        if self.n_latent:
            return (self.n_latent, 1, self.kv_rank + self.rope_dim)
        return (self.n_attention, self.n_kv_heads, self.head_dim)

    @property
    def window_geometry(self):
        """(window layers, K/V heads, head size, window) of the SECOND
        group of K/V layers: the layers that attend their last
        ``window`` keys only, whose blocks a cache gives back once every
        query that could read them has passed (their own pools, table
        and allocator: inference/cache.py).  None: every K/V layer
        keeps its whole context (``kv_geometry``'s one group)."""
        if not self.n_window:
            return None
        return (self.n_window, self.n_kv_heads, self.head_dim, self.window)

    @property
    def value_lanes(self):
        """None: keys and values are two pools.  An int: there is ONE
        pool, and a token's values are the first ``value_lanes`` lanes
        of its key (latent attention: the latent is both)."""
        return self.kv_rank if self.n_latent else None

    @property
    def state_geometry(self) -> tuple:
        """Per row and recurrent layer: (layers, conv state shape,
        recurrent state shape).  The SSM state ``[heads, head width,
        state]`` is STORED with heads and head width folded into one
        dim: a pool whose trailing dims are ``(8192, 128)`` has one
        natural tiling, so no program re-lays the whole pool out to
        suit its own products (the chunk program did, a 2.4 GB copy,
        when the three dims were kept apart).  The delta rule's matrix
        state ``[heads, values, keys]`` is stored transposed with the
        heads folded into the minor dim for the same reason, ``[keys,
        heads * values]`` (``(96, 5760)``: whole tiles; ops/
        delta_rule.py).  The short convolution has NO recurrent state:
        its third entry is None, the convolution's last inputs are the
        whole of it.  None for a model without recurrent layers: its
        whole past is blocks."""
        if self.n_short_conv:
            return (self.n_short_conv,
                    (self.conv_width - 1, self.d_model), None)
        if self.n_linear:
            return (self.n_linear,
                    (self.conv_width - 1, self.lin_conv_channels),
                    (self.lin_key_dim, self.lin_heads * self.lin_value_dim))
        if not self.n_mamba:
            return None
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


def _refuse_unless(*checks) -> None:
    """``(key, value got, values with a form)`` each: the first whose
    value has no form is refused by name."""
    for key, got, ok in checks:
        if got not in ok:
            raise ValueError(f"{key} = {got!r} is not implemented (only "
                             f"{' / '.join(map(repr, ok))})")


def _latent_keys(c: dict) -> dict:
    """``HybridConfig`` fields from ``deepseek_v2`` keys, and from
    ``xing4_0``'s, which are those plus sigmoid scores chosen by ``score
    + bias`` (``noaux_tc``, one group), ``hc_mult`` residual streams and
    ``num_nextn_predict_layers``.  What the layer function has no form
    for is refused here, by name."""
    rs = c.get("rope_scaling") or {}
    sigmoid = c.get("scoring_func", "softmax") == "sigmoid"
    _refuse_unless(
        ("rope_scaling.type", rs.get("type"), ("yarn",)),
        ("scoring_func / topk_method",
         (c.get("scoring_func", "softmax"), c.get("topk_method", "greedy")),
         (("softmax", "greedy"), ("softmax", "group_limited_greedy"),
          ("sigmoid", "noaux_tc"))),
        *((("n_group", c.get("n_group", 1), (1,)),
           ("topk_group", c.get("topk_group", 1), (1,)),
           ("norm_topk_prob", c["norm_topk_prob"], (True,)))
          if sigmoid else ()),
        ("moe_layer_freq", c.get("moe_layer_freq", 1), (1,)),
        ("tie_word_embeddings", c.get("tie_word_embeddings", False),
         (False,)),
        ("attention_bias", c.get("attention_bias", False), (False,)))
    if not c.get("q_lora_rank"):
        raise ValueError("q_lora_rank = None (a full-rank query "
                         "projection) is not implemented")
    yarn = Yarn(theta=c["rope_theta"], factor=rs["factor"],
                original_max=rs["original_max_position_embeddings"],
                beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
                mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"])
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    if sigmoid:
        # (the no-group sigmoid form is ``afmoe``'s: its own eps)
        more = dict(sigmoid_router=True, route_eps=1e-20, route_groups=())
    elif c.get("topk_method") == "group_limited_greedy":
        more = dict(route_groups=(c["n_group"], c["topk_group"]))
    else:
        more = dict(route_groups=(1, 1))
    if c.get("hc_mult", 1) > 1:
        more.update(hc_mult=c["hc_mult"],
                    hc_sinkhorn_iters=c["hc_sinkhorn_iters"],
                    hc_eps=c["hc_eps"],
                    hc_res_clamp=(c["mhc_h_res_clamp_min"],
                                  c["mhc_h_res_clamp_max"]))
    return dict(
        **more, mtp_layers=c.get("num_nextn_predict_layers", 0),
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        layer_types=(LATENT,) * c["num_hidden_layers"],
        n_heads=c["num_attention_heads"], n_kv_heads=1,
        head_dim=c["qk_nope_head_dim"], rope_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"], q_rank=c["q_lora_rank"],
        kv_rank=c["kv_lora_rank"], yarn=yarn,
        n_experts=c["n_routed_experts"],
        experts_per_token=c["num_experts_per_tok"],
        expert_width=c["moe_intermediate_size"],
        shared_width=c["n_shared_experts"] * c["moe_intermediate_size"],
        experts_held=(0, c["n_routed_experts"]),
        experts_in_every_layer=True, gated_experts=True,
        routed_scale=c["routed_scaling_factor"],
        norm_topk=c["norm_topk_prob"],
        dense_layers=min(c["first_k_dense_replace"],
                         c["num_hidden_layers"]),
        dense_width=c["intermediate_size"], tied_head=False,
        embedding_multiplier=1.0,
        attention_multiplier=qk ** -0.5 * yarn.softmax_mscale ** 2,
        residual_multiplier=1.0, logits_scaling=1.0,
        rms_eps=c["rms_norm_eps"], max_seq=c["max_position_embeddings"])


def _olmo_hybrid_keys(c: dict) -> dict:
    """``HybridConfig`` fields from ``olmo_hybrid`` keys.  What the
    layer function has no form for is refused here, by name."""
    kinds = {"linear_attention": LINEAR, "full_attention": ATTENTION}
    types = c["layer_types"][:c["num_hidden_layers"]]
    _refuse_unless(
        ("rope_parameters.rope_theta",
         (c.get("rope_parameters") or {}).get("rope_theta"), (None,)),
        ("linear_num_key_heads", c["linear_num_key_heads"],
         (c["linear_num_value_heads"],)),
        ("linear_allow_neg_eigval", c.get("linear_allow_neg_eigval"),
         (True,)),
        ("hidden_act", c.get("hidden_act", "silu"), ("silu",)),
        ("tie_word_embeddings", c.get("tie_word_embeddings", False),
         (False,)),
        ("attention_bias", c.get("attention_bias", False), (False,)),
        *(("layer_types", t, tuple(kinds)) for t in types))
    head_dim = c["hidden_size"] // c["num_attention_heads"]
    return dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        layer_types=tuple(kinds[t] for t in types),
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=head_dim,
        lin_heads=c["linear_num_value_heads"],
        lin_key_dim=c["linear_key_head_dim"],
        lin_value_dim=c["linear_value_head_dim"],
        conv_width=c["linear_conv_kernel_dim"],
        # every layer is its mixer THEN the dense gated MLP: no experts
        experts_in_every_layer=True, dense_layers=len(types),
        dense_width=c["intermediate_size"], tied_head=False,
        qk_norm=True, norm_output=True,
        embedding_multiplier=1.0, attention_multiplier=head_dim ** -0.5,
        residual_multiplier=1.0, logits_scaling=1.0,
        rms_eps=c["rms_norm_eps"], max_seq=c["max_position_embeddings"])


def _afmoe_keys(c: dict) -> dict:
    """``HybridConfig`` fields from ``afmoe`` keys.  What the layer
    function has no form for is refused here, by name."""
    kinds = {"sliding_attention": WINDOW, "full_attention": ATTENTION}
    types = c["layer_types"][:c["num_hidden_layers"]]
    _refuse_unless(
        ("rope_scaling", c.get("rope_scaling"), (None,)),
        ("score_func", c.get("score_func", "sigmoid"), ("sigmoid",)),
        ("route_norm", c.get("route_norm", True), (True,)),
        ("hidden_act", c.get("hidden_act", "silu"), ("silu",)),
        ("n_group", c.get("n_group", 1), (1,)),
        ("topk_group", c.get("topk_group", 1), (1,)),
        ("num_expert_groups", c.get("num_expert_groups", 1), (1,)),
        ("num_limited_groups", c.get("num_limited_groups", 1), (1,)),
        ("tie_word_embeddings", c.get("tie_word_embeddings", False),
         (False,)),
        ("attention_bias", c.get("attention_bias", False), (False,)),
        *(("layer_types", t, tuple(kinds)) for t in types))
    if c["num_shared_experts"] < 1:
        raise ValueError("num_shared_experts = 0 is not implemented")
    if "sliding_attention" not in types:
        raise ValueError("an afmoe model without a sliding_attention "
                         "layer is not implemented")
    return dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        layer_types=tuple(kinds[t] for t in types),
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        window=c["sliding_window"], rope_theta=float(c["rope_theta"]),
        qk_norm="head", attn_gate=True, sandwich_norm=True,
        n_experts=c["num_experts"],
        experts_per_token=c["num_experts_per_tok"],
        expert_width=c["moe_intermediate_size"],
        shared_width=c["num_shared_experts"] * c["moe_intermediate_size"],
        experts_held=(0, c["num_experts"]),
        experts_in_every_layer=True, gated_experts=True,
        sigmoid_router=True, route_eps=1e-20,
        routed_scale=c["route_scale"],
        dense_layers=min(c["num_dense_layers"], len(types)),
        dense_width=c["intermediate_size"], tied_head=False,
        embedding_multiplier=(math.sqrt(c["hidden_size"])
                              if c.get("mup_enabled") else 1.0),
        attention_multiplier=c["head_dim"] ** -0.5,
        residual_multiplier=1.0, logits_scaling=1.0,
        rms_eps=c["rms_norm_eps"], max_seq=c["max_position_embeddings"])


def _lfm2_keys(c: dict) -> dict:
    """``HybridConfig`` fields from ``lfm2_moe`` keys.  What the layer
    function has no form for is refused here, by name.  The published
    config carries no ``head_dim`` (``hidden_size / num_attention_
    heads``) and no ``tie_word_embeddings`` (tied: the published
    parameter count holds one embedding matrix)."""
    kinds = {"conv": SHORT_CONV, "full_attention": ATTENTION}
    types = c["layer_types"][:c["num_hidden_layers"]]
    _refuse_unless(
        ("conv_bias", c.get("conv_bias", False), (False,)),
        ("use_expert_bias", c.get("use_expert_bias", True), (True,)),
        ("norm_topk_prob", c.get("norm_topk_prob", True), (True,)),
        ("rope_scaling", c.get("rope_scaling"), (None,)),
        ("tie_word_embeddings", c.get("tie_word_embeddings", True),
         (True,)),
        ("hidden_act", c.get("hidden_act", "silu"), ("silu",)),
        ("attention_bias", c.get("attention_bias", False), (False,)),
        ("num_shared_experts", c.get("num_shared_experts", 0), (0,)),
        *(("layer_types", t, tuple(kinds)) for t in types))
    if c["conv_L_cache"] < 2:
        raise ValueError(f"conv_L_cache = {c['conv_L_cache']!r} is not "
                         f"implemented (a convolution keeps >= 1 input)")
    head_dim = c.get("head_dim") or (c["hidden_size"]
                                     // c["num_attention_heads"])
    return dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        layer_types=tuple(kinds[t] for t in types),
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=head_dim,
        conv_width=c["conv_L_cache"],
        rope_theta=float(c["rope_theta"]), rotary_full=True,
        qk_norm="head",
        n_experts=c["num_experts"],
        experts_per_token=c["num_experts_per_tok"],
        expert_width=c["moe_intermediate_size"], shared_width=0,
        experts_held=(0, c["num_experts"]),
        experts_in_every_layer=True, gated_experts=True,
        sigmoid_router=True, route_eps=1e-6,
        routed_scale=float(c["routed_scaling_factor"]),
        dense_layers=min(c["num_dense_layers"], len(types)),
        dense_width=c["intermediate_size"], tied_head=True,
        embedding_multiplier=1.0, attention_multiplier=head_dim ** -0.5,
        residual_multiplier=1.0, logits_scaling=1.0,
        rms_eps=c["norm_eps"], max_seq=c["max_position_embeddings"])


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
        if kind == LINEAR:
            Hl, Vl = cfg.lin_heads, cfg.lin_value_dim
            dt = jnp.exp(unif((Hl,), math.log(1e-3), math.log(1e-1)))
            return {
                "norm": jnp.ones((d,), pd),
                "wqkv": norm((d, cfg.lin_conv_channels)),
                "wg": norm((d, Hl * Vl)),
                "wab": norm((d, 2 * Hl)),
                "conv_w": unif((K, cfg.lin_conv_channels), -bound,
                               bound).astype(pd),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(unif((Hl,), 1.0, 16.0)),
                "gnorm": jnp.ones((Vl,), pd),
                "wo": norm((Hl * Vl, d)),
            }
        if kind == SHORT_CONV:
            return {
                "norm": jnp.ones((d,), pd),
                "in_proj": norm((d, 3 * d)),
                "conv_w": unif((K, d), -bound, bound).astype(pd),
                "out_proj": norm((d, d)),
            }
        if kind in (ATTENTION, WINDOW):
            # (with an output gate its projection rides the same product:
            # [q | k | v | gate], one read of the window a layer)
            ap = {
                "norm": jnp.ones((d,), pd),
                "wqkv": norm((d, hq + 2 * hkv
                              + (hq if cfg.attn_gate else 0))),
                "wo": norm((hq, d)),
            }
            if cfg.qk_norm == "head":
                ap["q_norm"] = jnp.ones((cfg.head_dim,), pd)
                ap["k_norm"] = jnp.ones((cfg.head_dim,), pd)
            elif cfg.qk_norm:
                ap["q_norm"] = jnp.ones((hq,), pd)
                ap["k_norm"] = jnp.ones((hkv,), pd)
            return ap
        if kind == LATENT:
            nh, dn, dr, dv = (cfg.n_heads, cfg.head_dim, cfg.rope_dim,
                              cfg.v_head_dim)
            return {
                "norm": jnp.ones((d,), pd),
                "wq_a": norm((d, cfg.q_rank)),
                "q_norm": jnp.ones((cfg.q_rank,), pd),
                "wq_nope": norm((nh, dn, cfg.q_rank)),
                "wq_rope": norm((dr, nh, cfg.q_rank)),
                "wkv_a": norm((d, cfg.kv_rank + dr)),
                "kv_norm": jnp.ones((cfg.kv_rank,), pd),
                "w_uk": norm((nh, cfg.kv_rank, dn)),
                "w_uv": norm((nh, cfg.kv_rank, dv)),
                "wo": norm((nh * dv, d)),
            }
        if kind == DENSE:
            return {
                "norm": jnp.ones((d,), pd),
                "w_in": norm((d, 2 * cfg.dense_width)),
                "w_out": norm((cfg.dense_width, d)),
            }
        ffn = {"norm": jnp.ones((d,), pd),
               "router": norm((d, cfg.n_experts))}
        if cfg.shared_width:
            ffn["shared_in"] = norm((d, halves * cfg.shared_width))
            ffn["shared_out"] = norm((cfg.shared_width, d))
        ffn["w_in"] = jnp.pad(
            norm((cfg.n_held, d, halves * cfg.expert_width)),
            [(0, 0), (0, 0), (0, pad)])
        ffn["w_out"] = norm((cfg.n_held, cfg.expert_width, d))
        if cfg.routes_by_sigmoid:
            ffn["router_bias"] = jax.random.normal(
                next(k), (cfg.n_experts,)) * 0.02
        return ffn

    def with_maps(sub, k):
        """A sublayer of the widened residual holds its three maps."""
        if cfg.hc_mult:
            from ray_tpu.ops import hyper_connections
            sub["hc"] = hyper_connections.init(next(k), cfg.hc_mult, d, pd)
        return sub

    def stream(key):
        return iter(jax.random.split(key, 16 if cfg.hc_mult else 12))

    keys = jax.random.split(rng, cfg.n_layers + 1)
    streams = [stream(key) for key in keys[1:]]
    layers = [{} for _ in cfg.layer_types]
    for i, kind in cfg.sublayers:
        layers[i][slot_of(kind)] = with_maps(sublayer(kind, streams[i]),
                                             streams[i])
        if cfg.sandwich_norm:
            layers[i][slot_of(kind)]["post_norm"] = jnp.ones((d,), pd)
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
    if cfg.mtp_layers:
        # a multi-token-prediction module: two norms and a projection
        # [2 d, d] in front of one more whole layer (its mixer, then
        # experts), a norm behind it; embedding and head are the model's
        params["mtp"] = []
        for j in range(cfg.mtp_layers):
            k = stream(jax.random.fold_in(rng, 2 + j))
            params["mtp"].append({
                "enorm": jnp.ones((d,), pd), "hnorm": jnp.ones((d,), pd),
                "eh_proj": (jax.random.normal(next(k), (2 * d, d))
                            * 0.02).astype(pd),
                "mixer": with_maps(sublayer(LATENT, k), k),
                "ffn": with_maps(sublayer(EXPERTS, k), k),
                "norm": jnp.ones((d,), pd)})
    return params


def slot_of(kind: str) -> str:
    """The key of a layer's dict that holds a sublayer of ``kind``."""
    return "ffn" if kind in (EXPERTS, DENSE) else "mixer"


def num_params(params) -> int:
    return sum(int(math.prod(p.shape)) for p in jax.tree.leaves(params))


def cast_at_use(params) -> list:
    """The leaves a program casts to the activations' dtype where it
    multiplies by them: every matrix (the convolution's taps are used in
    float32).  The family is published and held in that dtype, so the
    casts are no-ops and the tree is served as it is."""
    out = [params[n] for n in ("wte", "head") if n in params]
    names = {"mixer": ("in_proj", "out_proj", "wqkv", "wo", "wq_a",
                       "wq_nope", "wq_rope", "wkv_a", "w_uk", "w_uv",
                       "wg", "wab"),
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


def _ssm_core(cfg, mp, z, xBC, dt, state, n_valid):
    """The Mamba-2 mixer between its two projections, on ``in_proj``'s
    three outputs for the rows of ``state``.  -> (y [b, w, inner],
    state)."""
    conv_state, (ssm_pool, ssm_layer) = state
    b, w, _ = z.shape
    di, H, P, N, G = (cfg.ssm_inner, cfg.ssm_heads, cfg.ssm_head_dim,
                      cfg.ssm_state, cfg.ssm_groups)
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
         * mp["gnorm"].astype(jnp.float32)).astype(z.dtype)
    return y, (conv_state, (ssm_pool, ssm_layer))


# The two-part window's program calls what repeats layer after layer
# through ONE jitted function each: equal layers are traced and lowered
# once, XLA inlines the calls.  On the chip, from a warm compile cache,
# the fused program then adds 0.6-0.8 s to granite's set-up and 1.8-2.1
# to nemotron's; without, 3.7-5.0 and 3.7 (PR 46, PERF.md section 6).
# The one-part programs call the functions themselves and compile to
# what they always did: doing it for all three is ROADMAP A8's.
_SSM_CORE_PARAMS = ("conv_w", "conv_b", "dt_bias", "A_log", "D", "gnorm")
_ssm_core_once = jax.jit(_ssm_core, static_argnums=0)


def _mamba_mixer(cfg, mp, h, state, n_valid, rows: int = 0):
    """h [b, w, d]; state (conv [b, K-1, C], ssm): the rows' SSM state
    as ``ops/ssm.ssd`` addresses it, (pool [L, b, H * P, N] f32, layer).
    -> (out [b, w, d], state).

    With ``rows`` the window is in two parts (``block``): h [1, rows +
    w, d], ``n_valid`` [rows + 1], and ``state`` (conv, ssm, row): the
    one-token rows' state as above and WHICH of those rows the window
    of ``w`` tokens belongs to (it sits the step out: ``n_valid`` 0).
    The projections are one product each over the whole window; what
    lies between them runs a part at a time in the form that part has
    alone: the one-token kernel on the pool, then the chunked scan on
    that row's state, read from the pool the kernel left (the pool stays
    ONE chain of updates in place: read before the kernel's update it
    is a second reader of the kernel's operand, and a copy of the
    pool, 2.4 GB compiled for the chip)."""
    di = cfg.ssm_inner
    with jax.named_scope("mixer_ssm_proj"):
        zxd = jnp.dot(h, mp["in_proj"].astype(h.dtype))
        if rows:
            # ONE materialisation: with the parts' six consumers the
            # compiler computes the product again for most of them, the
            # weights read each time (25 evaluations in 9 layers, 4 ms
            # of a 29 ms program, read on the chip, PR 46)
            zxd = jax.lax.optimization_barrier(zxd)
        z, xBC, dt = jnp.split(zxd, [di, di + cfg.conv_channels], axis=-1)
    with jax.named_scope("mixer_ssm"):
        if rows:
            conv0, ssm0, row = state
            core = {k: mp[k] for k in _SSM_CORE_PARAMS}
            # [1, rows + w, .] -> [rows, 1, .] and [1, w, .]
            y1, (conv, (pool, layer)) = _ssm_core_once(
                cfg, core, *(t[0, :rows, None] for t in (z, xBC, dt)),
                (conv0, ssm0), n_valid[:rows])
            at = (layer, row, 0, 0)
            own = jax.lax.dynamic_slice(pool, at, (1, 1) + pool.shape[2:])
            yw, (own_conv, (own, _)) = _ssm_core_once(
                cfg, core, *(t[:, rows:] for t in (z, xBC, dt)),
                (jax.lax.dynamic_slice_in_dim(conv0, row, 1), (own, 0)),
                n_valid[rows:])
            state = (conv.at[row].set(own_conv[0]),
                     (jax.lax.dynamic_update_slice(pool, own, at), layer),
                     row)
            y = jnp.concatenate([y1[None, :, 0], yw], axis=1)
        else:
            y, state = _ssm_core(cfg, mp, z, xBC, dt, state, n_valid)
    with jax.named_scope("mixer_ssm_proj"):
        out = jnp.dot(y, mp["out_proj"].astype(h.dtype))
    return out, state


def _short_conv_core(cfg, mp, bcu, state, n_valid):
    """The short convolution between its two projections, on
    ``in_proj``'s output for the rows of ``state`` (conv [b, L-1, d],
    ``marks`` [b, J] or None).  -> (y [b, w, d], (conv, the state after
    each row's first ``marks`` tokens [b, J, L-1, d] or None))."""
    conv, marks = state
    K1, f32 = cfg.conv_width - 1, jnp.float32
    B, C, u = jnp.split(bcu, 3, axis=-1)
    v = (B.astype(f32) * u.astype(f32)).astype(bcu.dtype)
    if bcu.shape[1] == 1 and marks is None:
        c, conv = short_conv.conv_step(v[:, 0], conv, mp["conv_w"], n_valid)
        c, marked = c[:, None], None
    else:
        c, full = short_conv.conv_window(v, conv, mp["conv_w"])
        conv = short_conv.state_at(full, n_valid, K1).astype(conv.dtype)
        marked = None if marks is None else jax.vmap(
            lambda row, m: row[m[:, None] + jnp.arange(K1)])(full, marks)
    y = (C.astype(f32) * c.astype(f32)).astype(bcu.dtype)
    return y, (conv, marked)


def _short_conv_mixer(cfg, mp, h, state, n_valid, rows: int = 0):
    """h [b, w, d]; state (conv [b, L-1, d], ``marks``): the rows' last
    L-1 values of ``v`` and, of a window, the token counts [b, J] after
    which a cache wants the state kept (None: none).  -> (out [b, w, d],
    (conv, the states at the marks [b, J, L-1, d] or None)).

    With ``rows`` the window is in two parts (``block``): h [1, rows +
    w, d], ``n_valid`` [rows + 1], ``state`` (conv [rows, L-1, d],
    marks [1, J], row): the one-token rows' state, the marks of the
    window of ``w`` tokens and WHICH of those rows it belongs to (it
    sits the step out: ``n_valid`` 0).  The two projections are one
    product each over the whole window; the taps run a part at a time
    in the form that part has alone."""
    with jax.named_scope("mixer_short_conv"):
        with jax.named_scope("short_conv_in_proj"):
            bcu = jnp.dot(h, mp["in_proj"].astype(h.dtype))
            # ONE materialisation (see ``_mamba_mixer``)
            bcu = jax.lax.optimization_barrier(bcu)
        with jax.named_scope("short_conv_taps"):
            if rows:
                conv0, marks, row = state
                y1, (conv, _) = _short_conv_core(
                    cfg, mp, bcu[0, :rows, None], (conv0, None),
                    n_valid[:rows])
                yw, (own, marked) = _short_conv_core(
                    cfg, mp, bcu[:, rows:],
                    (jax.lax.dynamic_slice_in_dim(conv0, row, 1), marks),
                    n_valid[rows:])
                state = (jax.lax.dynamic_update_slice_in_dim(
                    conv, own, row, 0), marked)
                y = jnp.concatenate([y1[None, :, 0], yw], axis=1)
            else:
                y, state = _short_conv_core(cfg, mp, bcu, state, n_valid)
        with jax.named_scope("short_conv_out_proj"):
            out = jnp.dot(y, mp["out_proj"].astype(h.dtype))
    return out, state


def _unit(x, eps: float = 1e-6):
    """x [..., K] float32 scaled to unit length."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _linear_core(cfg, lp, qkv, ab, state, n_valid):
    """The delta-rule mixer between its projections, on ``wqkv``'s and
    ``wab``'s outputs for the rows of ``state``.  -> (o [b, w, H, V]
    float32, state)."""
    conv_state, (pool, layer) = state
    b, w, _ = qkv.shape
    H, K, V = cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim
    f32 = jnp.float32
    qkv, conv_state = ssm.causal_conv(qkv, conv_state, lp["conv_w"],
                                      None, n_valid)
    # (one materialisation again: q's, k's and v's consumers each
    # computed the taps over the whole [chunk, 11520] otherwise, 30
    # evaluations in 12 layers, compiled for the chip, PR 45)
    qkv = jax.lax.optimization_barrier(qkv)
    q, k, v = jnp.split(qkv, [H * K, 2 * H * K], axis=-1)
    q = _unit(q.reshape(b, w, H, K).astype(f32)) * K ** -0.5
    k = _unit(k.reshape(b, w, H, K).astype(f32))
    a, beta = ab[..., :H], 2.0 * jax.nn.sigmoid(ab[..., H:])
    g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(a + lp["dt_bias"])
    o, pool = delta_rule.delta_rule(q, k, v.reshape(b, w, H, V), g,
                                    beta, pool, layer, n_valid)
    return o, (conv_state, (pool, layer))


def _linear_mixer(cfg, lp, h, state, n_valid, rows: int = 0):
    """h [b, w, d]; state (conv [b, K-1, C], matrix): the rows' matrix
    state as ``ops/delta_rule.delta_rule`` addresses it, (pool [L, b,
    keys, heads * values] f32, layer).  -> (out [b, w, d], state).

    With ``rows`` the window is in two parts (``block``): h [1, rows +
    w, d], ``n_valid`` [rows + 1], and ``state`` (conv, matrix, row) as
    ``_mamba_mixer`` takes it.  The four projections are one product
    each over the whole window, the gated norm runs over both parts at
    once; between them the one-token kernel advances the rows' state
    where it lies in the pool (the window's row sits it out: ``n_valid``
    0, not visited), THEN the window kernel that row's state, read from
    the pool the step left and written back there: the pool stays ONE
    chain of updates in place (``_mamba_mixer``)."""
    H, V = cfg.lin_heads, cfg.lin_value_dim
    f32 = jnp.float32
    with jax.named_scope("mixer_linear_proj"):
        qkv = jnp.dot(h, lp["wqkv"].astype(h.dtype))
        gate = jnp.dot(h, lp["wg"].astype(h.dtype))
        ab = jnp.dot(h, lp["wab"].astype(h.dtype),
                     preferred_element_type=f32)
        # ONE materialisation each: the chunk program otherwise computes
        # the [chunk, 11520] product again for every consumer (the taps,
        # the splits: 4-5 x 0.47 ms a layer, read on the chip, PR 44)
        qkv, gate = jax.lax.optimization_barrier((qkv, gate))
    with jax.named_scope("mixer_linear_attention"):
        if rows:
            conv0, matrix0, row = state
            # [1, rows + w, .] -> [rows, 1, .] and [1, w, .]
            o1, (conv, (pool, layer)) = _linear_core(
                cfg, lp, qkv[0, :rows, None], ab[0, :rows, None],
                (conv0, matrix0), n_valid[:rows])
            at = (layer, row, 0, 0)
            own = jax.lax.dynamic_slice(pool, at, (1, 1) + pool.shape[2:])
            ow, (own_conv, (own, _)) = _linear_core(
                cfg, lp, qkv[:, rows:], ab[:, rows:],
                (jax.lax.dynamic_slice_in_dim(conv0, row, 1), (own, 0)),
                n_valid[rows:])
            state = (conv.at[row].set(own_conv[0]),
                     (jax.lax.dynamic_update_slice(pool, own, at), layer),
                     row)
            o = jnp.concatenate([o1[None, :, 0], ow], axis=1)
        else:
            o, state = _linear_core(cfg, lp, qkv, ab, state, n_valid)
        y = _rms_norm(o, lp["gnorm"], cfg.rms_eps).reshape(
            *o.shape[:2], H * V) * jax.nn.silu(gate.astype(f32))
    with jax.named_scope("mixer_linear_proj"):
        out = jnp.dot(y.astype(h.dtype), lp["wo"].astype(h.dtype))
    return out, state


# the two-part window's: equal layers traced once (``_ssm_core_once``)
_linear_once = jax.jit(_linear_mixer, static_argnums=(0, 5))


def _attention_mixer(cfg, ap, h, attend, tables=None,
                     scope: str = "mixer_attention"):
    """h [b, w, d]; ``attend(q [b, h, w, hd], k, v [b, w, hkv, hd]) ->
    o [b, h, w, hd]`` supplies the keys of the past (and, of a window
    layer, forgets those behind the window); ``tables``: the rotary
    tables at the window's positions where the layer turns q and k (all
    ``head_dim`` lanes, half-split pairs) — the turned key is what
    ``attend`` is handed, and what a cache keeps."""
    b, w, _ = h.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope(scope):
        qkv = jnp.dot(h, ap["wqkv"].astype(h.dtype))
        q, k, v, *gate = jnp.split(
            qkv, [nh * hd, (nh + nkv) * hd, (nh + 2 * nkv) * hd][
                :3 if cfg.attn_gate else 2], axis=-1)
        if cfg.qk_norm == "head":
            q, k, v, *gate = jax.lax.optimization_barrier((q, k, v, *gate))
            q = _rms_norm(q.reshape(b, w, nh, hd), ap["q_norm"],
                          cfg.rms_eps)
            k = _rms_norm(k.reshape(b, w, nkv, hd), ap["k_norm"],
                          cfg.rms_eps)
        elif cfg.qk_norm:
            # (one materialisation: see ``_linear_mixer``)
            q, k, v = jax.lax.optimization_barrier((q, k, v))
            q = _rms_norm(q, ap["q_norm"], cfg.rms_eps)
            k = _rms_norm(k, ap["k_norm"], cfg.rms_eps)
        q, k = q.reshape(b, w, nh, hd), k.reshape(b, w, nkv, hd)
        if tables is not None:
            with jax.named_scope("rotary"):
                q, k = rotate(q, tables), rotate(k, tables)
        o = attend(q.transpose(0, 2, 1, 3), k, v.reshape(b, w, nkv, hd))
        o = o.transpose(0, 2, 1, 3).reshape(b, w, nh * hd)
        if cfg.attn_gate:
            with jax.named_scope("attention_gate"):
                o = (o.astype(jnp.float32) * jax.nn.sigmoid(
                    gate[0].astype(jnp.float32))).astype(h.dtype)
        return jnp.dot(o, ap["wo"].astype(h.dtype))


def rotary_tables(cfg: HybridConfig, positions):
    """positions [...] int -> (cos, sin) [..., rope_dim / 2] float32 at
    the YaRN-scaled frequencies — or [..., head_dim / 2] at the plain
    ones of ``rope_theta`` (no scaling), which turn the window layers'
    whole heads (and, under ``rotary_full``, the attention layers');
    None for a model without rotary positions."""
    if cfg.rope_theta and cfg.yarn is None:
        with jax.named_scope("rotary"):
            inv = cfg.rope_theta ** (
                -jnp.arange(0, cfg.head_dim, 2, dtype=jnp.float32)
                / cfg.head_dim)
            ang = positions.astype(jnp.float32)[..., None] * inv
            return jnp.cos(ang), jnp.sin(ang)
    if cfg.yarn is None:
        return None
    with jax.named_scope("rotary"):
        inv = jnp.asarray(cfg.yarn.inv_freq(cfg.rope_dim), jnp.float32)
        ang = positions.astype(jnp.float32)[..., None] * inv
        m = cfg.yarn.table_mscale
        return jnp.cos(ang) * m, jnp.sin(ang) * m


def rotate(x, tables):
    """x [..., w, (heads,) rope_dim] turned by ``tables`` [..., w,
    rope_dim / 2]: pair j is lanes (j, j + rope_dim / 2)."""
    cos, sin = tables
    if x.ndim == cos.ndim + 1:                 # a heads dim before lanes
        cos, sin = cos[..., None, :], sin[..., None, :]
    xf = x.astype(jnp.float32)
    a, b = jnp.split(xf, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _latent_mixer(cfg, ap, h, attend, tables):
    """h [b, w, d]; ``tables`` the rotary tables at the window's
    positions; ``attend(q_nope [b, w, h, dn], q_rope [b, w, h, dr],
    latent [b, w, kv_rank + dr], w_uk [h, kv_rank, dn], w_uv [h,
    kv_rank, dv]) -> o [b, w, h * dv]`` supplies the latents of the
    past, and chooses the form of the product.

    The published ``W_qb`` is held as its no-position and its rope
    columns apart (``wq_nope`` [h, dn, q_rank] and ``wq_rope`` [dr, h,
    q_rank], the rank minor; the rope lanes before the heads, as the
    rotation splits them) and ``W_kvb`` a head, keys and values
    apart (``w_uk``, ``w_uv`` [h, kv_rank, .]): handed the fused
    matrices, the one-token program re-laid each out every pass to slice
    it and to bring the heads forward for the absorbed product (75 + 33
    MB of copies a layer in the described-chip compile)."""
    b, w, _ = h.shape
    nh, dn, dr = cfg.n_heads, cfg.head_dim, cfg.rope_dim
    with jax.named_scope("mixer_latent_proj"):
        cq = _rms_norm(jnp.dot(h, ap["wq_a"].astype(h.dtype)),
                       ap["q_norm"], cfg.rms_eps)
        q_nope = jnp.einsum("bwr,hdr->bwhd", cq,
                            ap["wq_nope"].astype(h.dtype))
        q_rope = jnp.einsum("bwr,dhr->bwhd", cq,
                            ap["wq_rope"].astype(h.dtype))
        ckv = jnp.dot(h, ap["wkv_a"].astype(h.dtype))
        c = _rms_norm(ckv[..., :cfg.kv_rank], ap["kv_norm"], cfg.rms_eps)
    with jax.named_scope("rotary"):
        q_rope = rotate(q_rope, tables)
        k_rope = rotate(ckv[..., cfg.kv_rank:], tables)
    with jax.named_scope("mixer_latent_attention"):
        o = attend(q_nope, q_rope, jnp.concatenate([c, k_rope], axis=-1),
                   ap["w_uk"].astype(h.dtype), ap["w_uv"].astype(h.dtype))
    with jax.named_scope("mixer_latent_proj"):
        return jnp.dot(o, ap["wo"].astype(h.dtype))


def _dense(cfg, fp, h):
    b, w, d = h.shape
    with jax.named_scope("dense_mlp"):
        return mlp(h.reshape(b * w, d), fp["w_in"],
                   fp["w_out"]).reshape(b, w, d)


def _experts(cfg, fp, h, valid):
    """h [b, w, d]; ``valid`` [b, w] marks the real tokens, or [parts,
    b, w] the real tokens of each part of the window -> (routed +
    shared [b, w, d] — routed alone where ``shared_width`` is 0 —
    counts [E_held], total; [parts, E_held] and [parts] of a window in
    parts)."""
    b, w, d = h.shape
    flat = h.reshape(b * w, d)
    with jax.named_scope("routed_experts"):
        routed, counts, total = routed_experts(
            flat, fp["router"], fp["w_in"], fp["w_out"],
            top_k=cfg.experts_per_token, held=cfg.experts_held,
            valid=valid.reshape(*valid.shape[:-2], b * w),
            gated=cfg.gated_experts,
            bias=fp.get("router_bias"), scale=cfg.routed_scale,
            groups=cfg.route_groups, normalise=cfg.norm_topk,
            eps=cfg.route_eps)
    if cfg.shared_width:
        with jax.named_scope("shared_expert"):
            routed = routed + mlp(flat, fp["shared_in"], fp["shared_out"],
                                  cfg.gated_experts)
    return routed.reshape(b, w, d), counts, total


_experts_once = jax.jit(_experts, static_argnums=0)   # see ``_ssm_core_once``


def block(cfg: HybridConfig, kind: str, lp, x, past, n_valid,
          rows: int = 0):
    """ONE residual sublayer on a window: x [b, w, d], ``n_valid`` [b]
    real tokens a row; ``lp`` its parameters.  ``past`` is the row's
    state for a Mamba, linear or short-convolution sublayer (returned
    updated), the ``attend`` function for an attention sublayer and
    (``attend``, rotary tables) for a latent or a window-attention one —
    and for an attention one under ``rotary_full`` — (returned as they
    came) and unused by experts and the dense MLP.
    -> (x, past, (counts [E_held], total) of an experts sublayer, else
        None).

    ``rows`` > 0: a window in TWO PARTS, x [1, rows + w, d] — ``rows``
    one-token rows (a decode step's) and then ONE row's window of ``w``
    tokens (a prefill chunk's) — with ``n_valid`` [rows + 1]: 0 / 1 a
    one-token row, then the window's real tokens.  Every product over
    ``d`` is then one product for both parts.  A Mamba or linear
    sublayer's ``past`` is the one-token rows' state and which of them
    the window belongs to, (conv, ssm or matrix, row) — a short
    convolution's (conv, marks, row); an attention sublayer's ``attend``
    treats the two parts apart itself (``decode.paged_attend`` with a
    length a row AND the window's positions); experts count the parts
    apart: counts [2, E_held], total [2].  Latent and window attention
    have no such form."""
    carried = None
    if cfg.hc_mult:
        # x [b, w, n, d]: the sublayer sees ONE stream, mixed from the n
        from ray_tpu.ops import hyper_connections
        x, carried = hyper_connections.mix_in(
            x, lp["hc"], iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
            clamp=cfg.hc_res_clamp)
    h = x if cfg.norm_output else _rms_norm(x, lp["norm"], cfg.rms_eps)
    load = None
    if rows and kind not in TWO_PART:
        raise ValueError(f"a {kind} sublayer has no two-part form")
    if kind == MAMBA:
        mix, past = _mamba_mixer(cfg, lp, h, past, n_valid, rows)
    elif kind == LINEAR:
        mix, past = (_linear_once if rows else _linear_mixer)(
            cfg, lp, h, past, n_valid, rows)
    elif kind == SHORT_CONV:
        mix, past = _short_conv_mixer(cfg, lp, h, past, n_valid, rows)
    elif kind == ATTENTION:
        mix = _attention_mixer(cfg, lp, h,
                               *(past if cfg.rotary_full else (past,)),
                               scope="mixer_full_attention" if cfg.n_window
                               else "mixer_attention")
    elif kind == WINDOW:
        mix = _attention_mixer(cfg, lp, h, *past,
                               scope="mixer_swa_attention")
    elif kind == LATENT:
        mix = _latent_mixer(cfg, lp, h, *past)
    elif kind == DENSE:
        mix = _dense(cfg, lp, h)
    else:
        if rows:
            at = jnp.arange(-rows, x.shape[1] - rows)   # the window's from 0
            valid = jnp.stack([
                jnp.pad(n_valid[:rows] > 0, (0, at.size - rows)),
                (at >= 0) & (at < n_valid[rows])])[:, None]
        else:
            valid = jnp.arange(x.shape[1])[None, :] < n_valid[:, None]
        mix, counts, total = (_experts_once if rows else _experts)(
            cfg, lp, h, valid)
        load = (counts, total)
    if cfg.norm_output:
        mix = _rms_norm(mix, lp["norm"], cfg.rms_eps)
    elif cfg.sandwich_norm:
        mix = _rms_norm(mix, lp["post_norm"], cfg.rms_eps)
    if carried is not None:
        return hyper_connections.mix_out(carried, mix), past, load
    return x + cfg.residual_multiplier * mix, past, load


def _embedding(cfg: HybridConfig, params, tokens):
    return (params["wte"][tokens].astype(jnp.float32)
            * cfg.embedding_multiplier).astype(cfg.dtype)


def split_streams(cfg: HybridConfig, x):
    """x [.., d] as what enters the first layer: itself, or under
    ``hc_mult`` n streams [.., n, d], each a copy of it."""
    if not cfg.hc_mult:
        return x
    return jnp.broadcast_to(x[..., None, :],
                            (*x.shape[:-1], cfg.hc_mult, x.shape[-1]))


def embed(cfg: HybridConfig, params, tokens):
    return split_streams(cfg, _embedding(cfg, params, tokens))


def merge_streams(cfg: HybridConfig, x):
    """What leaves the last layer as ONE stream [..., d]: under
    ``hc_mult`` the n streams' sum."""
    if not cfg.hc_mult:
        return x
    return x.astype(jnp.float32).sum(-2).astype(x.dtype)


def head(cfg: HybridConfig, params, x, norm=None):
    """x [..., d] ([..., n, d] under ``hc_mult``) -> logits [..., V]
    float32 over the held vocabulary; ``norm``: the final norm's weight
    where it is not the model's own (a prediction module's)."""
    h = _rms_norm(merge_streams(cfg, x),
                  params["norm_f"] if norm is None else norm, cfg.rms_eps)
    w = params["wte"].T if cfg.tied_head else params["head"]
    logits = jnp.dot(h, w.astype(h.dtype),
                     preferred_element_type=jnp.float32)
    return logits / cfg.logits_scaling


def run_layers(cfg: HybridConfig, params, x, n_valid, state_in: Callable,
               state_out: Callable, attend_for: Callable, positions=None,
               rows: int = 0, window_for: Callable = None):
    """The unrolled layer loop over a window.  ``state_in(mi)`` gives
    recurrent (Mamba, linear or short-convolution) layer ``mi``'s state
    for the window's rows, (conv, (state pool, layer)) — or (conv,
    marks) — as its mixer takes it, and
    ``state_out(mi, state)``
    takes it back; ``attend_for(ai)`` gives attention or latent layer
    ``ai``'s ``attend`` and ``window_for(wi)`` window-attention layer
    ``wi``'s (the two kinds count apart: they keep their K/V in
    different pools); ``positions`` [b, w] are the window's, read by
    rotary positions alone.  ``rows``: the window is in two parts
    (``block``: x [1, rows + w, d], ``n_valid`` [rows + 1], a Mamba
    layer's state with the window's row).
    -> (x, load [N_LOAD] int32: held assignments, all assignments, the
        busiest held expert's assignments and the held experts with at
        least one assignment, each summed over the experts sublayers;
        real tokens only; [2, N_LOAD] of a window in two parts, the
        one-token rows' then the window's)."""
    mi = ai = wi = 0
    load = jnp.zeros((2, N_LOAD) if rows else (N_LOAD,), jnp.int32)
    tables = (rotary_tables(cfg, positions)
              if cfg.n_latent or cfg.n_window or cfg.rotary_full else None)
    for i, kind in cfg.sublayers:
        lp = params["layers"][i]
        if kind == WINDOW:
            x, _, _ = block(cfg, kind, lp["mixer"], x,
                            (window_for(wi), tables), n_valid, rows)
            wi += 1
        elif kind == LATENT:
            x, _, _ = block(cfg, kind, lp["mixer"], x,
                            (attend_for(ai), tables), n_valid, rows)
            ai += 1
        elif kind == DENSE:
            x, _, _ = block(cfg, kind, lp["ffn"], x, None, n_valid, rows)
        elif kind in RECURRENT:
            x, state, _ = block(cfg, kind, lp["mixer"], x, state_in(mi),
                                n_valid, rows)
            state_out(mi, state)
            mi += 1
        elif kind == ATTENTION:
            x, _, _ = block(cfg, kind, lp["mixer"], x,
                            (attend_for(ai), tables) if cfg.rotary_full
                            else attend_for(ai), n_valid, rows)
            ai += 1
        else:
            x, _, (counts, total) = block(cfg, kind, lp["ffn"], x, None,
                                          n_valid, rows)
            load = load + jnp.stack(
                [counts.sum(-1), total, counts.max(-1),
                 (counts > 0).sum(-1, dtype=jnp.int32)], axis=-1)
    return x, load


def zero_state(cfg: HybridConfig, rows: int):
    """(conv, recurrent) state of ``rows`` rows that have seen
    nothing, the recurrent state as a pool of this one layer (a short
    convolution's: (conv, no marks))."""
    if cfg.state_geometry is None:
        return None
    _, conv, ssm_shape = cfg.state_geometry
    if ssm_shape is None:
        return jnp.zeros((rows, *conv), cfg.dtype), None
    return (jnp.zeros((rows, *conv), cfg.dtype),
            (jnp.zeros((1, rows, *ssm_shape), jnp.float32), 0))


def causal_attend(cfg: HybridConfig, window: int = 0):
    """``attend`` over the window's own keys (a full sequence), in the
    form the model's attention layers take it; ``window`` > 0: each
    query over its last ``window`` keys only, its own among them."""
    if cfg.n_latent:
        def latent(q_nope, q_rope, lat, w_uk, w_uv):
            b, w = lat.shape[:2]
            pos = jnp.arange(w, dtype=jnp.int32)
            lat = jnp.pad(lat, ((0, 0), (0, -w % KEY_BLOCK), (0, 0)))
            return jnp.stack([latent_window_attention(
                q_nope[i], q_rope[i],
                lambda j, n, c=lat[i]: jax.lax.dynamic_slice_in_dim(
                    c, j * n, n),
                w_uk, w_uv, pos, scale=cfg.attention_multiplier,
                n_blocks=-(-w // KEY_BLOCK)) for i in range(b)])
        return latent
    rep = cfg.n_heads // cfg.n_kv_heads

    def attend(q, k, v):
        b, nh, w, hd = q.shape
        qg = q.reshape(b, cfg.n_kv_heads, rep, w, hd)
        logits = jnp.einsum("bgrqd,bkgd->bgrqk", qg, k,
                            preferred_element_type=jnp.float32) \
            * cfg.attention_multiplier
        mask = jnp.tril(jnp.ones((w, w), bool))
        if window:
            mask &= ~jnp.tril(jnp.ones((w, w), bool), -window)
        probs = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)
        o = jnp.einsum("bgrqk,bkgd->bgrqd", probs.astype(v.dtype), v)
        return o.reshape(b, nh, w, hd)
    return attend


def forward(params, tokens, cfg: HybridConfig, mtp: bool = False):
    """tokens [b, s] -> logits [b, s, V] float32: the layer function on
    one window of the whole sequence, from zero state.  ``mtp``: ->
    (logits, the prediction modules' logits: module k's [b, s - k, V],
    position t's guess at token t + k + 1)."""
    b, s = tokens.shape
    attend = causal_attend(cfg)
    within = causal_attend(cfg, cfg.window) if cfg.n_window else None
    x, _ = run_layers(
        cfg, params, embed(cfg, params, tokens),
        jnp.full((b,), s, jnp.int32),
        state_in=lambda mi: zero_state(cfg, b),
        state_out=lambda mi, state: None,
        attend_for=lambda ai: attend, window_for=lambda wi: within,
        positions=jnp.broadcast_to(jnp.arange(s), (b, s)))
    logits = head(cfg, params, x)
    if not mtp:
        return logits
    # DeepSeek-V3's form: module k joins the embedding of token t + k to
    # the stream the module before it (the model, for k = 1) left at t,
    # runs one more layer over the s - k positions and reads the SAME
    # head behind a norm of its own
    more = []
    for k, mp in enumerate(params.get("mtp", ()), 1):
        own = jnp.concatenate(
            [_rms_norm(_embedding(cfg, params, tokens[:, k:]), mp["enorm"],
                       cfg.rms_eps),
             _rms_norm(merge_streams(cfg, x)[:, :s - k], mp["hnorm"],
                       cfg.rms_eps)], axis=-1)
        x = split_streams(cfg, jnp.dot(own,
                                       mp["eh_proj"].astype(own.dtype)))
        n_valid = jnp.full((b,), s - k, jnp.int32)
        tables = rotary_tables(
            cfg, jnp.broadcast_to(jnp.arange(s - k), (b, s - k)))
        x, _, _ = block(cfg, LATENT, mp["mixer"], x, (attend, tables),
                        n_valid)
        x, _, _ = block(cfg, EXPERTS, mp["ffn"], x, None, n_valid)
        more.append(head(cfg, params, x, mp["norm"]))
    return logits, tuple(more)
