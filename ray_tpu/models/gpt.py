"""GPT: decoder-only transformer, TPU-first.

Flagship model of the framework (north-star config: GPT-2 124M DP×8, see
BASELINE.md).  Design choices are all MXU/HBM-driven:

  * params are a plain pytree with per-leaf *logical axes* — sharding is
    declarative (parallel.sharding rules map logical→mesh axes; pjit/XLA
    inserts the collectives).  dp/fsdp/tp/sp all come from the same
    forward function with different rules, no model rewrite.
  * layers are STACKED (leading ``layers`` dim) and the forward runs
    ``lax.scan`` over them: one compiled layer body regardless of depth,
    so compile time is O(1) in n_layers and XLA pipelines the weight
    loads.
  * attention dispatches to the pallas flash kernel on TPU, and to
    shard_map'd ring attention when the mesh has an ``sp`` axis (exact
    long-context attention, kv rotating over the ICI ring).
  * optional ``remat`` wraps the scanned body in jax.checkpoint —
    activation memory O(sqrt) trade per the HBM charter.
  * activations run in ``cfg.dtype`` (bf16 by default), params and the
    softmax/logsumexp accumulators in f32.

The reference has no analogue (it rides torch models); capability parity
target is the GPT-2 124M benchmark workload in
release/air_tests/air_benchmarks (SURVEY.md §6 north-star configs).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec

from ray_tpu.ops.attention import attention
from ray_tpu.ops.flash_attention import LANES
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.parallel.sharding import (DEFAULT_LLM_RULES, Rules, spec_for)


@dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304          # gpt-2 vocab padded to a multiple of 128
    max_seq: int = 1024
    d_model: int = 768
    n_heads: int = 12
    n_layers: int = 12
    d_ff: int = 3072
    dropout: float = 0.0             # framework trains with no dropout by default
    dtype: Any = jnp.bfloat16        # activation dtype (MXU-native)
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: Optional[str] = None   # None=full recompute, "dots"
    tie_embeddings: bool = True
    attn_impl: Optional[str] = None  # None=auto, "flash", "reference"
    attn_block_q: int = 512          # pallas flash tile sizes (fwd + bwd)
    attn_block_k: int = 512
    pp_microbatches: Optional[int] = None  # None = 2*pp stages (GPipe)
    # MoE (0 = dense MLP).  When n_experts > 0 every layer's MLP becomes
    # a top-k routed expert layer (GShard/Switch formulation: static
    # capacity, one-hot dispatch/combine einsums — the dispatch einsum
    # IS the all-to-all when experts are sharded over the ep mesh axis).
    n_experts: int = 0
    expert_top_k: int = 2
    capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01     # load-balance aux loss coefficient

    def __post_init__(self):
        if self.remat_policy not in (None, "dots"):
            raise ValueError(
                f"unknown remat_policy {self.remat_policy!r}; expected "
                "None (full recompute) or 'dots' (matmul outputs and the "
                "fused attention's output + logsumexp are kept, so the "
                "backward pass re-runs no matmul and no attention forward)")
        if self.n_experts:
            if not 1 <= self.expert_top_k <= self.n_experts:
                raise ValueError(
                    f"expert_top_k {self.expert_top_k} must be in "
                    f"[1, n_experts={self.n_experts}]")
            if self.capacity_factor <= 0:
                raise ValueError(
                    f"capacity_factor {self.capacity_factor} must be > 0")

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    # what a serving cache holds for this model (inference/cache.py)
    @property
    def kv_geometry(self) -> tuple:
        """(layers that keep K/V, K/V heads, head size)."""
        return (self.n_layers, self.n_heads, self.head_dim)

    state_geometry = None            # no recurrent state beside the K/V
    value_lanes = None               # keys and values are two pools

    @staticmethod
    def gpt2_124m(**kw) -> "GPTConfig":
        return GPTConfig(**{**dict(d_model=768, n_heads=12, n_layers=12,
                                   d_ff=3072, max_seq=1024), **kw})

    @staticmethod
    def tiny(**kw) -> "GPTConfig":
        """Test-sized config (CPU-mesh friendly)."""
        return GPTConfig(**{**dict(vocab_size=512, max_seq=128, d_model=64,
                                   n_heads=4, n_layers=2, d_ff=128,
                                   remat=False), **kw})

    @staticmethod
    def tiny_moe(**kw) -> "GPTConfig":
        """Test-sized mixture-of-experts config."""
        return GPTConfig.tiny(**{**dict(n_experts=4, expert_top_k=2,
                                        dtype=jnp.float32), **kw})


# -- params ----------------------------------------------------------------

# logical axes per leaf; "layers" is the scan dim, sharded over pp when
# the mesh has one (DEFAULT_LLM_RULES maps layers->pp; pruned to None on
# meshes without a pp axis).
PARAM_AXES = {
    "wte": ("vocab", "embed"),
    "wpe": (None, "embed"),
    "ln_f_scale": ("embed",),
    "ln_f_bias": ("embed",),
    "layers": {
        "ln1_scale": ("layers", "embed"),
        "ln1_bias": ("layers", "embed"),
        "wqkv": ("layers", "embed", "qkv"),
        "wo": ("layers", "heads", "embed"),  # [L, d, d]: in-dim is head-major
        "bo": ("layers", "embed"),
        "ln2_scale": ("layers", "embed"),
        "ln2_bias": ("layers", "embed"),
        "w_up": ("layers", "embed", "mlp"),
        "b_up": ("layers", "mlp"),
        "w_down": ("layers", "mlp", "embed"),
        "b_down": ("layers", "embed"),
    },
}


# MoE layers swap the dense MLP leaves for expert-stacked ones; the
# "expert" logical axis maps to the ep mesh axis (sharding.py rules)
MOE_MLP_AXES = {
    "w_router": ("layers", "embed", None),
    "w_up": ("layers", "expert", "embed", "mlp"),
    "b_up": ("layers", "expert", "mlp"),
    "w_down": ("layers", "expert", "mlp", "embed"),
    "b_down": ("layers", "expert", "embed"),
}


def param_logical_axes(cfg: GPTConfig, *, served: bool = False):
    """Logical axes of ``init_params``' tree, or with ``served`` of
    ``serving_params``' (which always carries the head's matrix)."""
    axes = dict(PARAM_AXES)
    if cfg.n_experts:
        axes["layers"] = {**axes["layers"], **MOE_MLP_AXES}
    if served or not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def init_params(cfg: GPTConfig, rng: jax.Array):
    """GPT-2 style init: N(0, 0.02), residual projections scaled by
    1/sqrt(2*n_layers)."""
    k = iter(jax.random.split(rng, 16))
    d, L, f = cfg.d_model, cfg.n_layers, cfg.d_ff
    std = 0.02
    res_std = std / math.sqrt(2 * L)
    pd = cfg.param_dtype

    def norm(key, shape, s=std):
        return (jax.random.normal(key, shape) * s).astype(pd)

    if cfg.n_experts:
        E = cfg.n_experts
        mlp = {
            "w_router": norm(next(k), (L, d, E)),
            "w_up": norm(next(k), (L, E, d, f)),
            "b_up": jnp.zeros((L, E, f), pd),
            "w_down": norm(next(k), (L, E, f, d), res_std),
            "b_down": jnp.zeros((L, E, d), pd),
        }
    else:
        mlp = {
            "w_up": norm(next(k), (L, d, f)),
            "b_up": jnp.zeros((L, f), pd),
            "w_down": norm(next(k), (L, f, d), res_std),
            "b_down": jnp.zeros((L, d), pd),
        }
    params = {
        "wte": norm(next(k), (cfg.vocab_size, d)),
        "wpe": norm(next(k), (cfg.max_seq, d), 0.01),
        "ln_f_scale": jnp.ones((d,), pd),
        "ln_f_bias": jnp.zeros((d,), pd),
        "layers": {
            "ln1_scale": jnp.ones((L, d), pd),
            "ln1_bias": jnp.zeros((L, d), pd),
            "wqkv": norm(next(k), (L, d, 3 * d)),
            "wo": norm(next(k), (L, d, d), res_std),
            "bo": jnp.zeros((L, d), pd),
            "ln2_scale": jnp.ones((L, d), pd),
            "ln2_bias": jnp.zeros((L, d), pd),
            **mlp,
        },
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = norm(next(k), (d, cfg.vocab_size))
    return params


# the per-layer leaves every program multiplies or adds in ``cfg.dtype``
# (``.astype(cfg.dtype)`` at use, in _transformer_layer and _moe_mlp);
# with the head's matrix, all of them
CAST_AT_USE = ("wqkv", "wo", "bo", "w_up", "b_up", "w_down", "b_down")


def cast_at_use(params) -> list:
    """The leaves of a tree that a serving program casts to
    ``cfg.dtype`` where it uses them (a no-op on a leaf stored so): the
    ``CAST_AT_USE`` leaves and the head's matrix, which without a leaf
    of its own is the whole embedding table."""
    head = params["lm_head"] if "lm_head" in params else params["wte"]
    return [params["layers"][name] for name in CAST_AT_USE] + [head]


def serving_params(params, cfg: GPTConfig):
    """The tree a serving engine hands its programs every pass, derived
    ONCE: each ``cast_at_use`` leaf stored in ``cfg.dtype``, so that no
    program begins by casting the stacked weights (at GPT-2 XL 6.1 GB
    of float32 read and 3.1 GB of bfloat16 written, every decode and
    every chunk program).  The products take the same rounding of the
    same weight either way: same bits out.

    Kept in their own precision: ``wte`` and ``wpe`` (the programs add
    them in it and THEN round; a rounded table would round twice), the
    LayerNorm leaves (``_layer_norm`` computes in float32), ``w_router``
    (cast to float32 at use); all but ``wte`` by identity.  A leaf
    already in ``cfg.dtype`` is returned as the same array.

    The head's matrix is always a leaf of its own, ``lm_head`` [d, V]
    (``_head`` takes it where a tree has one): with a tied embedding
    ``wte`` transposed and rounded.  ``wte`` is then only the table the
    programs gather token rows from, and its rows are padded with zeros
    to whole lanes (``_token_rows`` drops them): the chip keeps a
    ``[V, 1600]`` array column-major, a width of 1600 being no multiple
    of its 128 lanes, and every program began by re-tiling the table
    (322 MB in, 322 MB out) to gather 32 rows of it.  A width of whole
    lanes (768, or 1600 -> 1664) has the one row-major layout; such a
    ``wte`` is returned as the same array."""
    dt = jnp.dtype(cfg.dtype)

    def stored(a):
        return a if a.dtype == dt else a.astype(dt)

    layers = dict(params["layers"])
    for name in CAST_AT_USE:
        layers[name] = stored(layers[name])
    wte = params["wte"]
    head = (stored(params["lm_head"]) if "lm_head" in params
            else jax.jit(lambda w: w.T.astype(dt))(wte))
    pad = -wte.shape[1] % LANES
    if pad:
        wte = jnp.pad(wte, ((0, 0), (0, pad)))
    return {**params, "wte": wte, "layers": layers, "lm_head": head}


def num_params(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


# -- forward ---------------------------------------------------------------

def _layer_norm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def _constrain(x, logical, mesh, rules):
    if mesh is None:
        return x
    spec = spec_for(logical, rules, mesh)
    return lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


def _attend(q, k, v, cfg: GPTConfig, mesh: Optional[Mesh], rules: Rules):
    """[b, h, s, hd] attention; ring attention when seq is sp-sharded."""
    spec = (spec_for(("batch", "heads", "seq", "kv"), rules, mesh)
            if mesh is not None else None)
    if mesh is not None and mesh.shape.get("sp", 1) > 1:
        ring = partial(ring_attention, axis_name="sp", causal=True)
        return shard_map(ring, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
    return attention(q, k, v, causal=True, impl=cfg.attn_impl,
                     block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
                     mesh=mesh, spec=spec)


def _moe_mlp(y, lp, cfg: GPTConfig, mesh: Optional[Mesh], rules: Rules):
    """Top-k routed expert MLP, GShard/Switch formulation with groups.

    Tokens route in GROUPS (one group per sequence, the GShard device
    group): capacity is per group (C = cf·k·s/E), so the dispatch and
    combine tensors are [G, s, E, C] — O(s²) per group, with the group
    dim sharded over the data axes, NOT O(N²) global.  The dispatch
    einsum scatters tokens into each group's [E, C, d] buffer; with
    experts sharded over ``ep`` ("expert"→ep rule) that einsum IS the
    all-to-all, inserted by XLA, while expert compute stays sharded over
    the data axes on the group dim (green-field capability, SURVEY.md §7
    M4: the reference has no MoE engine).  Returns
    (output [b, s, d], load-balance aux loss scalar)."""
    b, s, d = y.shape                  # groups G = b, tokens/group n = s
    E, k = cfg.n_experts, cfg.expert_top_k
    C = max(1, int(math.ceil(cfg.capacity_factor * k * s / E)))

    logits = jnp.einsum("gnd,de->gne", y.astype(jnp.float32),
                        lp["w_router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)              # [G, n, E] f32

    remaining = probs
    counts = jnp.zeros((b, E), jnp.float32)   # per-group expert fill
    combine = jnp.zeros((b, s, E, C), jnp.float32)
    gates_sum = jnp.zeros((b, s), jnp.float32)
    top1_frac = None
    for i in range(k):
        idx = jnp.argmax(remaining, axis=-1)              # [G, n]
        mask = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # [G, n, E]
        gate = jnp.sum(remaining * mask, axis=-1)         # [G, n]
        # position of each token in its chosen expert's queue (0-based,
        # offset by earlier rounds' fill of this group's queues)
        pos = jnp.cumsum(mask, axis=1) - 1.0 + counts[:, None, :]
        posn = jnp.sum(pos * mask, axis=-1)               # [G, n]
        keep = (posn < C).astype(jnp.float32)             # capacity drop
        disp = (mask * keep[..., None])[..., None] \
            * jax.nn.one_hot(posn.astype(jnp.int32), C,
                             dtype=jnp.float32)[..., None, :]
        combine = combine + gate[..., None, None] * disp  # [G, n, E, C]
        gates_sum = gates_sum + gate * keep
        counts = counts + jnp.sum(mask * keep[..., None], axis=1)
        if i == 0:
            top1_frac = jnp.mean(mask, axis=(0, 1))       # [E]
        remaining = remaining * (1.0 - mask)
    # normalize the selected gates to sum to 1 per token (GShard)
    combine = combine / jnp.maximum(gates_sum, 1e-9)[..., None, None]
    dispatch = (combine > 0).astype(cfg.dtype)            # [G, n, E, C]

    # Switch load-balance loss: E * Σ_e f_e · P_e (f from the top-1
    # routing decision, P the mean router probability)
    aux = E * jnp.sum(top1_frac * jnp.mean(probs, axis=(0, 1)))

    yd = y.astype(cfg.dtype)
    expert_in = jnp.einsum("gnec,gnd->gecd", dispatch, yd)  # [G, E, C, d]
    expert_in = _constrain(expert_in, ("batch", "expert", None, "embed"),
                           mesh, rules)
    hid = jnp.einsum("gecd,edf->gecf", expert_in,
                     lp["w_up"].astype(cfg.dtype)) \
        + lp["b_up"].astype(cfg.dtype)[None, :, None, :]
    hid = _constrain(hid, ("batch", "expert", None, "mlp"), mesh, rules)
    hid = jax.nn.gelu(hid)
    out_e = jnp.einsum("gecf,efd->gecd", hid,
                       lp["w_down"].astype(cfg.dtype)) \
        + lp["b_down"].astype(cfg.dtype)[None, :, None, :]
    out_e = _constrain(out_e, ("batch", "expert", None, "embed"),
                       mesh, rules)
    out = jnp.einsum("gnec,gecd->gnd", combine.astype(cfg.dtype), out_e)
    return out, aux


def causal_attend(cfg: GPTConfig, mesh: Optional[Mesh], rules: Rules):
    """``attend`` over the window's own keys: a whole sequence, causal
    (training, the oracle, the full-width prefill)."""
    def attend(q, k, v):
        return _attend(q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                       cfg, mesh, rules)
    return attend


def _transformer_layer(x, lp, cfg: GPTConfig, mesh: Optional[Mesh],
                       rules: Rules, attend, return_kv: bool = False):
    """One pre-LN transformer block, THE statement of it: training and
    every serving program run this function on their window.  x
    [b, w, d], lp = one layer's params (no leading layers dim).
    ``attend(q [b, h, w, hd], k [b, w, h, hd], v [b, w, h, hd]) ->
    o [b, h, w, hd]`` supplies the keys of the past: ``causal_attend``
    has none but the window's own, a paged program's commits the
    window's K/V to its pool and reads the rows' tables back
    (inference/decode.paged_attend).  Returns (x, moe aux loss — 0 when
    dense); with ``return_kv`` also the per-head K/V ([b, h, w, hd]
    each) with which a full-width prefill seeds a cache.

    A MoE layer routes each token on its own, so a serving window routes
    as the whole sequence does; expert CAPACITY is per window (C =
    ceil(cf·k·w/E)), so a window's tokens equal the full forward's
    whenever capacity never binds (capacity_factor >= n_experts /
    expert_top_k guarantees it; a one-token window can never drop)."""
    b, w, _ = x.shape
    h, hd = cfg.n_heads, cfg.head_dim

    y = _layer_norm(x, lp["ln1_scale"], lp["ln1_bias"])
    qkv = jnp.einsum("bsd,de->bse", y, lp["wqkv"].astype(cfg.dtype))
    qkv = _constrain(qkv, ("batch", "seq", "qkv"), mesh, rules)
    q, k, v = (t.reshape(b, w, h, hd) for t in jnp.split(qkv, 3, axis=-1))
    o = attend(q.transpose(0, 2, 1, 3), k, v)
    o = o.transpose(0, 2, 1, 3).reshape(b, w, cfg.d_model)
    o = jnp.einsum("bsd,de->bse", o, lp["wo"].astype(cfg.dtype)) \
        + lp["bo"].astype(cfg.dtype)
    x = x + o
    x = _constrain(x, ("batch", "seq", "embed"), mesh, rules)

    y = _layer_norm(x, lp["ln2_scale"], lp["ln2_bias"])
    if cfg.n_experts:
        dn, aux = _moe_mlp(y, lp, cfg, mesh, rules)
    else:
        u = jnp.einsum("bsd,df->bsf", y, lp["w_up"].astype(cfg.dtype)) \
            + lp["b_up"].astype(cfg.dtype)
        u = _constrain(u, ("batch", "seq", "mlp"), mesh, rules)
        u = jax.nn.gelu(u)
        dn = jnp.einsum("bsf,fd->bsd", u, lp["w_down"].astype(cfg.dtype)) \
            + lp["b_down"].astype(cfg.dtype)
        aux = jnp.zeros((), jnp.float32)
    x = x + dn
    x = _constrain(x, ("batch", "seq", "embed"), mesh, rules)
    if return_kv:
        return x, aux, (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
    return x, aux


def _checkpoint_policy(remat_policy: Optional[str]):
    """What a rematerialised layer keeps for its backward pass (names
    validated at GPTConfig construction).  None keeps nothing.  "dots"
    keeps matmul outputs and recomputes only the cheap elementwise/norm
    work — a fraction of full-remat's extra FLOPs for modest activation
    memory (the policy knob the scaling playbook recommends).  The fused
    attention is the layer's largest product and no ``dot`` to that
    policy, so what its forward rule names (ops/flash_attention.py: the
    output and one float32 a row of logsumexp) is kept by name beside
    them: the backward scan then runs no attention forward kernel."""
    if remat_policy is None:
        return None
    cp = jax.checkpoint_policies
    return cp.save_from_both_policies(
        cp.dots_with_no_batch_dims_saveable,
        cp.save_only_these_names("flash_out", "flash_lse"))


def _layer_scan_body(cfg: GPTConfig, mesh, rules, return_kv: bool = False):
    """Scan body over a stacked layer dim, rematerialized per cfg: the
    block over a whole sequence.  Carry is (x, accumulated moe aux
    loss); with ``return_kv`` each step also emits that layer's K/V
    heads (stacked to [L, b, h, s, hd] by the scan — the prefill cache
    layout)."""
    attend = causal_attend(cfg, mesh, rules)

    def layer(carry, lp):
        x, aux = carry
        if return_kv:
            x, a, kv = _transformer_layer(x, lp, cfg, mesh, rules, attend,
                                          return_kv=True)
            return (x, aux + a), kv
        x, a = _transformer_layer(x, lp, cfg, mesh, rules, attend)
        return (x, aux + a), None

    if cfg.remat:
        return jax.checkpoint(layer,
                              policy=_checkpoint_policy(cfg.remat_policy))
    return layer


def _token_rows(params, tokens, cfg: GPTConfig):
    """``wte``'s rows for ``tokens``, in the table's precision (a served
    table's rows are padded to whole lanes, see ``serving_params``)."""
    return params["wte"][tokens][..., :cfg.d_model]


def _embed(params, tokens, cfg: GPTConfig, mesh, rules):
    s = tokens.shape[1]
    x = _token_rows(params, tokens, cfg) + params["wpe"][:s][None, :, :]
    x = x.astype(cfg.dtype)
    return _constrain(x, ("batch", "seq", "embed"), mesh, rules)


def _head(params, x, cfg: GPTConfig, mesh, rules):
    x = _layer_norm(x, params["ln_f_scale"], params["ln_f_bias"])
    # a served tree (serving_params) brings the tied head's matrix too
    w_out = (params["lm_head"] if "lm_head" in params else params["wte"].T)
    logits = jnp.einsum("bsd,dv->bsv", x, w_out.astype(cfg.dtype))
    logits = _constrain(logits, ("batch", "seq", "vocab"), mesh, rules)
    return logits.astype(jnp.float32)


def forward(params, tokens, cfg: GPTConfig, *, mesh: Optional[Mesh] = None,
            rules: Rules = DEFAULT_LLM_RULES, return_aux: bool = False,
            return_kv: bool = False):
    """tokens [b, s] int32 → logits [b, s, vocab] (f32).

    With a mesh, activations carry sharding constraints so pjit lays out
    batch over dp/fsdp, heads/mlp over tp, seq over sp; without one it is
    an ordinary single-device jax function.  A mesh with pp > 1 runs the
    layer stack as a GPipe microbatch pipeline (parallel.pipeline).
    ``return_aux`` also returns the summed MoE load-balance loss.
    ``return_kv`` additionally returns the per-layer attention K/V
    ((k, v), each [L, b, h, s, hd]) — the prefill half of the
    incremental-decode path (ray_tpu.inference); the SAME forward math
    seeds the cache, so there is no separate prefill network to drift.
    """
    if mesh is not None and mesh.shape.get("pp", 1) > 1:
        if return_kv:
            raise NotImplementedError(
                "return_kv (inference prefill) is not supported on a "
                "pp mesh; prefill with dp/tp sharding instead")
        return _forward_pipelined(params, tokens, cfg, mesh, rules,
                                  return_aux)

    x = _embed(params, tokens, cfg, mesh, rules)
    (x, aux), kv = lax.scan(_layer_scan_body(cfg, mesh, rules, return_kv),
                            (x, jnp.zeros((), jnp.float32)),
                            params["layers"])
    logits = _head(params, x, cfg, mesh, rules)
    if return_kv:
        return ((logits, aux, kv) if return_aux else (logits, kv))
    return (logits, aux) if return_aux else logits


def _forward_pipelined(params, tokens, cfg: GPTConfig, mesh: Mesh,
                       rules: Rules, return_aux: bool = False):
    """Pipeline-parallel forward: embedding and head run under GSPMD auto
    sharding (once, sharded over dp/tp); only the layer stack rides the
    pp pipeline (parallel.pipeline.pipeline_apply, single-hop ppermute
    hand-offs).  Composes with dp/fsdp/tp AND MoE (the load-balance aux
    loss rides the same ppermute hand-off as the activation, summed at
    the last stage); sp+pp is not supported (ring attention would nest
    shard_maps — shard long sequences with sp, deep stacks with pp)."""
    from ray_tpu.parallel.pipeline import pipeline_apply

    if mesh.shape.get("sp", 1) > 1:
        raise NotImplementedError(
            "sp and pp on the same mesh are not supported; shard long "
            "sequences with sp, deep stacks with pp")
    S = mesh.shape["pp"]
    if cfg.n_layers % S != 0:
        raise ValueError(f"n_layers {cfg.n_layers} not divisible by pp={S}")
    M = cfg.pp_microbatches or 2 * S
    b, s = tokens.shape
    if b % M != 0:
        raise ValueError(f"batch {b} not divisible by microbatches {M}")

    x = _embed(params, tokens, cfg, mesh, rules)
    x_mb = x.reshape(M, b // M, s, cfg.d_model)

    # dp/fsdp/tp are auto axes inside the pipeline's shard_map, so the
    # stage body keeps its usual logical-axis constraints (their specs
    # never mention pp)
    body = _layer_scan_body(cfg, mesh, rules)

    def stage_fn(local_layers, x, aux):
        (x, aux), _ = lax.scan(body, (x, aux), local_layers)
        return x, aux

    outs, aux = pipeline_apply(stage_fn, x_mb, params["layers"],
                               mesh=mesh, carry_aux=True)
    x = outs.reshape(b, s, cfg.d_model)
    logits = _head(params, x, cfg, mesh, rules)
    if return_aux:
        # per-microbatch means summed over M microbatches -> batch mean
        return logits, aux / M
    return logits


def loss_fn(params, batch, cfg: GPTConfig, *, mesh: Optional[Mesh] = None,
            rules: Rules = DEFAULT_LLM_RULES):
    """Next-token cross-entropy.  batch = {"tokens": [b, s+1] int32} or
    {"tokens": [b, s], "targets": [b, s]}."""
    tokens = batch["tokens"]
    if "targets" in batch:
        inp, tgt = tokens, batch["targets"]
    else:
        inp, tgt = tokens[:, :-1], tokens[:, 1:]
    logits, aux = forward(params, inp, cfg, mesh=mesh, rules=rules,
                          return_aux=True)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    ce = jnp.mean(logz - gold)
    if cfg.n_experts:
        return ce + cfg.moe_aux_weight * aux
    return ce


def sample_token(logits, *, temperature: float = 1.0,
                 rng: Optional[jax.Array] = None) -> jax.Array:
    """Next-token sampling head shared by ``generate()`` (the
    full-recompute correctness oracle) and the KV-cache engine
    (ray_tpu.inference.engine) — one implementation so greedy decode is
    token-identical across the two paths by construction.

    logits [..., vocab] f32 → token ids [...] int32.  temperature == 0.0
    is exact argmax (ties break to the lowest index); otherwise softmax
    sampling at the given temperature (``rng`` required).
    """
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if rng is None:
        raise ValueError("temperature > 0 sampling requires an rng key")
    return jax.random.categorical(
        rng, logits.astype(jnp.float32) / temperature, axis=-1
    ).astype(jnp.int32)


def generate(params, cfg: GPTConfig, prompt, max_new: int, *,
             rng: Optional[jax.Array] = None, temperature: float = 1.0):
    """Greedy/sampled decode via lax.scan (static shapes — the whole loop
    is one compiled program).  prompt [b, s0] int32, returns [b, s0+max_new].
    Simple full-recompute decode (no kv cache — every step re-runs the
    whole prefix).  The production incremental path lives in
    ray_tpu.inference (prefill seeds a KV cache via ``forward(...,
    return_kv=True)``, per-step decode reuses it); this path is kept as
    the correctness oracle the engine's greedy output is asserted
    token-identical against, and both share ``sample_token``."""
    b, s0 = prompt.shape
    total = s0 + max_new
    if total > cfg.max_seq:
        raise ValueError(f"{total} exceeds max_seq {cfg.max_seq}")
    toks = jnp.zeros((b, total), jnp.int32).at[:, :s0].set(prompt)
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    def step(carry, i):
        toks, rng = carry
        logits = forward(params, toks, cfg)[:, i - 1, :]
        if temperature == 0.0:
            nxt = sample_token(logits, temperature=0.0)
        else:
            rng, sub = jax.random.split(rng)
            nxt = sample_token(logits, temperature=temperature, rng=sub)
        toks = toks.at[:, i].set(nxt)
        return (toks, rng), None

    (toks, _), _ = lax.scan(step, (toks, rng), jnp.arange(s0, total))
    return toks


class GPT:
    """OO convenience wrapper over the functional API."""

    def __init__(self, cfg: GPTConfig):
        self.cfg = cfg

    def init(self, rng):
        return init_params(self.cfg, rng)

    def logical_axes(self):
        return param_logical_axes(self.cfg)

    def apply(self, params, tokens, **kw):
        return forward(params, tokens, self.cfg, **kw)

    def loss(self, params, batch, **kw):
        return loss_fn(params, batch, self.cfg, **kw)
