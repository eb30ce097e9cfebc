"""BERT: bidirectional transformer encoder, TPU-first (north-star #5:
HF BERT-base + PBT sweep on v5e-16).

Reference capability: the reference's HuggingFace Train integration
(python/ray/train/huggingface/) fine-tunes torch BERT; it ships no model
code.  Here the encoder is framework-owned and shares the GPT design:
plain pytree params with logical sharding axes, ``lax.scan`` over stacked
layers (O(1) compile in depth), pallas attention dispatch, bf16
activations / f32 accumulators, declarative dp/fsdp/tp sharding via the
same rule table (parallel/sharding.py) — no model rewrite per layout.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.models.gpt import _layer_norm  # shared f32 layernorm
from ray_tpu.ops.attention import attention
from ray_tpu.parallel.sharding import DEFAULT_LLM_RULES, Rules, spec_for


@dataclass(frozen=True)
class BERTConfig:
    vocab_size: int = 30592          # bert-base vocab padded to 128
    max_seq: int = 512
    type_vocab: int = 2
    d_model: int = 768
    n_heads: int = 12
    n_layers: int = 12
    d_ff: int = 3072
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    ignore_index: int = -100         # label value meaning "not an MLM target"
    attn_impl: Optional[str] = None  # None=auto (flash on TPU), "reference"
    pp_microbatches: Optional[int] = None  # None = 2*pp stages (GPipe)

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @staticmethod
    def bert_base(**kw) -> "BERTConfig":
        return BERTConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "BERTConfig":
        return BERTConfig(**{**dict(vocab_size=512, max_seq=128, d_model=64,
                                    n_heads=4, n_layers=2, d_ff=128,
                                    remat=False, dtype=jnp.float32), **kw})


PARAM_AXES = {
    "wte": ("vocab", "embed"),
    "wpe": (None, "embed"),
    "wtype": (None, "embed"),
    "ln_emb_scale": ("embed",),
    "ln_emb_bias": ("embed",),
    "layers": {
        "wqkv": ("layers", "embed", "qkv"),
        "wo": ("layers", "heads", "embed"),
        "bo": ("layers", "embed"),
        "ln1_scale": ("layers", "embed"),
        "ln1_bias": ("layers", "embed"),
        "w_up": ("layers", "embed", "mlp"),
        "b_up": ("layers", "mlp"),
        "w_down": ("layers", "mlp", "embed"),
        "b_down": ("layers", "embed"),
        "ln2_scale": ("layers", "embed"),
        "ln2_bias": ("layers", "embed"),
    },
    "mlm_dense_w": ("embed", "embed"),
    "mlm_dense_b": ("embed",),
    "mlm_ln_scale": ("embed",),
    "mlm_ln_bias": ("embed",),
    "mlm_bias": ("vocab",),
    "pooler_w": ("embed", "embed"),
    "pooler_b": ("embed",),
}


def param_logical_axes(cfg: BERTConfig):
    return dict(PARAM_AXES)


def init_params(cfg: BERTConfig, rng: jax.Array):
    k = iter(jax.random.split(rng, 16))
    d, L, f = cfg.d_model, cfg.n_layers, cfg.d_ff
    pd, std = cfg.param_dtype, 0.02

    def norm(key, shape, s=std):
        return (jax.random.normal(key, shape) * s).astype(pd)

    return {
        "wte": norm(next(k), (cfg.vocab_size, d)),
        "wpe": norm(next(k), (cfg.max_seq, d)),
        "wtype": norm(next(k), (cfg.type_vocab, d)),
        "ln_emb_scale": jnp.ones((d,), pd),
        "ln_emb_bias": jnp.zeros((d,), pd),
        "layers": {
            "wqkv": norm(next(k), (L, d, 3 * d)),
            "wo": norm(next(k), (L, d, d), std / math.sqrt(2 * L)),
            "bo": jnp.zeros((L, d), pd),
            "ln1_scale": jnp.ones((L, d), pd),
            "ln1_bias": jnp.zeros((L, d), pd),
            "w_up": norm(next(k), (L, d, f)),
            "b_up": jnp.zeros((L, f), pd),
            "w_down": norm(next(k), (L, f, d), std / math.sqrt(2 * L)),
            "b_down": jnp.zeros((L, d), pd),
            "ln2_scale": jnp.ones((L, d), pd),
            "ln2_bias": jnp.zeros((L, d), pd),
        },
        "mlm_dense_w": norm(next(k), (d, d)),
        "mlm_dense_b": jnp.zeros((d,), pd),
        "mlm_ln_scale": jnp.ones((d,), pd),
        "mlm_ln_bias": jnp.zeros((d,), pd),
        "mlm_bias": jnp.zeros((cfg.vocab_size,), pd),
        "pooler_w": norm(next(k), (d, d)),
        "pooler_b": jnp.zeros((d,), pd),
    }


def _constrain(x, logical, mesh, rules):
    if mesh is None:
        return x
    spec = spec_for(logical, rules, mesh)
    return lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


def encode(params, tokens, cfg: BERTConfig, *,
           attention_mask: Optional[jax.Array] = None,
           token_type_ids: Optional[jax.Array] = None,
           mesh=None, rules: Rules = DEFAULT_LLM_RULES):
    """tokens [b, s] int32 → hidden [b, s, d] (cfg.dtype)."""
    b, s = tokens.shape
    h, hd = cfg.n_heads, cfg.head_dim

    x = params["wte"][tokens] + params["wpe"][:s][None, :, :]
    if token_type_ids is not None:
        x = x + params["wtype"][token_type_ids]
    x = _layer_norm(x.astype(cfg.dtype), params["ln_emb_scale"],
                    params["ln_emb_bias"])
    x = _constrain(x, ("batch", "seq", "embed"), mesh, rules)

    # [b, 1, 1, s] additive-style boolean mask broadcast over (h, q)
    attn_mask = None
    if attention_mask is not None:
        attn_mask = attention_mask[:, None, None, :].astype(bool)

    attn_spec = (spec_for(("batch", "heads", "seq", "kv"), rules, mesh)
                 if mesh is not None else None)

    def layer(x, lp):
        bx, sx = x.shape[0], x.shape[1]  # microbatched under pp
        qkv = jnp.einsum("bsd,de->bse", x, lp["wqkv"].astype(cfg.dtype))
        qkv = _constrain(qkv, ("batch", "seq", "qkv"), mesh, rules)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):
            return t.reshape(bx, sx, h, hd).transpose(0, 2, 1, 3)

        # auto-dispatch (pallas flash on TPU) when there is no padding
        # mask; the masked path needs the reference impl
        impl = "reference" if attn_mask is not None else cfg.attn_impl
        o = attention(heads(q), heads(k), heads(v), causal=False,
                      mask=attn_mask, impl=impl, mesh=mesh,
                      spec=attn_spec)
        o = o.transpose(0, 2, 1, 3).reshape(bx, sx, cfg.d_model)
        o = jnp.einsum("bsd,de->bse", o, lp["wo"].astype(cfg.dtype)) \
            + lp["bo"].astype(cfg.dtype)
        x = _layer_norm(x + o, lp["ln1_scale"], lp["ln1_bias"])  # post-LN
        x = _constrain(x, ("batch", "seq", "embed"), mesh, rules)

        u = jnp.einsum("bsd,df->bsf", x, lp["w_up"].astype(cfg.dtype)) \
            + lp["b_up"].astype(cfg.dtype)
        u = _constrain(u, ("batch", "seq", "mlp"), mesh, rules)
        u = jax.nn.gelu(u)
        dn = jnp.einsum("bsf,fd->bsd", u, lp["w_down"].astype(cfg.dtype)) \
            + lp["b_down"].astype(cfg.dtype)
        x = _layer_norm(x + dn, lp["ln2_scale"], lp["ln2_bias"])
        x = _constrain(x, ("batch", "seq", "embed"), mesh, rules)
        return x, None

    body = jax.checkpoint(layer) if cfg.remat else layer

    if mesh is not None and mesh.shape.get("pp", 1) > 1:
        # GPipe microbatch pipeline over pp (parallel.pipeline); the
        # encoder stack is residual-stream shaped so the generic stage
        # runner applies directly
        from ray_tpu.parallel.pipeline import pipeline_apply
        if attn_mask is not None:
            raise NotImplementedError(
                "attention_mask + pp pipeline is not supported yet; "
                "pad-free batches only on pp meshes")
        S = mesh.shape["pp"]
        if cfg.n_layers % S != 0:
            raise ValueError(
                f"n_layers {cfg.n_layers} not divisible by pp={S}")
        M = cfg.pp_microbatches or 2 * S
        if b % M != 0:
            raise ValueError(f"batch {b} not divisible by microbatches {M}")
        x_mb = x.reshape(M, b // M, s, cfg.d_model)

        def stage_fn(local_layers, xm):
            xm, _ = lax.scan(body, xm, local_layers)
            return xm

        outs = pipeline_apply(stage_fn, x_mb, params["layers"], mesh=mesh)
        return outs.reshape(b, s, cfg.d_model)

    x, _ = lax.scan(body, x, params["layers"])
    return x


def mlm_logits(params, hidden, cfg: BERTConfig):
    """MLM head: dense+gelu+LN then tied-embedding projection."""
    y = jnp.einsum("bsd,de->bse", hidden,
                   params["mlm_dense_w"].astype(hidden.dtype)) \
        + params["mlm_dense_b"].astype(hidden.dtype)
    y = jax.nn.gelu(y)
    y = _layer_norm(y, params["mlm_ln_scale"], params["mlm_ln_bias"])
    logits = jnp.einsum("bsd,vd->bsv", y, params["wte"].astype(y.dtype))
    return logits.astype(jnp.float32) + params["mlm_bias"].astype(jnp.float32)


def pool(params, hidden):
    """[CLS] pooler: tanh(dense(hidden[:, 0]))."""
    cls = hidden[:, 0, :]
    return jnp.tanh(cls @ params["pooler_w"].astype(cls.dtype)
                    + params["pooler_b"].astype(cls.dtype))


def loss_fn(params, batch, cfg: BERTConfig, *, mesh=None,
            rules: Rules = DEFAULT_LLM_RULES):
    """Masked-LM cross-entropy.  batch = {"input_ids": [b,s] int32,
    "labels": [b,s] int32 with ignore_index where not masked,
    optional "attention_mask": [b,s]}."""
    hidden = encode(params, batch["input_ids"], cfg,
                    attention_mask=batch.get("attention_mask"),
                    token_type_ids=batch.get("token_type_ids"),
                    mesh=mesh, rules=rules)
    logits = mlm_logits(params, hidden, cfg)
    labels = batch["labels"]
    valid = labels != cfg.ignore_index
    safe = jnp.where(valid, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = jnp.where(valid, logz - gold, 0.0)
    return jnp.sum(nll) / jnp.maximum(jnp.sum(valid), 1)


def num_params(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


class BERT:
    """OO convenience wrapper over the functional API."""

    def __init__(self, cfg: BERTConfig):
        self.cfg = cfg

    def init(self, rng):
        return init_params(self.cfg, rng)

    def logical_axes(self):
        return param_logical_axes(self.cfg)

    def encode(self, params, tokens, **kw):
        return encode(params, tokens, self.cfg, **kw)

    def loss(self, params, batch, **kw):
        return loss_fn(params, batch, self.cfg, **kw)
