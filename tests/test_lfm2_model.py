"""The hybrid layer function in its ``lfm2_moe`` layout (Liquid AI
LFM2-8B-A1B: gated short-convolution layers beside rotary grouped-query
attention with per-head q/k norms, two leading dense layers, sigmoid-
and-bias-routed gated experts with NO shared expert, a tied head) at a
tiny size on the CPU, held to the plain reference
(chipbench/reference/lfm2.py: float32, no cache, no state)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import lfm2 as ref
from ray_tpu.models import hybrid
from ray_tpu.ops.routed_experts import route

# the catalog row's ``config`` (model-configs guide, architectures.jsonl,
# LFM2-8B-A1B), verbatim
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                    "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "full_attention",
                    "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}
# the same keys at a tiny size: two periods, the second dense layer a
# conv layer's, experts behind both kinds of mixer
PUB = {**CATALOG, "hidden_size": 64, "intermediate_size": 96,
       "moe_intermediate_size": 32, "num_attention_heads": 4,
       "num_key_value_heads": 2, "num_experts": 8,
       "num_experts_per_tok": 2, "num_hidden_layers": 7,
       "vocab_size": 256, "max_position_embeddings": 256,
       "routed_scaling_factor": 1.5}
F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
HELD = (0, 8)
# float32 against float32: what is left is the order of the sums
# (measured 4e-6 at 50 tokens; logits of std 1.5).  bfloat16 inputs to
# every product, the nearest thing below what the test states, move the
# same logits by 3e-2 (``test_forward_in_bfloat16_products_fails``).
ATOL = 5e-5


@pytest.fixture(scope="module")
def cfg():
    return hybrid.HybridConfig.from_published(PUB, **F32)


@pytest.fixture(scope="module")
def params(cfg):
    return seeded(cfg)


def seeded(cfg, seed=0):
    """Seeded weights, with norm gains and the selection bias moved off
    their trivial values (a norm or a bias left out would show) and the
    tied embedding at a size at which the logits tell tokens apart."""
    p = hybrid.init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 9), 64))

    def jitter(a, scale):
        return a + scale * jax.random.normal(next(keys), a.shape, a.dtype)
    p["wte"] = p["wte"] * 12
    p["norm_f"] = jitter(p["norm_f"], 0.2)
    for lp in p["layers"]:
        for sub in lp.values():
            for name in ("norm", "q_norm", "k_norm"):
                if name in sub:
                    sub[name] = jitter(sub[name], 0.2)
            if "router_bias" in sub:
                sub["router_bias"] = jitter(sub["router_bias"], 0.3)
    return p


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n)


def _ref(params, toks, **kw):
    return np.asarray(ref.logits(params, np.asarray(toks), PUB, HELD, **kw))


def test_catalog_config_builds_the_published_layout():
    """The catalog row's ``config`` verbatim: every key read or checked,
    8.34 B parameters by the program's own shapes."""
    cfg = hybrid.HybridConfig.from_published(CATALOG)
    S, A = hybrid.SHORT_CONV, hybrid.ATTENTION
    assert (cfg.n_short_conv, cfg.n_attention, cfg.n_layers) == (18, 6, 24)
    assert cfg.layer_types[:4] == (S, S, A, S)
    assert cfg.sublayers[:6] == ((0, S), (0, hybrid.DENSE), (1, S),
                                 (1, hybrid.DENSE), (2, A),
                                 (2, hybrid.EXPERTS))
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) \
        == (2048, 32, 8, 64)
    assert cfg.kv_geometry == (6, 8, 64)
    assert cfg.state_geometry == (18, (2, 2048), None)
    assert cfg.window_geometry is None and cfg.value_lanes is None
    assert cfg.qk_norm == "head" and cfg.rotary_full
    assert cfg.rope_theta == 1e6 and cfg.yarn is None
    assert cfg.gated_experts and cfg.routes_by_sigmoid and cfg.tied_head
    assert (cfg.n_experts, cfg.experts_per_token, cfg.expert_width,
            cfg.shared_width) == (32, 4, 1792, 0)
    assert (cfg.dense_layers, cfg.dense_width) == (2, 7168)
    assert cfg.route_eps == 1e-6 and cfg.routed_scale == 1.0
    assert cfg.attention_multiplier == 0.125 and cfg.rms_eps == 1e-5
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (1.0, 1.0, 1.0)
    assert cfg.max_seq == 128000
    shapes = jax.eval_shape(
        lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0)))
    assert round(hybrid.num_params(shapes) / 1e9, 2) == 8.34
    # ... and the layout is reached by the keys, not the name alone
    bare = {k: v for k, v in CATALOG.items() if k != "model_type"}
    assert hybrid.HybridConfig.from_published(bare) == cfg


@pytest.mark.parametrize("key,value", [
    ("conv_bias", True), ("use_expert_bias", False),
    ("norm_topk_prob", False), ("rope_scaling", {"type": "yarn"}),
    ("tie_word_embeddings", False), ("hidden_act", "gelu"),
    ("attention_bias", True), ("num_shared_experts", 1),
    ("conv_L_cache", 1)])
def test_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        hybrid.HybridConfig.from_published({**PUB, key: value})


def test_unknown_layer_type_refused_by_name():
    types = ["conv", "sliding_attention"] + PUB["layer_types"][2:]
    with pytest.raises(ValueError, match="layer_types = 'sliding_att"):
        hybrid.HybridConfig.from_published({**PUB, "layer_types": types})


def test_one_pool_holds_one_kind_of_state():
    with pytest.raises(ValueError, match="one pool holds one kind"):
        hybrid.HybridConfig.tiny(
            layer_types=(hybrid.MAMBA, hybrid.SHORT_CONV))


def test_params_have_no_shared_expert_and_no_head(cfg, params):
    assert "head" not in params
    for i, lp in enumerate(params["layers"]):
        dense = i < 2
        assert set(lp["ffn"]) == ({"norm", "w_in", "w_out"} if dense else
                                  {"norm", "router", "router_bias", "w_in",
                                   "w_out"})
        conv = PUB["layer_types"][i] == "conv"
        assert set(lp["mixer"]) == (
            {"norm", "in_proj", "conv_w", "out_proj"} if conv else
            {"norm", "wqkv", "wo", "q_norm", "k_norm"})
    assert params["layers"][0]["mixer"]["in_proj"].shape == (64, 192)
    assert params["layers"][0]["mixer"]["conv_w"].shape == (3, 64)


@pytest.mark.parametrize("n", [1, 2, 50])
def test_forward_is_the_reference(cfg, params, n):
    toks = _tokens(n, seed=n)
    out = np.asarray(hybrid.forward(params, jnp.asarray(toks)[None], cfg))
    want = _ref(params, toks)
    assert want.std() > 1.0
    np.testing.assert_allclose(out[0], want, atol=ATOL)


def test_forward_in_bfloat16_products_fails(params):
    toks = _tokens(50, seed=50)
    err = np.abs(_ref(params, toks, round_to=jnp.bfloat16)
                 - _ref(params, toks)).max()
    assert err > 100 * ATOL


def test_forward_batched_rows_are_independent(cfg, params):
    toks = np.stack([_tokens(30, 1), _tokens(30, 2)])
    out = np.asarray(hybrid.forward(params, jnp.asarray(toks), cfg))
    for r in range(2):
        np.testing.assert_allclose(out[r], _ref(params, toks[r]), atol=ATOL)


def test_full_layers_are_rotated_and_afmoe_s_are_not(cfg, params):
    """``run_layers`` hands rotary tables to the ATTENTION sublayers of
    this layout: the same window at other positions gives other logits
    (a token past the first sees relative positions only, so shift ONE
    of two tokens' positions); the switch is the layout's own field,
    not ``rope_theta``'s meaning."""
    toks = jnp.asarray(_tokens(12, 3))[None]

    def at(positions, c):
        attend = hybrid.causal_attend(c)
        x, _ = hybrid.run_layers(
            c, params, hybrid.embed(c, params, toks), jnp.array([12]),
            state_in=lambda mi: hybrid.zero_state(c, 1),
            state_out=lambda mi, s: None, attend_for=lambda ai: attend,
            positions=positions)
        return np.asarray(x)
    base = jnp.arange(12)[None]
    gaps = base.at[0, 6:].add(5)          # a gap in the middle
    assert np.abs(at(base, cfg) - at(gaps, cfg)).max() > 1e-3
    np.testing.assert_allclose(at(base + 7, cfg), at(base, cfg), atol=1e-4)
    import dataclasses
    plain = dataclasses.replace(cfg, rotary_full=False)
    np.testing.assert_array_equal(at(base, plain), at(gaps, plain))


def test_experts_without_a_shared_expert_are_a_loop_over_tokens(cfg,
                                                                params):
    fp = params["layers"][3]["ffn"]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 9, 64))
    mine, _, (counts, total) = hybrid.block(
        cfg, hybrid.EXPERTS, fp, x, None, jnp.array([9]))
    h = hybrid._rms_norm(x[0], fp["norm"], cfg.rms_eps)
    experts, gates = route(h, fp["router"], 2, fp["router_bias"],
                           scale=1.5, eps=1e-6)
    want = []
    for t in range(9):
        acc = 0.0
        for e, g in zip(np.asarray(experts[t]), np.asarray(gates[t])):
            a, b = jnp.split(h[t] @ fp["w_in"][e], 2)
            acc = acc + g * ((jax.nn.silu(a) * b) @ fp["w_out"][e])
        want.append(x[0, t] + acc)
    np.testing.assert_allclose(mine[0], jnp.stack(want), atol=2e-6)
    assert int(total) == 18 and int(counts.sum()) == 18
    # gates: unbiased scores of the choice by score + bias, normalised
    scores = jax.nn.sigmoid(h @ fp["router"])
    top = jax.lax.top_k(scores + fp["router_bias"], 2)[1]
    np.testing.assert_array_equal(experts, top)
    chosen = jnp.take_along_axis(scores, top, -1)
    np.testing.assert_allclose(
        gates, chosen / (chosen.sum(-1, keepdims=True) + 1e-6) * 1.5,
        rtol=1e-6)


def test_scopes_of_the_program(cfg, params):
    """The spans a trace splits the pass by; none for an expert the
    layout does not have."""
    text = jax.jit(lambda p, t: hybrid.forward(p, t, cfg)).lower(
        params, jnp.zeros((1, 8), jnp.int32)).as_text(debug_info=True)
    assert "/shared_expert" not in text and "shared_expert/" not in text
    for scope in ("mixer_short_conv", "short_conv_taps",
                  "short_conv_in_proj", "short_conv_out_proj", "rotary",
                  "routed_experts", "mixer_attention"):
        assert scope in text, scope


def test_mixer_chunks_then_steps_are_the_window(cfg, params):
    """The mixer's three shapes: one window from zero state, the same in
    two windows, then token by token — and the state at marks."""
    mp = params["layers"][0]["mixer"]
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 14, 64))
    zero = hybrid.zero_state(cfg, 1)
    whole, (state, marked) = hybrid._short_conv_mixer(
        cfg, mp, h, (zero[0], jnp.array([[3, 8, 0]])), jnp.array([14]))
    a, (s1, _) = hybrid._short_conv_mixer(cfg, mp, h[:, :8], zero,
                                          jnp.array([8]))
    np.testing.assert_array_equal(marked[0, 1], s1[0])
    np.testing.assert_array_equal(marked[0, 2], zero[0][0])
    b, (s2, _) = hybrid._short_conv_mixer(cfg, mp, h[:, 8:11], (s1, None),
                                          jnp.array([3]))
    outs = [a, b]
    for t in range(11, 14):
        o, (s2, _) = hybrid._short_conv_mixer(cfg, mp, h[:, t:t + 1],
                                              (s2, None), jnp.array([1]))
        outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), whole, atol=1e-6)
    np.testing.assert_allclose(s2, state, atol=1e-7)
    # padding past n_valid is the identity on the state
    _, (s3, _) = hybrid._short_conv_mixer(cfg, mp, h, zero, jnp.array([8]))
    np.testing.assert_array_equal(s3, s1)
