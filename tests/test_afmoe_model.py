"""The hybrid layer function in its ``afmoe`` layout (Arcee Trinity:
window and full attention layers, rotary on the window layers only,
per-head q/k norms, a sigmoid output gate, sandwich norms, a leading
dense layer, sigmoid-routed gated experts with a selection bias and a
shared gated expert) at a tiny size on the CPU, held to the plain
reference (chipbench/reference/afmoe.py: float32, the whole score row of
every query, no cache)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import afmoe as ref
from ray_tpu.inference.cache import BlockPool, PoolLayout
from ray_tpu.models import hybrid
from ray_tpu.ops.routed_experts import route

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
PUB = dict(
    model_type="afmoe", vocab_size=256, hidden_size=64, head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=5,
    num_attention_heads=4, num_key_value_heads=2, hidden_act="silu",
    max_position_embeddings=640, rms_norm_eps=1e-5,
    tie_word_embeddings=False, layer_types=["sliding_attention"] + PERIOD,
    global_attn_every_n_layers=4, sliding_window=12, rope_theta=10000,
    rope_scaling=None, mup_enabled=True, num_dense_layers=1,
    num_experts=16, num_experts_per_tok=3, num_shared_experts=1,
    score_func="sigmoid", route_norm=True, route_scale=2.448, n_group=1,
    topk_group=1, num_expert_groups=1, num_limited_groups=1,
    load_balance_coeff=5e-5, use_grouped_mm=True)
F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32, max_seq=160)
# float32 against float32: what is left is the order of the sums
# (measured 5e-7 at 40 tokens; logits of magnitude 0.6).  bfloat16 inputs
# to every product, the nearest thing below what the test states, move
# the same logits by 5e-3: a hundred times the tolerance.
ATOL = 5e-5
HELD = (0, 16)


@pytest.fixture(scope="module")
def cfg():
    return hybrid.HybridConfig.from_published(PUB, **F32)


@pytest.fixture(scope="module")
def params(cfg):
    """Seeded weights, with norm gains and the selection bias moved off
    their trivial values so that a norm or a bias left out would show."""
    p = hybrid.init_params(cfg, jax.random.PRNGKey(0))
    keys = iter(jax.random.split(jax.random.PRNGKey(9), 64))

    def jitter(a, scale):
        return a + scale * jax.random.normal(next(keys), a.shape, a.dtype)
    for lp in p["layers"]:
        for sub in lp.values():
            for name in ("norm", "post_norm", "q_norm", "k_norm"):
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


def test_config_from_published_keys(cfg):
    W, A = hybrid.WINDOW, hybrid.ATTENTION
    assert cfg.layer_types == (W, W, W, W, A)
    assert cfg.sublayers[:4] == ((0, W), (0, hybrid.DENSE), (1, W),
                                 (1, hybrid.EXPERTS))
    assert (cfg.n_window, cfg.n_attention, cfg.window) == (4, 1, 12)
    assert cfg.kv_geometry == (1, 2, 16)
    assert cfg.window_geometry == (4, 2, 16, 12)
    assert cfg.state_geometry is None and cfg.value_lanes is None
    assert cfg.qk_norm == "head" and cfg.attn_gate and cfg.sandwich_norm
    assert cfg.gated_experts and cfg.routes_by_sigmoid
    assert cfg.embedding_multiplier == 8.0          # sqrt(64): mup
    assert cfg.attention_multiplier == 0.25 and cfg.routed_scale == 2.448
    assert (cfg.dense_layers, cfg.dense_width, cfg.shared_width) \
        == (1, 96, 32)


def test_published_config_gives_the_stated_scale():
    """The benchmark's configuration file through ``model_config``: the
    widths of the catalog's row, this chip's layers, experts and
    vocabulary, 4,322 M parameters by the program's own shapes."""
    from chipbench.traffic.open_loop_http_afmoe import model_config
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "trinity-large-preview-5L-e32.json")) as f:
        config = json.load(f)
    cfg, published, held = model_config(config)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) \
        == (3072, 48, 8, 128)
    assert (cfg.dense_width, cfg.expert_width, cfg.n_experts,
            cfg.experts_per_token) == (12288, 3072, 256, 4)
    assert (cfg.window, cfg.routed_scale, held) == (4096, 2.448, (0, 32))
    W, A = hybrid.WINDOW, hybrid.ATTENTION
    assert cfg.layer_types == (W, W, W, W, A) and cfg.dense_layers == 1
    assert published["layer_types"] == ["sliding_attention"] * 4 \
        + ["full_attention"]
    shapes = jax.eval_shape(
        lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert 4.32e9 < n < 4.33e9
    # a 32 k-token row: 4 KB a token in the full layer's pools, and at
    # most a window, a chunk and a block of 16 KB a token in the window
    # layers', where one table a row would hold all 32 k
    assert PoolLayout(1, 2, 64, 8, 128).width == 1024


@pytest.mark.parametrize("change, named", [
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"score_func": "softmax"}, "score_func"),
    ({"route_norm": False}, "route_norm"),
    ({"n_group": 2}, "n_group"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"num_shared_experts": 0}, "num_shared_experts"),
    ({"layer_types": ["sliding_attention", "chunked_attention"] * 3},
     "layer_types"),
])
def test_what_has_no_form_is_refused_by_name(change, named):
    with pytest.raises(ValueError, match=named):
        hybrid.HybridConfig.from_published({**PUB, **change}, **F32)


def test_window_layers_keep_a_pool_of_their_own(cfg):
    """Two kinds of K/V state side by side: the full layer's pool and
    the window layers', each with its own blocks and layout."""
    pool = BlockPool(cfg, n_blocks=20, block_size=8, max_seq=96,
                     n_window_blocks=9, window_span=12 + 16 + 8)
    assert pool.layout.shape == (1 * 21, 8, 128)
    assert pool.window.layout.shape == (4 * 10, 8, 128)
    assert pool.window.blocks_per_row == 6          # ceil(36 / 8) + 1
    assert len(pool.pools) == 4
    assert pool.bytes_total() == 2 * 4 * (21 + 40) * 8 * 128
    a, b = pool.alloc(), pool.window.alloc()
    assert (pool.n_used, pool.window.n_used) == (1, 1)
    pool.decref(a)
    assert (pool.n_used, pool.window.n_used) == (0, 1)
    pool.window.decref(b)
    with pytest.raises(ValueError, match="n_window_blocks"):
        BlockPool(cfg, n_blocks=20, block_size=8, max_seq=96)
    with pytest.raises(ValueError, match="cannot hold"):
        BlockPool(cfg, n_blocks=20, block_size=8, max_seq=96,
                  n_window_blocks=5, window_span=36)


@pytest.mark.parametrize("n", [7, 12, 13, 70])
def test_forward_equals_reference_logits(cfg, params, n):
    """Contexts shorter than, equal to, one past and several times the
    window of 12."""
    toks = _tokens(n, n)
    got = np.asarray(hybrid.forward(params, toks[None], cfg))[0]
    np.testing.assert_allclose(got, _ref(params, toks), atol=ATOL)


def test_reference_controls_move_the_logits(cfg, params):
    """The tolerance tells the stated precision from the next one down
    (bfloat16 inputs to every product), and each mechanism left out of
    the reference moves the logits: the window, the rotation on the
    window layers (and one on the full layer), the gate, the post norms,
    the selection bias."""
    toks = _tokens(70, 1)
    want = _ref(params, toks)
    assert np.abs(_ref(params, toks, round_to=jnp.bfloat16) - want).max() \
        > 20 * ATOL

    def moved(published=PUB, tree=params):
        return np.abs(np.asarray(ref.logits(tree, toks, published, HELD))
                      - want).max()
    assert moved({**PUB, "sliding_window": 11}) > 20 * ATOL  # off by one
    assert moved({**PUB, "sliding_window": 10 ** 6}) > 20 * ATOL
    assert moved({**PUB, "rope_theta": 500000}) > 20 * ATOL
    full_first = {**PUB, "layer_types": ["full_attention"] + PERIOD}
    assert moved(full_first) > 20 * ATOL

    def without(slot, name, value):
        layers = [{**lp, slot: {**lp[slot], name: value(lp[slot][name])}}
                  if name in lp[slot] else lp for lp in params["layers"]]
        return {**params, "layers": layers}
    # the gate's columns of the fused projection zeroed: sigmoid(0)
    assert moved(tree=without(
        "mixer", "wqkv", lambda w: w.at[:, -64:].set(0.0))) > 20 * ATOL
    assert moved(tree=without("mixer", "post_norm", jnp.ones_like)) \
        > 20 * ATOL
    assert moved(tree=without("ffn", "router_bias", jnp.zeros_like)) \
        > 20 * ATOL


def test_sigmoid_router_over_gated_experts_against_the_reference(cfg,
                                                                 params):
    """The third pairing of router and expert form: sigmoid scores, the
    choice by score + bias, gates the unbiased scores over (their sum +
    1e-20) times route_scale."""
    fp = params["layers"][2]["ffn"]
    h = jnp.asarray(np.random.default_rng(4).standard_normal((50, 64)),
                    jnp.float32)
    experts, gates = route(h, fp["router"], 3, fp["router_bias"], 2.448,
                           eps=1e-20)
    scores, idx = ref._choice(dict(ref._static(PUB)), fp, h, None)
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(idx, -1))
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(experts), -1)
    np.testing.assert_allclose(
        gates, chosen / chosen.sum(-1, keepdims=True) * 2.448, rtol=1e-6)
    # the bias moves the choice and never the gates
    plain, _ = route(h, fp["router"], 3, jnp.zeros(16), 2.448, eps=1e-20)
    assert (np.sort(plain, -1) != np.sort(experts, -1)).any()


def test_the_older_router_forms_unchanged_bit_for_bit():
    """``eps`` is added only where a recipe has one: without it the
    sigmoid form computes what it computed."""
    h = jnp.asarray(np.random.default_rng(5).standard_normal((40, 64)),
                    jnp.float32)
    w = jnp.asarray(np.random.default_rng(6).standard_normal((64, 16)),
                    jnp.float32)
    bias = jnp.linspace(-0.2, 0.2, 16)
    experts, gates = route(h, w, 3, bias, 2.5)
    scores = jax.nn.sigmoid(h @ w)
    _, want = jax.lax.top_k(scores + bias, 3)
    chosen = jnp.take_along_axis(scores, want, -1)
    np.testing.assert_array_equal(experts, want)
    np.testing.assert_array_equal(
        gates, chosen / chosen.sum(-1, keepdims=True) * 2.5)


def test_the_shares_add_up(cfg, params):
    """The 8 shares of a deployment in which 8 chips share a layer (2
    of 16 experts each), the shared expert counted once = the uncut
    layer; the program's share of a range says what the reference's
    does."""
    fp = params["layers"][1]["ffn"]
    x = jnp.asarray(np.random.default_rng(6).standard_normal((30, 64)),
                    jnp.float32)
    c = ref._static(PUB)
    whole = np.asarray(ref._sublayer(fp, x, "experts", c, (0, 16), None))
    shared_only, parts = None, np.zeros_like(whole)
    for g in range(8):
        held = (2 * g, 2 * g + 2)
        cut = {**fp, "w_in": fp["w_in"][held[0]:held[1]],
               "w_out": fp["w_out"][held[0]:held[1]]}
        part = np.asarray(ref._sublayer(cut, x, "experts", c, held, None))
        hc = hybrid.HybridConfig.from_published(PUB, experts_held=held,
                                                **F32)
        mine, _, load = hybrid.block(hc, hybrid.EXPERTS, cut, x[None],
                                     None, jnp.full((1,), 30, jnp.int32))
        np.testing.assert_allclose(mine[0], part, atol=ATOL)
        assert int(load[1]) == 30 * 3 and 0 <= int(load[0].sum()) < 90
        # zero the routed part to find what every share repeats
        none = {**cut, "w_out": jnp.zeros_like(cut["w_out"])}
        base = np.asarray(ref._sublayer(none, x, "experts", c, held, None))
        if shared_only is None:
            shared_only = base
        np.testing.assert_allclose(base, shared_only, atol=1e-6)
        # inside the post norm the parts do not add: compare before it
        parts += _before_post_norm(cut, x, held) \
            - _before_post_norm(none, x, held)
    np.testing.assert_allclose(
        _before_post_norm({**fp, "w_out": jnp.zeros_like(fp["w_out"])}, x,
                          (0, 16)) + parts,
        _before_post_norm(fp, x, (0, 16)), atol=ATOL)


def _before_post_norm(fp, x, held):
    """What an experts sublayer adds up before its output norm: the
    routed part of the held range plus the shared expert."""
    c = dict(ref._static(PUB))
    f32 = {k: a.astype(jnp.float32) for k, a in fp.items()}
    h = ref._rms_norm(x, f32["norm"], c["rms_norm_eps"])
    return np.asarray(ref._experts(c, f32, h, held, None))


def test_every_assumed_convention_is_in_the_configuration_file():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "trinity-large-preview-5L-e32.json")) as f:
        assumed = json.load(f)["assumed"]
    for key in ref.ASSUMED:
        assert key in assumed, key


def test_reference_imports_nothing_from_the_program():
    with open(ref.__file__) as f:
        src = f.read()
    assert "import ray_tpu" not in src and "from ray_tpu" not in src


def test_other_layouts_keep_their_block_and_one_pool():
    """The new forms are fields with the old behaviour as default: the
    other layouts have no gate, no post norm, no window group, a softmax
    (granite) or relu^2-sigmoid (nemotron) router as before, and one
    pool and one table."""
    tiny = hybrid.HybridConfig.tiny()
    assert not (tiny.attn_gate or tiny.sandwich_norm or tiny.window
                or tiny.rope_theta or tiny.routes_by_sigmoid)
    assert tiny.window_geometry is None
    p = hybrid.init_params(tiny, jax.random.PRNGKey(0))
    assert set(p["layers"][1]["mixer"]) == {"norm", "wqkv", "wo"}
    assert "router_bias" not in p["layers"][1]["ffn"]
    relu2 = hybrid.HybridConfig.tiny(gated_experts=False)
    assert relu2.routes_by_sigmoid
    pool = BlockPool(tiny, n_blocks=12, block_size=8, max_seq=96,
                     state_rows=2)
    assert pool.window is None and len(pool.pools) == 2
