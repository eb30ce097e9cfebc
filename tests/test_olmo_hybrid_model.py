"""The hybrid layer function in its ``olmo_hybrid`` layout (gated
delta-rule linear attention over a matrix state beside full attention
with q/k norms, a dense gated MLP in every layer, every sublayer's norm
on its OUTPUT) at a tiny size on the CPU, held to the plain reference
(chipbench/reference/olmo_hybrid.py: float32, the recurrence token by
token, no cache)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import olmo_hybrid as ref
from ray_tpu.inference.cache import BlockPool
from ray_tpu.models import hybrid

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
PUB = dict(
    model_type="olmo_hybrid", vocab_size=256, hidden_size=64,
    intermediate_size=96, num_hidden_layers=4, num_attention_heads=4,
    num_key_value_heads=4, hidden_act="silu", max_position_embeddings=640,
    attention_bias=False, rms_norm_eps=1e-6, tie_word_embeddings=False,
    layer_types=PERIOD, linear_num_key_heads=3, linear_num_value_heads=3,
    linear_key_head_dim=8, linear_value_head_dim=16,
    linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    rope_parameters={"rope_theta": None})
F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32, max_seq=128)
# float32 against float32: what is left is the order of the sums (the
# window form adds a block of 64 tokens at once where the reference adds
# a token at a time; measured 8e-6 at 70 tokens, 4 layers, logits of
# magnitude 0.7).  The matrix state rounded to bfloat16 after every
# token, the nearest thing below what the configuration states, moves
# the same logits by 7e-2: four thousand times the tolerance.
ATOL = 5e-5


@pytest.fixture(scope="module")
def cfg():
    return hybrid.HybridConfig.from_published(PUB, **F32)


@pytest.fixture(scope="module")
def params(cfg):
    return hybrid.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n)


def _published():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "olmo-hybrid-7b-16L.json")) as f:
        c = json.load(f)
    return {**c, "num_hidden_layers": c["published"]["num_hidden_layers"],
            "layer_types": PERIOD * 8}


def test_config_from_published_keys(cfg):
    assert cfg.layer_types == (hybrid.LINEAR,) * 3 + (hybrid.ATTENTION,)
    assert (cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim) == (3, 8, 16)
    assert cfg.qk_norm and cfg.norm_output and not cfg.tied_head
    assert cfg.dense_layers == 4 and cfg.dense_width == 96
    # a layer is its mixer then the dense MLP; no experts sublayer exists
    assert [k for _, k in cfg.sublayers] == [
        hybrid.LINEAR, hybrid.DENSE] * 3 + [hybrid.ATTENTION, hybrid.DENSE]
    assert cfg.state_geometry == (3, (3, 3 * (8 + 8 + 16)), (8, 3 * 16))
    assert cfg.kv_geometry == (1, 4, 16) and cfg.value_lanes is None
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling, cfg.attention_multiplier) == (1, 1, 1, 0.25)


def test_published_config_gives_the_stated_scale():
    """The catalog row's config at its 32 layers: 7.43 B parameters, a
    matrix state of whole tiles, 27.4 MB of state a row at 12 layers."""
    cfg = hybrid.HybridConfig.from_published(_published())
    shapes = jax.eval_shape(lambda k: hybrid.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert abs(hybrid.num_params(shapes) / 1e9 - 7.43) < 0.005
    assert (cfg.n_linear, cfg.n_attention) == (24, 8)
    layers, conv, matrix = cfg.state_geometry
    assert conv == (3, 11520) and matrix == (96, 5760)
    assert matrix[0] % 8 == 0 and matrix[1] % 128 == 0
    assert 12 * (96 * 5760 * 4 + 3 * 11520 * 2) == 27_371_520
    assert cfg.kv_geometry == (8, 30, 128) and cfg.max_seq == 65536


@pytest.mark.parametrize("change, named", [
    ({"rope_parameters": {"rope_theta": 10000.0}}, "rope_theta"),
    ({"linear_num_key_heads": 2}, "linear_num_key_heads"),
    ({"linear_allow_neg_eigval": False}, "linear_allow_neg_eigval"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"attention_bias": True}, "attention_bias"),
    ({"layer_types": ["sliding_attention"] * 4}, "layer_types"),
])
def test_what_has_no_form_is_refused_by_name(change, named):
    with pytest.raises(ValueError, match=named):
        hybrid.HybridConfig.from_published({**PUB, **change})


def test_one_pool_holds_one_kind_of_recurrent_state():
    with pytest.raises(ValueError, match="one pool holds one kind"):
        hybrid.HybridConfig.tiny(
            layer_types=(hybrid.MAMBA, hybrid.LINEAR), lin_heads=3,
            lin_key_dim=8, lin_value_dim=16)


def test_state_pool_takes_its_shapes_from_the_model(cfg):
    pool = BlockPool(cfg, n_blocks=8, block_size=8, max_seq=64,
                     state_rows=2)
    st = pool.state
    assert st.conv.shape == (3, 2, 3, 96) and st.conv.dtype == jnp.float32
    assert st.ssm.shape == (3, 2, 8, 48) and st.ssm.dtype == jnp.float32
    assert pool.state_bytes() == st.conv.nbytes + st.ssm.nbytes
    st.swap(st.conv + 1, st.ssm + 1)
    st.admit(1)
    assert float(jnp.abs(st.ssm[:, 1]).max()) == 0.0
    assert float(st.ssm[:, 0].min()) == 1.0


@pytest.mark.parametrize("n", [70, 5, 129])
def test_forward_equals_reference_logits(cfg, params, n):
    toks = np.stack([_tokens(n, 1), _tokens(n, 2)])
    got = np.asarray(hybrid.forward(params, jnp.asarray(toks), cfg))
    for row in range(2):
        want = np.asarray(ref.logits(params, toks[row], PUB))
        np.testing.assert_allclose(got[row], want, atol=ATOL)


def test_reference_controls_move_the_logits(cfg, params):
    """The tolerance tells the stated precision from the next one down:
    a bfloat16 matrix state, and float8 inputs to every product."""
    toks = _tokens(70, 1)
    want = np.asarray(ref.logits(params, toks, PUB))
    for kw in (dict(state_round_to=jnp.bfloat16),
               dict(round_to=jnp.float8_e4m3fn)):
        moved = np.abs(np.asarray(ref.logits(params, toks, PUB, **kw))
                       - want).max()
        assert moved > 100 * ATOL, (kw, moved)


def test_every_assumed_convention_is_in_the_configuration_file():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "olmo-hybrid-7b-16L.json")) as f:
        assumed = json.load(f)["assumed"]
    for key in ("norm_place", "qk_norm", "conv", "gated_norm"):
        assert key in ref.ASSUMED and key in assumed


def test_reference_imports_nothing_from_the_program():
    with open(ref.__file__) as f:
        src = f.read()
    assert "import ray_tpu" not in src and "from ray_tpu" not in src


def test_other_layouts_keep_their_block():
    """The changed residual path is a field: the pre-norm layouts still
    norm the INPUT (and have no q/k norm weights)."""
    tiny = hybrid.HybridConfig.tiny()
    assert not tiny.norm_output and not tiny.qk_norm
    p = hybrid.init_params(tiny, jax.random.PRNGKey(0))
    assert "q_norm" not in p["layers"][1]["mixer"]
    assert tiny.state_geometry == (2, (3, 8 * 16 + 2 * 16), (8 * 16, 16))
