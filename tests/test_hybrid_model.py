"""The hybrid Mamba-2 / attention MoE family (models/hybrid.py) against
its plain references, at tiny widths on the CPU, in BOTH published
layouts of the one layer function: ``granite`` (chipbench/reference/
hybrid_ssm_moe.py; pattern mamba-attention-mamba, every layer followed by
8 gated experts top-3, one B/C group, tied head) and ``nemotron``
(chipbench/reference/nemotron_h.py; pattern ``ME*ME``, single-mixer
layers, 2 B/C groups over 6 heads, 8 relu^2 experts top-3 behind a
sigmoid router with a selection bias, 4 query heads a K/V head, untied
head), d 64.

The model's one layer function agrees with the reference as a full
forward, and as chunked prefill + decode through both caches of the
engine; the expert layer's shares add up to the uncut layer.  The ops
are held to their definitions in tests/test_hybrid_ops.py, and the
engine serves both layouts in tests/test_hybrid_engine.py, which takes
``fam`` from here (one file until PR 43: under `--dist loadfile` it held
one worker for more than half of a tier-1 run).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from types import SimpleNamespace

from chipbench.reference import hybrid_ssm_moe as ref
from chipbench.reference import nemotron_h as ref_n
from ray_tpu.inference import recurrent
from ray_tpu.inference.cache import BlockPool, PoolLayout, StatePool
from ray_tpu.inference.decode import pack_chunk, pack_step
from ray_tpu.models import hybrid
from ray_tpu.ops import routed_experts as rx

CFG = hybrid.HybridConfig.tiny()
# the same model under the published config's own key names (what the
# reference reads)
PUBLISHED = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    attention_multiplier=1 / 16, residual_multiplier=0.22,
    embedding_multiplier=12.0, logits_scaling=16.0, rms_norm_eps=1e-5,
    mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_n_groups=1,
    mamba_d_conv=4, mamba_chunk_size=8, mamba_expand=2,
    num_local_experts=8, num_experts_per_tok=3, intermediate_size=32,
    shared_intermediate_size=48, vocab_size=256,
    max_position_embeddings=128,
    layer_types=["mamba", "attention", "mamba"], num_hidden_layers=3)
HELD = (0, 8)


# the second layout under ITS published config's key names
PUBLISHED_N = dict(
    model_type="nemotron_h", hidden_size=64, num_attention_heads=8,
    num_key_value_heads=2, head_dim=16, layer_norm_epsilon=1e-5,
    mamba_num_heads=6, mamba_head_dim=16, ssm_state_size=16, n_groups=2,
    conv_kernel=4, chunk_size=8, expand=2, n_routed_experts=8,
    num_experts_per_tok=3, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=48, n_shared_experts=1, n_group=1,
    topk_group=1, norm_topk_prob=True, routed_scaling_factor=2.5,
    tie_word_embeddings=False, vocab_size=256,
    max_position_embeddings=128, hybrid_override_pattern="ME*MEM*",
    num_hidden_layers=5)
CFG_N = hybrid.HybridConfig.from_published(
    PUBLISHED_N, dtype=jnp.float32, param_dtype=jnp.float32)


def _init(cfg):
    return jax.jit(lambda k: hybrid.init_params(cfg, k))(
        jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def params():
    return _init(CFG)


@pytest.fixture(scope="module")
def params_n():
    p = _init(CFG_N)
    # a selection bias large enough to change what is chosen (scores
    # lie in 0.4-0.6 at these widths)
    for lp in p["layers"]:
        if "ffn" in lp:
            lp["ffn"]["router_bias"] = lp["ffn"]["router_bias"] * 10
    return p


@pytest.fixture(scope="module", params=["granite", "nemotron"])
def fam(request, params, params_n):
    """One of the two layouts: its config, weights, published keys,
    reference and the tolerance of a float32 logit against it.  The
    second's logits are ~25 times the first's (std 0.16 against 0.0067:
    no multipliers, an untied N(0, 0.02) head), and so is the rounding
    of a float32 sum."""
    if request.param == "granite":
        return SimpleNamespace(cfg=CFG, params=params, published=PUBLISHED,
                               ref=ref, atol=1e-5, top_k=3, expert_layers=3)
    return SimpleNamespace(cfg=CFG_N, params=params_n,
                           published=PUBLISHED_N, ref=ref_n, atol=5e-5,
                           top_k=3, expert_layers=2)


# ------------------------------------------- the expert layer's shares

def test_shares_add_up_to_the_uncut_layer(params):
    """The share test: the routed parts of the two halves of the
    experts, plus the shared expert counted ONCE, are the reference's
    whole layer."""
    fp = params["layers"][0]["ffn"]
    h = jax.random.normal(jax.random.PRNGKey(9), (29, CFG.d_model))
    parts, held_counts, totals = [], [], []
    for lo, hi in ((0, 4), (4, 8)):
        out, counts, total = rx.routed_experts(
            h, fp["router"], fp["w_in"][lo:hi], fp["w_out"][lo:hi],
            top_k=CFG.experts_per_token, held=(lo, hi))
        parts.append(out)
        held_counts.append(int(counts.sum()))
        totals.append(int(total))
    shared = rx.mlp(h, fp["shared_in"], fp["shared_out"])
    with jax.default_matmul_precision("highest"):
        whole = ref._experts(PUBLISHED, fp, h, HELD, None)
    np.testing.assert_allclose(parts[0] + parts[1] + shared, whole,
                               atol=1e-5)
    assert sum(held_counts) == totals[0] == totals[1] == 29 * 3
    # and a share alone is the reference given the same share
    with jax.default_matmul_precision("highest"):
        half = ref._experts(
            PUBLISHED, {**fp, "w_in": fp["w_in"][:4],
                        "w_out": fp["w_out"][:4]}, h, (0, 4), None)
    np.testing.assert_allclose(parts[0] + shared, half, atol=1e-5)


def test_relu2_shares_add_up_to_the_uncut_layer(params_n):
    """The share test of the second form: the two halves' routed parts
    plus the shared expert counted ONCE are the uncut reference layer
    (sigmoid scores, a non-zero selection bias, relu^2 experts)."""
    fp = next(lp["ffn"] for lp in params_n["layers"] if "ffn" in lp)
    h = jax.random.normal(jax.random.PRNGKey(9), (29, CFG_N.d_model))
    parts, touched = [], 0
    for lo, hi in ((0, 4), (4, 8)):
        out, counts, total = rx.routed_experts(
            h, fp["router"], fp["w_in"][lo:hi], fp["w_out"][lo:hi],
            top_k=3, held=(lo, hi), gated=False, bias=fp["router_bias"],
            scale=2.5)
        parts.append(out)
        touched += int(counts.sum())
        assert int(total) == 29 * 3
    assert touched == 29 * 3
    # the stack is stored with zero columns up to a whole lane tile
    assert fp["w_in"].shape == (8, 64, 128) and fp["w_out"].shape[1] == 32
    assert float(jnp.abs(fp["w_in"][..., 32:]).max()) == 0.0
    shared = rx.mlp(h, fp["shared_in"], fp["shared_out"], gated=False)
    with jax.default_matmul_precision("highest"):
        whole = ref_n._experts(PUBLISHED_N, fp, h, (0, 8), None)
        half = ref_n._experts(
            PUBLISHED_N, {**fp, "w_in": fp["w_in"][:4],
                          "w_out": fp["w_out"][:4]}, h, (0, 4), None)
    np.testing.assert_allclose(parts[0] + parts[1] + shared, whole,
                               atol=1e-5)
    np.testing.assert_allclose(parts[0] + shared, half, atol=1e-5)


# ------------------------------------------------------------- the caches

def test_pool_layout_from_kv_geometry():
    big = hybrid.HybridConfig(vocab_size=512, experts_held=(0, 1))
    assert big.kv_geometry == (1, 8, 128)
    lay = PoolLayout(*big.kv_geometry[:1], 5, 16, *big.kv_geometry[1:])
    assert lay.width == 1024 and lay.shape == (5, 16, 1024)
    # the second published layout at its own widths: 2 attention layers
    # x 2 K/V heads of 128 = 256 lanes a stored row; 6 state layers of
    # [4096, 128] with a convolution over 4096 + 2 x 8 x 128 channels
    nano = hybrid.HybridConfig.from_published(
        {**PUBLISHED_N, "hidden_size": 2688, "num_attention_heads": 32,
         "head_dim": 128, "mamba_num_heads": 64, "mamba_head_dim": 64,
         "ssm_state_size": 128, "n_groups": 8,
         "hybrid_override_pattern": "MEMEM*EMEMEM*", "num_hidden_layers": 13})
    assert nano.kv_geometry == (2, 2, 128)
    assert nano.state_geometry == (6, (3, 6144), (4096, 128))
    assert PoolLayout(2, 5, 16, 2, 128).width == 256
    pool = BlockPool(CFG, n_blocks=12, block_size=8, max_seq=96,
                     state_rows=3)
    # K/V on the ONE attention layer, 2 K/V heads of 16
    assert pool.layout.n_layers == 1 and pool.layout.n_heads == 2
    assert PoolLayout.of(CFG, pool.k) == pool.layout


def test_state_pool_lifecycle_and_bytes():
    pool = BlockPool(CFG, n_blocks=12, block_size=8, max_seq=96,
                     state_rows=3)
    st = pool.state
    assert isinstance(st, StatePool)
    assert st.conv.shape == (2, 3, 3, 8 * 16 + 2 * 16)
    assert st.ssm.shape == (2, 3, 8 * 16, 16) and st.ssm.dtype == jnp.float32
    kv_bytes = 2 * int(np.prod(pool.layout.shape)) * 4
    assert pool.bytes_total() == kv_bytes + st.bytes_total()
    assert st.bytes_total() == st.conv.nbytes + st.ssm.nbytes
    st.swap(st.conv + 1, st.ssm + 1)
    st.admit(1)
    assert st.rows_in_use == 1 and pool.stats()["state_rows_in_use"] == 1
    assert float(jnp.abs(st.ssm[:, 1]).max()) == 0.0
    assert float(jnp.abs(st.conv[:, 1]).max()) == 0.0
    assert float(st.ssm[:, 0].min()) == 1.0          # neighbours untouched
    st.release(1)
    assert st.rows_in_use == 0
    st.admit(2)
    pool.reset()
    assert pool.state.rows_in_use == 0
    assert float(jnp.abs(pool.state.ssm).max()) == 0.0
    # a model that keeps K/V only has no state pool
    from ray_tpu.models.gpt import GPTConfig
    assert BlockPool(GPTConfig.tiny(), 16, 8).state is None


# ---------------------------------------------------- model vs reference

def _ref_logits(fam, seq, **kw):
    return np.asarray(fam.ref.logits(fam.params, np.asarray(seq),
                                     fam.published, HELD, **kw))


def test_from_published_keys():
    cfg = hybrid.HybridConfig.from_published(
        PUBLISHED, dtype=jnp.float32, param_dtype=jnp.float32)
    assert cfg == CFG


def test_from_published_nemotron_h_keys():
    """The other key set: the pattern string's ``M`` / ``E`` / ``*``
    (its first ``num_hidden_layers`` characters), one sublayer a layer."""
    c = CFG_N
    assert c.layer_types == ("mamba", "experts", "attention", "mamba",
                             "experts")
    assert c.sublayers == tuple(enumerate(c.layer_types))
    assert CFG.sublayers == ((0, "mamba"), (0, "experts"),
                             (1, "attention"), (1, "experts"),
                             (2, "mamba"), (2, "experts"))
    assert (c.ssm_inner, c.ssm_groups, c.conv_channels) == (96, 2, 160)
    assert (c.n_heads, c.n_kv_heads, c.head_dim) == (8, 2, 16)
    assert not (c.tied_head or c.gated_experts or c.experts_in_every_layer)
    assert c.routed_scale == 2.5 and c.attention_multiplier == 0.25
    assert (c.embedding_multiplier, c.residual_multiplier,
            c.logits_scaling) == (1.0, 1.0, 1.0)
    assert c.kv_geometry == (1, 2, 16) and c.state_geometry[0] == 2


@pytest.mark.parametrize("change, said", [
    ({"hybrid_override_pattern": "ME-ME*"}, "dense MLP layer"),
    ({"n_shared_experts": 2}, "n_shared_experts"),
    ({"topk_group": 2}, "topk_group"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings")])
def test_from_published_refuses_what_it_has_no_form_for(change, said):
    with pytest.raises(ValueError, match=said):
        hybrid.HybridConfig.from_published({**PUBLISHED_N, **change})


def test_forward_logits_equal_reference(fam):
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 37), 0, 256)
    got = np.asarray(hybrid.forward(fam.params, toks, fam.cfg))
    for b in range(2):
        # a fifth of the programs' tolerance: one window, no cache
        np.testing.assert_allclose(got[b], _ref_logits(fam, toks[b]),
                                   atol=fam.atol / 5)


def test_reference_in_lower_precision_differs(fam):
    """``round_to`` is the reading a serving tolerance has to reject:
    the reference with float8 inputs to every product lies far outside
    the tolerance the served path is held to, and bfloat16 outside it
    too (1.7 x it at the least: the first layout)."""
    toks = np.arange(40) % 256
    full = _ref_logits(fam, toks)
    low = _ref_logits(fam, toks, round_to=jnp.float8_e4m3fn)
    assert np.abs(full - low).max() > 50 * np.abs(
        full - np.asarray(hybrid.forward(fam.params, toks[None],
                                         fam.cfg))[0]).max()
    assert np.abs(full - low).max() > 100 * fam.atol
    bf = _ref_logits(fam, toks, round_to=jnp.bfloat16)
    assert np.abs(full - bf).max() > fam.atol


def test_programs_chunks_then_decode_equal_reference_logits(fam):
    params, CFG = fam.params, fam.cfg
    per_token = fam.top_k * fam.expert_layers
    """The chunk program over a prompt (a partial last chunk), then the
    decode program token by token, both through the K/V pool and the
    state pool: every position's logits are the reference's."""
    bs, C, n_rows = 8, 8, 3
    pool = BlockPool(CFG, n_blocks=12, block_size=bs, max_seq=96,
                     state_rows=n_rows)
    T = pool.blocks_per_seq
    step = recurrent.make_recurrent_decode_step(CFG, block_size=bs,
                                                n_table=T)
    chunk = recurrent.make_recurrent_chunk_fn(CFG, chunk=C, block_size=bs,
                                              n_table=T)
    seq = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (30,), 0,
                                        256))
    n_prompt, row = 21, 1
    want = _ref_logits(fam, seq)
    table = np.zeros(T, np.int32)
    table[:4] = [3, 7, 2, 9]
    k, v, conv, s_ = pool.k, pool.v, pool.state.conv, pool.state.ssm
    # neighbours' state must come back untouched
    s_ = s_.at[:, 0].set(1.5)
    feed = jnp.zeros(n_rows, jnp.int32)     # the rows' next tokens
    for pos in range(0, n_prompt, C):
        n_q = min(C, n_prompt - pos)
        toks = np.zeros(C, np.int32)
        toks[:n_q] = seq[pos:pos + n_q]
        logits, load, (k, v), (conv, s_), feed = chunk(
            params, (k, v), (conv, s_), feed,
            pack_chunk(table, toks, pos, row, n_q))
        np.testing.assert_allclose(np.asarray(logits)[:n_q],
                                   want[pos:pos + n_q], atol=fam.atol)
        assert load.tolist()[:2] == [n_q * per_token] * 2
        # experts touched: at least one a layer, at most the tokens' picks
        assert fam.expert_layers <= int(load[3]) <= min(
            n_q * per_token, 8 * fam.expert_layers)
        # the last REAL position's greedy token rides with the load
        assert int(load[hybrid.N_LOAD]) == int(
            np.asarray(logits)[n_q - 1].argmax()) == int(feed[row])
    tables = np.zeros((n_rows, T), np.int32)
    tables[row] = table
    active = np.zeros(n_rows, bool)
    active[row] = True
    for pos in range(n_prompt, 30):
        tokens = np.zeros(n_rows, np.int32)
        positions = np.zeros(n_rows, np.int32)
        tokens[row], positions[row] = seq[pos], pos
        logits, load, (k, v), (conv, s_), feed = step(
            params, (k, v), (conv, s_), feed,
            pack_step(tables, tokens, positions, active))
        np.testing.assert_allclose(np.asarray(logits)[row], want[pos],
                                   atol=fam.atol)
        # 1 token x top-3 x expert layers, each pick another expert
        assert load.tolist()[:4] == [per_token, per_token,
                                     fam.expert_layers, per_token]
    np.testing.assert_array_equal(np.asarray(s_[:, 0]), 1.5)
    assert float(jnp.abs(s_[:, 2]).max()) == 0.0
