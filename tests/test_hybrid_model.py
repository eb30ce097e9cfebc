"""The hybrid Mamba-2 / attention MoE family (models/hybrid.py) against
its plain references, at tiny widths on the CPU, in BOTH published
layouts of the one layer function: ``granite`` (chipbench/reference/
hybrid_ssm_moe.py; pattern mamba-attention-mamba, every layer followed by
8 gated experts top-3, one B/C group, tied head) and ``nemotron``
(chipbench/reference/nemotron_h.py; pattern ``ME*ME``, single-mixer
layers, 2 B/C groups over 6 heads, 8 relu^2 experts top-3 behind a
sigmoid router with a selection bias, 4 query heads a K/V head, untied
head), d 64.

The ops agree with each other and with the definition; the model's one
layer function agrees with the reference as a full forward, and as
chunked prefill + decode through both caches of the engine; the expert
layer's shares add up to the uncut layer; what a recurrent state makes
impossible is refused by derivation.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from types import SimpleNamespace

from chipbench.reference import hybrid_ssm_moe as ref
from chipbench.reference import nemotron_h as ref_n
from ray_tpu.inference import (EngineConfig, InferenceEngine,
                               SpeculationUnsupported, metrics_snapshot)
from ray_tpu.inference import recurrent
from ray_tpu.inference.cache import BlockPool, PoolLayout, StatePool
from ray_tpu.inference.decode import pack_chunk, pack_step
from ray_tpu.models import hybrid
from ray_tpu.ops import routed_experts as rx
from ray_tpu.ops import ssm
from ray_tpu.ops.attention import mha_reference, packed_attention

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


def _ssm_inputs(b, s, seed=0, groups=1):
    """``groups`` B/C groups: head h reads group h // (H / groups)."""
    H, P, N = 4, 8, 16
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        x=jax.random.normal(k[0], (b, s, H, P)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (b, s, H)) - 2),
        A=-jnp.exp(jax.random.normal(k[2], (H,))),
        B=jax.random.normal(k[3], (b, s, groups, N)),
        C=jax.random.normal(k[4], (b, s, groups, N)), D=jnp.ones((H,)),
        state=jax.random.normal(k[5], (b, H, P, N)))


GROUPS = pytest.mark.parametrize("groups", [1, 2, 4])


# ---------------------------------------------------------------- ops/ssm

@GROUPS
@pytest.mark.parametrize("s", [8, 13, 37])      # one chunk / partial / 4+5
def test_window_scan_equals_recurrence(s, groups):
    a = _ssm_inputs(2, s, groups=groups)
    y0, s0 = ssm.ssd_recurrence(**a)
    y1, s1 = ssm.ssd_window(**a, n_valid=jnp.full((2,), s), chunk=8)
    np.testing.assert_allclose(y1, y0, atol=2e-5)
    np.testing.assert_allclose(s1, s0, atol=2e-5)


def _pool_of(state, layers=1, layer=0):
    """[b, H, P, N] state as ``layer`` of a pool [layers, b, H * P, N]
    whose other layers hold other numbers."""
    b, H, P, N = state.shape
    pool = jax.random.normal(jax.random.PRNGKey(7), (layers, b, H * P, N))
    return pool.at[layer].set(state.reshape(b, H * P, N))


@GROUPS
def test_one_step_form_equals_recurrence(groups):
    a = _ssm_inputs(2, 11, groups=groups)
    y0, s0 = ssm.ssd_recurrence(**a)
    pool, ys = _pool_of(a["state"]), []
    step = jax.jit(ssm.ssd, static_argnames="chunk")
    for t in range(11):
        y, pool = step(a["x"][:, t:t + 1], a["dt"][:, t:t + 1], a["A"],
                       a["B"][:, t:t + 1], a["C"][:, t:t + 1], a["D"],
                       pool, 0, jnp.ones((2,), jnp.int32), chunk=8)
        ys.append(y)
    np.testing.assert_allclose(jnp.concatenate(ys, 1), y0, atol=2e-5)
    np.testing.assert_allclose(pool[0].reshape(s0.shape), s0, atol=2e-5)


def test_tokens_past_n_valid_leave_the_state_alone():
    a = _ssm_inputs(2, 21)
    n_valid = jnp.array([0, 13])
    y, state = ssm.ssd_window(**a, n_valid=n_valid, chunk=8)
    np.testing.assert_array_equal(state[0], a["state"][0])
    cut = {k: (v[1:, :13] if k in ("x", "dt", "B", "C") else v)
           for k, v in a.items()}
    cut["state"] = a["state"][1:]
    y13, s13 = ssm.ssd_recurrence(**cut)
    np.testing.assert_allclose(y[1, :13], y13[0], atol=2e-5)
    np.testing.assert_allclose(state[1], s13[0], atol=2e-5)
    # the one-token form: a row that sits the pass out
    pool = _pool_of(a["state"])
    _, st = ssm.ssd_step(a["x"][:, :1], a["dt"][:, :1], a["A"],
                         a["B"][:, :1], a["C"][:, :1], a["D"], pool, 0,
                         jnp.array([0, 1]))
    np.testing.assert_array_equal(st[0, 0], pool[0, 0])
    assert not np.allclose(st[0, 1], pool[0, 1])


@pytest.mark.parametrize("live", [
    (1, 1, 1, 1, 1), (0, 1, 1, 1, 1), (1, 1, 0, 1, 1), (1, 1, 1, 1, 0),
    (0, 0, 0, 1, 0), (0, 0, 0, 0, 0)],
    ids=["all", "first-idle", "middle-idle", "last-idle", "one", "none"])
@GROUPS
def test_one_step_kernel_touches_live_rows_of_its_layer_only(live, groups):
    """The one-token kernel on a pool of three layers: the live rows of
    ITS layer advance as the definition says; idle rows and the other
    layers come back bit for bit — also from a pass in which no row
    advances at all."""
    a = _ssm_inputs(5, 1, seed=2, groups=groups)
    state = a.pop("state")
    pool = _pool_of(state, layers=3, layer=1)
    n_valid = jnp.array(live, jnp.int32)
    y, new = jax.jit(ssm.ssd_step)(**a, pool=pool, layer=jnp.int32(1),
                                   n_valid=n_valid)
    y0, s0 = ssm.ssd_recurrence(**a, state=state)
    on = np.array(live, bool)
    np.testing.assert_allclose(y[on], y0[on], atol=2e-5)
    np.testing.assert_allclose(new[1][on], s0.reshape(pool.shape[1:])[on],
                               atol=2e-5)
    np.testing.assert_array_equal(new[1][~on], pool[1][~on])
    np.testing.assert_array_equal(new[0], pool[0])
    np.testing.assert_array_equal(new[2], pool[2])


def test_causal_conv_window_equals_token_by_token():
    k = jax.random.split(jax.random.PRNGKey(3), 4)
    b, s, C, K = 2, 9, 6, 4
    x = jax.random.normal(k[0], (b, s, C))
    w, bias = jax.random.normal(k[1], (K, C)), jax.random.normal(k[2], (C,))
    st0 = jax.random.normal(k[3], (b, K - 1, C))
    n_valid = jnp.array([9, 4])
    y, st = ssm.causal_conv(x, st0, w, bias, n_valid)
    state, ys = st0, []
    for t in range(s):
        yt, new = ssm.causal_conv(x[:, t:t + 1], state, w, bias,
                                  (t < n_valid).astype(jnp.int32))
        state = new
        ys.append(yt)
    np.testing.assert_allclose(jnp.concatenate(ys, 1)[0], y[0], atol=1e-6)
    np.testing.assert_allclose(jnp.concatenate(ys, 1)[1, :4], y[1, :4],
                               atol=1e-6)
    np.testing.assert_allclose(state, st, atol=1e-6)
    # the state after 4 real tokens is the last 3 of them
    np.testing.assert_allclose(st[1], x[1, 1:4], atol=1e-6)


# ------------------------------------------------------ ops/routed_experts

def _expert_weights(seed=5, E=8, d=64, f=32):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (d, E)) * 0.1,
            jax.random.normal(k[1], (E, d, 2 * f)) * 0.1,
            jax.random.normal(k[2], (E, f, d)) * 0.1,
            jax.random.normal(k[3], (40, d)))


@pytest.mark.parametrize("m, k, n", [
    (40, 256, 256),     # k and n in 256s: two n tiles
    (87, 64, 128),      # whole-k tiles, n one lane tile
    (300, 384, 640)],   # n an odd number of lane tiles, rows padded
    ids=["even-tiles", "one-tile", "odd-tiles"])
def test_grouped_matmul_equals_group_by_group(m, k, n):
    """``grouped_matmul``: every group's rows times its own matrix, an
    empty group among them; rows past the last group are nobody's."""
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    x, w = jax.random.normal(ks[0], (m, k)), jax.random.normal(ks[1],
                                                               (5, k, n))
    sizes = np.array([m // 3, 0, m // 4, 1, m // 5], np.int32)
    got = np.asarray(rx.grouped_matmul(x, w, jnp.asarray(sizes)))
    at = 0
    for i, size in enumerate(sizes):
        np.testing.assert_allclose(
            got[at:at + size], np.asarray(x[at:at + size] @ w[i]),
            atol=1e-4)
        at += size
    assert got.shape == (m, n)


def test_every_token_to_one_expert_loses_none():
    w_r, w_in, w_out, h = _expert_weights()
    # the router sends everything to expert 5: dropless means all 40
    # tokens are computed, none capped
    w_r = jnp.zeros_like(w_r).at[:, 5].set(jnp.sign(h.sum(0)))
    h = jnp.abs(h) * jnp.sign(h.sum(0))
    out, counts, total = rx.routed_experts(h, w_r, w_in, w_out, top_k=1,
                                           held=(0, 8))
    assert counts.tolist() == [0, 0, 0, 0, 0, 40, 0, 0] and int(total) == 40
    np.testing.assert_allclose(out, rx.mlp(h, w_in[5], w_out[5]),
                               atol=1e-5)


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


def test_bias_chooses_and_unbiased_scores_weigh():
    """Selection by ``score + bias``, weights from the scores WITHOUT
    it: a seeded non-zero bias tells the three apart — choosing by the
    score alone picks other experts, and weighing by the biased score
    gives other weights."""
    w_r, _, _, h = _expert_weights()
    bias = jax.random.normal(jax.random.PRNGKey(11), (8,)) * 0.3
    experts, gates = rx.route(h, w_r, 3, bias, 2.5)
    scores = np.asarray(jax.nn.sigmoid(h @ w_r))
    want = np.argsort(-(scores + np.asarray(bias)), axis=-1)[:, :3]
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(want, -1))
    assert (np.sort(want, -1)
            != np.sort(np.argsort(-scores, -1)[:, :3], -1)).any()
    chosen = np.take_along_axis(scores, np.asarray(experts), -1)
    np.testing.assert_allclose(gates, chosen / chosen.sum(-1, keepdims=True)
                               * 2.5, rtol=1e-6)
    biased = chosen + np.asarray(bias)[np.asarray(experts)]
    assert np.abs(np.asarray(gates) - biased
                  / biased.sum(-1, keepdims=True) * 2.5).max() > 1e-2
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.5, rtol=1e-6)


def test_padding_routes_but_is_not_counted():
    w_r, w_in, w_out, h = _expert_weights()
    valid = jnp.arange(40) < 25
    out, counts, total = rx.routed_experts(h, w_r, w_in[2:6], w_out[2:6],
                                           top_k=3, held=(2, 6),
                                           valid=valid)
    experts, _ = rx.route(h[:25], w_r, 3)
    want = [(np.asarray(experts) == e).sum() for e in range(2, 6)]
    assert counts.tolist() == want and int(total) == 75
    assert out.shape == h.shape


# ------------------------------------------------------------ attention

def test_packed_attention_grouped_queries():
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    b, h, kv, nq, S, hd = 2, 8, 2, 3, 20, 16
    q = jax.random.normal(k[0], (b, h, nq, hd))
    K = jax.random.normal(k[1], (b, S, kv * hd))
    V = jax.random.normal(k[2], (b, S, kv * hd))
    lens = jnp.array([7, 20])
    got = packed_attention(q, K, V, q_per_kv=h // kv, scale=0.1,
                           kv_lengths=lens)

    def heads(t):
        return jnp.repeat(t.reshape(b, S, kv, hd).transpose(0, 2, 1, 3),
                          h // kv, 1)
    want = mha_reference(q, heads(K), heads(V), causal=False, scale=0.1,
                         kv_lengths=lens)
    np.testing.assert_allclose(got, want, atol=1e-5)


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
    for pos in range(0, n_prompt, C):
        n_q = min(C, n_prompt - pos)
        toks = np.zeros(C, np.int32)
        toks[:n_q] = seq[pos:pos + n_q]
        logits, load, (k, v), (conv, s_) = chunk(
            params, (k, v), (conv, s_),
            pack_chunk(table, toks, pos, row, n_q))
        np.testing.assert_allclose(np.asarray(logits)[:n_q],
                                   want[pos:pos + n_q], atol=fam.atol)
        assert load.tolist()[:2] == [n_q * per_token] * 2
        # experts touched: at least one a layer, at most the tokens' picks
        assert fam.expert_layers <= int(load[3]) <= min(
            n_q * per_token, 8 * fam.expert_layers)
        # the last REAL position's greedy token rides with the load
        assert int(load[hybrid.N_LOAD]) == int(
            np.asarray(logits)[n_q - 1].argmax())
    tables = np.zeros((n_rows, T), np.int32)
    tables[row] = table
    active = np.zeros(n_rows, bool)
    active[row] = True
    for pos in range(n_prompt, 30):
        tokens = np.zeros(n_rows, np.int32)
        positions = np.zeros(n_rows, np.int32)
        tokens[row], positions[row] = seq[pos], pos
        logits, load, (k, v), (conv, s_) = step(
            params, (k, v), (conv, s_),
            pack_step(tables, tokens, positions, active))
        np.testing.assert_allclose(np.asarray(logits)[row], want[pos],
                                   atol=fam.atol)
        # 1 token x top-3 x expert layers, each pick another expert
        assert load.tolist()[:4] == [per_token, per_token,
                                     fam.expert_layers, per_token]
    np.testing.assert_array_equal(np.asarray(s_[:, 0]), 1.5)
    assert float(jnp.abs(s_[:, 2]).max()) == 0.0


# ------------------------------------------------------ through the engine

def _margins(fam, prompt, emitted):
    """How far each emitted token's reference logit lies below that
    position's maximum (teacher-forced full forward)."""
    seq = np.asarray(list(prompt) + list(emitted))
    step = _ref_logits(fam, seq)[len(prompt) - 1:len(seq) - 1]
    return step.max(-1) - step[np.arange(len(emitted)), emitted]


def _engine(fam, **kw):
    ec = dict(max_slots=3, max_seq=96, n_blocks=14, kv_block_size=8,
              prefill_chunk=8)
    return InferenceEngine(fam.params, fam.cfg,
                           EngineConfig(**{**ec, **kw}))


def test_engine_rows_admitted_at_different_times(fam):
    """Continuous batching: rows join while others decode, one finishes
    mid-batch; every emitted token is the reference's argmax."""
    eng = _engine(fam)
    rng = np.random.default_rng(0)
    plan = [(5, 6), (19, 10), (33, 3), (8, 12), (27, 7)]
    prompts = [rng.integers(0, 256, n).tolist() for n, _ in plan]
    reqs = []
    for p, (_, m) in zip(prompts, plan):
        reqs.append(eng.submit(p, max_new=m))
        time.sleep(0.05)
    outs = [r.result(timeout=300) for r in reqs]
    st = eng.stats()
    eng.shutdown()
    for p, o, (_, m) in zip(prompts, outs, plan):
        assert len(o) == m
        assert _margins(fam, p, o).max() <= fam.atol
    tokens = sum(n + m - 1 for n, m in plan)
    assert st["expert_assignments_total"] == tokens * fam.top_k \
        * fam.expert_layers
    assert fam.expert_layers * st["decode_iterations"] \
        <= st["expert_touched_held_decode"] < st["expert_touched_held"] \
        <= st["expert_assignments_held"]
    assert st["expert_assignments_held"] == st["expert_assignments_total"]
    assert st["expert_load_max"] >= st["expert_assignments_held"] / 8
    assert st["state_rows_in_use"] == 0 and st["state_bytes"] > 0
    assert st["cache_bytes"] > st["state_bytes"]
    assert st["prefix_hit_tokens"] == 0 and st["chunk_passes"] >= 12


def test_first_token_behind_a_running_decode(fam):
    """A prompt that ends while other rows decode: its first token is
    not waited for before the pass's decode step is dispatched, the row
    joins the batch a pass later, and a request that its first token
    ends never decodes.  Streams are the reference's, token for token."""
    eng = _engine(fam)
    rng = np.random.default_rng(3)
    long_ = rng.integers(0, 256, 6).tolist()
    first = eng.submit(long_, max_new=40)
    it = first.stream(timeout=300)
    head = [next(it) for _ in range(3)]           # it is decoding now
    plan = [(11, 1), (17, 5), (4, 1)]
    prompts = [rng.integers(0, 256, n).tolist() for n, _ in plan]
    reqs = [eng.submit(p, max_new=m) for p, (_, m) in zip(prompts, plan)]
    outs = [r.result(timeout=300) for r in reqs]
    whole = head + list(it)
    assert eng._first_pending == [] and eng.stats()["active_slots"] == 0
    eng.shutdown()
    assert len(whole) == 40 and _margins(fam, long_, whole).max() <= fam.atol
    for p, o, (_, m) in zip(prompts, outs, plan):
        assert len(o) == m and _margins(fam, p, o).max() <= fam.atol


def test_engine_preemption_and_re_prefill(fam):
    """A pool too small for all rows: the youngest is preempted, drops
    its state with its blocks, re-prefills from zero and continues its
    stream exactly."""
    eng = _engine(fam, n_blocks=12, max_slots=3)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).tolist() for n in (30, 28, 26)]
    reqs = [eng.submit(p, max_new=24) for p in prompts]
    outs = [r.result(timeout=300) for r in reqs]
    st = eng.stats()
    eng.shutdown()
    assert st["preemptions"] >= 1
    for p, o in zip(prompts, outs):
        assert len(o) == 24 and _margins(fam, p, o).max() <= fam.atol


def test_recurrent_family_refuses_by_derivation(fam):
    eng = _engine(fam, prefix_cache=True)
    try:
        assert eng.trie is None            # nothing is ever adopted
        a = list(range(40))
        eng.generate(a, max_new=2, timeout=300)
        eng.generate(a, max_new=2, timeout=300)
        assert eng.stats()["prefix_hit_tokens"] == 0
    finally:
        eng.shutdown()
    for mode in ("ngram", "self"):
        with pytest.raises(SpeculationUnsupported):
            _engine(fam, speculate=mode)


def test_new_counters_are_exported(fam):
    eng = _engine(fam)
    try:
        eng.generate([1, 2, 3], max_new=3, timeout=300)
        names = {m[0]: m for m in metrics_snapshot()}
        for name in ("ray_tpu_inference_state_bytes",
                     "ray_tpu_inference_state_rows_in_use",
                     "ray_tpu_inference_expert_assignments_held_total",
                     "ray_tpu_inference_expert_assignments_total",
                     "ray_tpu_inference_expert_load_max_total",
                     "ray_tpu_inference_expert_touched_held_total",
                     "ray_tpu_inference_expert_touched_held_decode_total"):
            assert name in names
        key = next(k for k in
                   names["ray_tpu_inference_expert_assignments_total"][3]
                   if dict(k).get("engine") == eng.name)
        assert names["ray_tpu_inference_expert_assignments_total"][3][key] \
            == 5 * fam.top_k * fam.expert_layers
        # 3 prompt tokens in one chunk, then 2 decode steps of one token
        touched = names["ray_tpu_inference_expert_touched_held_decode_total"]
        assert touched[3][key] == 2 * fam.top_k * fam.expert_layers
    finally:
        eng.shutdown()
    # a model that keeps K/V only reports zeros under the same keys
    from ray_tpu.models import gpt
    cfg = gpt.GPTConfig.tiny()
    eng = InferenceEngine(gpt.init_params(cfg, jax.random.PRNGKey(0)), cfg,
                          EngineConfig(max_slots=2))
    try:
        eng.generate([1, 2, 3], max_new=2, timeout=300)
        st = eng.stats()
        assert st["state_bytes"] == st["expert_assignments_total"] \
            == st["expert_touched_held"] == 0
    finally:
        eng.shutdown()


def test_served_through_the_deployment(fam):
    """The same server class and builder as GPT: ``serve.run`` of
    ``build_gpt_deployment(cfg=<hybrid>)``."""
    from ray_tpu import serve
    from ray_tpu.inference import build_gpt_deployment
    handle = serve.run(
        build_gpt_deployment(
            name="hy", cfg=fam.cfg, params=fam.params, warm_on_init=True,
            engine_cfg=EngineConfig(max_slots=2, max_seq=96, n_blocks=12,
                                    kv_block_size=8, prefill_chunk=8)),
        use_actors=False)
    try:
        prompt = list(range(3, 20))
        got = handle.remote({"prompt": prompt, "max_tokens": 5}).result(
            timeout=300)
        assert _margins(fam, prompt, got["tokens"]).max() <= fam.atol
        st = handle.options(method_name="engine_stats").remote().result(
            timeout=30)
        assert st["state_bytes"] > 0
    finally:
        serve.shutdown()


def test_sampled_rows_beside_greedy_rows(fam):
    """A greedy pass fetches tokens, not logits (they stay on the
    device); a sampled row indexes them there with its own rng: the same
    seed gives the same stream, and its greedy neighbour stays exact."""
    outs = []
    for _ in range(2):
        eng = _engine(fam)
        try:
            hot = eng.submit(list(range(9)), max_new=8, temperature=0.9,
                             seed=5)
            cold = eng.submit(list(range(20, 31)), max_new=8)
            outs.append((hot.result(timeout=300), cold.result(timeout=300)))
        finally:
            eng.shutdown()
    assert outs[0] == outs[1]
    assert _margins(fam, list(range(20, 31)), outs[0][1]).max() <= fam.atol
    assert _margins(fam, list(range(9)), outs[0][0]).max() > fam.atol
