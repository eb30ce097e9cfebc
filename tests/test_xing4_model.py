"""The hybrid layer function in its ``xing4_0`` layout (a residual of
four streams, manifold-constrained hyper-connections, round every
latent-attention and feed-forward sublayer; sigmoid scores chosen by
``score + bias``, every expert held, one shared; a multi-token-prediction
module) at a tiny size on the CPU, held to the plain reference
(chipbench/reference/xing4.py, float32): the full forward and the
prediction module's logits, the maps alone, the serving programs through
the latent pool, and the engine — cold, through an adopted prefix and
across a preemption."""

import math
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import xing4 as ref
from ray_tpu.inference import EngineConfig, InferenceEngine
from ray_tpu.inference import recurrent
from ray_tpu.inference.cache import BlockPool, RadixIndex
from ray_tpu.inference.decode import pack_chunk, pack_step
from ray_tpu.models import hybrid
from ray_tpu.ops import hyper_connections as hc

PUB = dict(
    model_type="xing4_0", vocab_size=256, hidden_size=64,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
    moe_intermediate_size=32, n_routed_experts=8, n_shared_experts=1,
    num_experts_per_tok=4, first_k_dense_replace=1, moe_layer_freq=1,
    n_group=1, topk_group=1, topk_method="noaux_tc", norm_topk_prob=True,
    scoring_func="sigmoid", routed_scaling_factor=2, rms_norm_eps=1e-6,
    rope_theta=10000, num_nextn_predict_layers=1, hc_mult=4,
    hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30,
    rope_scaling=dict(type="yarn", factor=64,
                      original_max_position_embeddings=16, beta_fast=32,
                      beta_slow=1, mscale=1, mscale_all_dim=1),
    max_position_embeddings=640, tie_word_embeddings=False)
F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32, max_seq=128)
HELD = (0, 8)
# float32 program against float32 reference through 6 sublayers and a
# head: the two differ by the ORDER of float32 sums alone (read: 3e-7 on
# logits of std 0.16); 1e-4 leaves that 300 x of room and is 13 x under
# what the maps in bfloat16 move the logits by (1.3e-3: the test below)
ATOL = 1e-4


@pytest.fixture(scope="module")
def cfg():
    return hybrid.HybridConfig.from_published(PUB, **F32)


@pytest.fixture(scope="module")
def params(cfg):
    """``init_params`` with ``Phi`` at the standard deviation that the
    published widths' N(0, 0.02) gives ``v Phi`` (2.4): at 4 x 64 lanes
    N(0, 0.02) would leave the maps all but constant."""
    p = hybrid.init_params(cfg, jax.random.PRNGKey(0))
    scale = 2.4 / (0.02 * math.sqrt(cfg.hc_mult * cfg.d_model))

    def widen(tree):
        if isinstance(tree, dict):
            return {k: v * scale if k == "phi" else widen(v)
                    for k, v in tree.items()}
        if isinstance(tree, list):
            return [widen(v) for v in tree]
        return tree
    return widen(p)


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _ref(params, toks, **kw):
    return np.asarray(ref.logits(params, np.asarray(toks), PUB, HELD, **kw))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n)


# ----------------------------------------------------------- the config

def test_config_from_published_keys(cfg):
    assert cfg.layer_types == (hybrid.LATENT,) * 3
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps,
            cfg.hc_res_clamp, cfg.mtp_layers) == (4, 20, 1e-6, (-30, 30), 1)
    assert cfg.routes_by_sigmoid and cfg.gated_experts
    assert (cfg.route_groups, cfg.route_eps, cfg.routed_scale,
            cfg.norm_topk) == ((), 1e-20, 2, True)
    assert (cfg.dense_layers, cfg.shared_width, cfg.experts_per_token) == (
        1, 32, 4)
    # nothing new is cached: the pool is the latent layout's own
    assert cfg.kv_geometry == (3, 1, 40) and cfg.state_geometry is None
    assert not recurrent.has_step_chunk(cfg)
    # every expert is held: no share of a layer is left out
    assert cfg.experts_held == (0, PUB["n_routed_experts"])
    assert cfg.n_held == cfg.n_experts


def test_published_widths_give_the_stated_parameters():
    pub = {**PUB, "vocab_size": 131072, "hidden_size": 3584,
           "num_hidden_layers": 6, "num_attention_heads": 32,
           "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
           "qk_rope_head_dim": 64, "v_head_dim": 128,
           "intermediate_size": 9216, "moe_intermediate_size": 1024,
           "n_routed_experts": 64, "num_nextn_predict_layers": 0,
           "rope_scaling": {**PUB["rope_scaling"],
                            "original_max_position_embeddings": 4096}}
    big = hybrid.HybridConfig.from_published(pub, max_seq=16640)
    assert big.attention_multiplier == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2)
    assert big.kv_geometry == (6, 1, 576) and big.value_lanes == 512
    shapes = jax.eval_shape(lambda k: hybrid.init_params(big, k),
                            jax.random.PRNGKey(0))
    assert "mtp" not in shapes
    n = hybrid.num_params(shapes)
    # ISSUE 61's reckoning: 4,792 M (the maps 0.36 M a sublayer)
    assert 4.78e9 < n < 4.80e9, n
    maps = shapes["layers"][1]["ffn"]["hc"]
    assert maps["phi"].shape == (24, 4 * 3584)
    assert maps["phi"].dtype == jnp.float32
    with_mtp = jax.eval_shape(
        lambda k: hybrid.init_params(hybrid.HybridConfig.from_published(
            {**pub, "num_nextn_predict_layers": 1}, max_seq=16640), k),
        jax.random.PRNGKey(0))
    # one more whole layer, [2 C, C] and three norms: 1.49 GB of bfloat16
    assert 7.6e8 < hybrid.num_params(with_mtp) - n < 7.8e8


@pytest.mark.parametrize("change, named", [
    ({"n_group": 2}, "n_group"),
    ({"topk_group": 2}, "topk_group"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"topk_method": "group_limited_greedy"}, "topk_method"),
    ({"scoring_func": "tanh"}, "scoring_func"),
])
def test_what_has_no_form_is_refused_by_name(change, named):
    with pytest.raises(ValueError, match=named):
        hybrid.HybridConfig.from_published({**PUB, **change})


def test_the_other_latent_layout_reads_no_new_key():
    """``deepseek_v2``'s keys give the fields they gave: one stream, no
    prediction module, the softmax router's groups."""
    from tests.test_latent_model import PUB as DEEPSEEK
    old = hybrid.HybridConfig.from_published(DEEPSEEK)
    assert (old.hc_mult, old.mtp_layers, old.sigmoid_router,
            old.route_groups, old.route_eps) == (0, 0, None, (4, 2), 0.0)
    p = jax.eval_shape(lambda k: hybrid.init_params(old, k),
                       jax.random.PRNGKey(0))
    assert "hc" not in p["layers"][0]["mixer"] and "mtp" not in p


def test_a_one_stream_layout_never_imports_the_mix():
    """The mix's module is imported inside the layout's branch: a process
    that traces another layout's forward has not loaded it."""
    code = (
        "import sys, jax, jax.numpy as jnp\n"
        "from ray_tpu.models import hybrid\n"
        "from ray_tpu.inference import recurrent, engine\n"
        "cfg = hybrid.HybridConfig.tiny()\n"
        "p = hybrid.init_params(cfg, jax.random.PRNGKey(0))\n"
        "jax.eval_shape(lambda p: hybrid.forward(p, jnp.zeros((1, 8), "
        "jnp.int32), cfg), p)\n"
        "assert 'ray_tpu.ops.hyper_connections' not in sys.modules\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=170,
                   env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})


# ------------------------------------------------------------- the maps

def _streams(t, seed=3):
    return jax.random.normal(jax.random.PRNGKey(seed), (t, 4, 64))


def _maps(cfg, hp, x):
    pre, post, res = hc.maps(
        x.reshape(x.shape[0], -1), hp, n=cfg.hc_mult,
        iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps, clamp=cfg.hc_res_clamp)
    return pre.T, post.T, jnp.moveaxis(res, -1, 0)


def test_maps_equal_the_reference_and_are_doubly_stochastic(cfg, params):
    hp = params["layers"][1]["ffn"]["hc"]
    x = _streams(33)
    got = _maps(cfg, hp, x)
    want = ref.maps(PUB, hp, x)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-6)
    res = np.asarray(got[2])
    assert res.min() > 0
    # the last normalisation is the columns': exact to float32 rounding;
    # the rows are as close as 20 iterations bring a 4 x 4 matrix whose
    # entries span e^-7 .. e^7 (v Phi has std 2.4): read 9e-3 at worst
    # over these 33 tokens, most under 1e-4
    np.testing.assert_allclose(res.sum(1), 1.0, atol=1e-6)
    np.testing.assert_allclose(res.sum(2), 1.0, atol=2e-2)
    assert np.median(np.abs(res.sum(2) - 1.0)) < 1e-4
    # ... and NOT doubly stochastic after one iteration: the 20 matter
    once = np.asarray(hc.sinkhorn(jnp.moveaxis(_raw_res(hp, x), 0, -1), 1))
    assert np.abs(once.sum(1) - 1.0).max() > 1e-2     # rows, [n, n, T]
    assert 0 < np.asarray(got[0]).min() and np.asarray(got[0]).max() < 1
    assert 0 < np.asarray(got[1]).min() and np.asarray(got[1]).max() < 2
    # the maps depend on their input to order 1 (else no check feels them)
    assert np.asarray(got[0]).std() > 0.2


def _raw_res(hp, x):
    """exp(A_res) [T, n, n] before any iteration, by the definition."""
    flat = x.reshape(x.shape[0], -1)
    v = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                             + 1e-6) * hp["w"]
    a = v @ hp["phi"].T
    return jnp.exp(jnp.clip(hp["alpha"][2] * a[:, 8:] + hp["b"][8:], -30,
                            30)).reshape(-1, 4, 4)


def test_the_clamp_is_reached_by_a_constructed_input(cfg, params):
    """An offset of +-100 on two entries of ``A_res``: unclamped,
    exp(100) is inf in float32 and the iterations give NaN; clamped to
    +-30 the map is finite, equals the reference's, entry (0, 0) takes
    its row and column (as far as 20 iterations go: 0.95-0.99) and
    entry (0, 1) is e^-60 of it."""
    hp = dict(params["layers"][0]["mixer"]["hc"])
    hp["b"] = hp["b"].at[8].set(100.0).at[9].set(-100.0)
    x = _streams(9, seed=4)
    _, _, res = _maps(cfg, hp, x)
    assert np.isfinite(np.asarray(res)).all()
    np.testing.assert_allclose(res, ref.maps(PUB, hp, x)[2], atol=1e-6)
    assert np.asarray(res)[:, 0, 0].min() > 0.9
    assert np.asarray(res)[:, 0, 1].max() < 1e-20
    open_cfg = hybrid.HybridConfig.from_published(
        {**PUB, "mhc_h_res_clamp_min": -200, "mhc_h_res_clamp_max": 200},
        **F32)
    assert not np.isfinite(np.asarray(_maps(open_cfg, hp, x)[2])).all()


# ------------------------------------------------------------ the model

def test_forward_and_the_prediction_module_equal_reference_logits(
        cfg, params):
    toks = jnp.asarray(np.stack([_tokens(40, 1), _tokens(40, 2)]))
    logits, (guess,) = jax.jit(
        lambda p, t: hybrid.forward(p, t, cfg, mtp=True))(params, toks)
    assert logits.shape == (2, 40, 256) and guess.shape == (2, 39, 256)
    for i in range(2):
        want, (want_guess,) = ref.logits(params, np.asarray(toks[i]), PUB,
                                         HELD, mtp=True)
        np.testing.assert_allclose(logits[i], want, atol=ATOL)
        np.testing.assert_allclose(guess[i], want_guess, atol=ATOL)
    # the module is a model of its own: its guess is not the main logits
    assert np.abs(np.asarray(guess) - np.asarray(logits[:, :39])).max() > .1
    # without ``mtp`` the same logits and nothing else
    np.testing.assert_array_equal(
        jax.jit(lambda p, t: hybrid.forward(p, t, cfg))(params, toks),
        logits)


def test_the_tolerance_tells_the_maps_in_bfloat16(params):
    """The second control at this size: the reference with ONLY the
    maps' arithmetic (norm, product with Phi, exponentials, iterations)
    in bfloat16 lies far outside the tolerance every comparison here
    uses — a program that computed them so would fail these tests."""
    toks = _tokens(40, 1)
    moved = np.abs(_ref(params, toks, round_maps_to=jnp.bfloat16)
                   - _ref(params, toks)).max()
    assert moved > 10 * ATOL, moved
    # and float8 inputs to every product move them further still
    assert np.abs(_ref(params, toks, round_to=jnp.float8_e4m3fn)
                  - _ref(params, toks)).max() > moved


def test_every_expert_is_held_and_the_parts_are_the_whole(cfg, params):
    """One share here: the held range is all 8 experts, so the held
    experts' part IS the uncut layer — the reference told to hold two
    halves gives parts that differ from it, and the program's count of
    held assignments equals its count of all."""
    toks = _tokens(24, 5)
    whole = _ref(params, toks)
    assert np.abs(_ref_held(params, toks, (0, 4)) - whole).max() > 1e-3
    x = hybrid.embed(cfg, params, jnp.asarray(toks)[None])
    fp = params["layers"][1]["ffn"]
    _, _, (counts, total) = hybrid.block(
        cfg, hybrid.EXPERTS, fp, x, None, jnp.asarray([24], jnp.int32))
    assert int(counts.sum()) == int(total) == 24 * 4


def _ref_held(params, toks, held):
    lo, hi = held
    cut = {**params, "layers": [
        {**lp, "ffn": {**lp["ffn"], "w_in": lp["ffn"]["w_in"][lo:hi],
                       "w_out": lp["ffn"]["w_out"][lo:hi]}}
        if "router" in lp["ffn"] else lp for lp in params["layers"]]}
    return np.asarray(ref.logits(cut, np.asarray(toks), PUB, held))


# --------------------------------------------------- the serving programs

def test_programs_chunks_then_decode_equal_reference_logits(cfg, params):
    """Prefill in chunks, then decode, through the latent pool: the
    streams ``[.., 4, 64]`` ride between the layers of both programs,
    and every position's logits are the reference's full forward."""
    bs, C, n_rows = 8, 8, 3
    pool = BlockPool(cfg, n_blocks=12, block_size=bs, max_seq=96)
    assert pool.v is None and pool.state is None       # ONE latent pool
    T = pool.blocks_per_seq
    step = recurrent.make_recurrent_decode_step(cfg, block_size=bs,
                                                n_table=T)
    chunk = recurrent.make_recurrent_chunk_fn(cfg, chunk=C, block_size=bs,
                                              n_table=T)
    seq = _tokens(30, 8)
    want = _ref(params, seq)
    n_prompt, row = 21, 1
    table = np.zeros(T, np.int32)
    table[:4] = [3, 7, 2, 9]
    pools, state = pool.pools, ()
    feed = jnp.zeros(n_rows, jnp.int32)
    for pos in range(0, n_prompt, C):
        n_q = min(C, n_prompt - pos)
        toks = np.zeros(C, np.int32)
        toks[:n_q] = seq[pos:pos + n_q]
        logits, load, pools, state, feed = chunk(
            params, pools, state, feed,
            pack_chunk(table, toks, pos, row, n_q))
        assert state == () and len(pools) == 1
        np.testing.assert_allclose(np.asarray(logits)[:n_q],
                                   want[pos:pos + n_q], atol=ATOL)
        # two experts layers x top-4, all held
        assert load.tolist()[:2] == [n_q * 8] * 2
    tables = np.zeros((n_rows, T), np.int32)
    tables[row] = table
    active = np.zeros(n_rows, bool)
    active[row] = True
    for pos in range(n_prompt, 30):
        tokens = np.zeros(n_rows, np.int32)
        positions = np.zeros(n_rows, np.int32)
        tokens[row], positions[row] = seq[pos], pos
        logits, load, pools, state, feed = step(
            params, pools, state, feed,
            pack_step(tables, tokens, positions, active))
        np.testing.assert_allclose(np.asarray(logits)[row], want[pos],
                                   atol=ATOL)
        assert load.tolist()[:2] == [8, 8]


# ------------------------------------------------------------- the engine

def _margins(params, prompt, emitted):
    seq = np.asarray(list(prompt) + list(emitted))
    step = _ref(params, seq)[len(prompt) - 1:len(seq) - 1]
    return step.max(-1) - step[np.arange(len(emitted)), emitted]


def _engine(cfg, params, **kw):
    ec = dict(max_slots=3, max_seq=96, n_blocks=30, kv_block_size=8,
              prefill_chunk=8)
    return InferenceEngine(params, cfg, EngineConfig(**{**ec, **kw}))


def test_engine_cold_then_adopted_prefix_equal_the_reference(cfg, params):
    """The same head asked twice: the second ask adopts the first's
    blocks from the radix index (the streams are activations: nothing of
    them is cached, and nothing of them is missed) and prefills its tail
    alone; both answers are the reference's argmax by its LOGITS, and
    the second equals what a cold engine gives it."""
    doc = _tokens(40, 11).tolist()
    q1, q2 = _tokens(9, 12).tolist(), _tokens(13, 13).tolist()
    eng = _engine(cfg, params)
    try:
        assert isinstance(eng.trie, RadixIndex)
        assert eng._step_chunk is None
        a = eng.submit(doc + q1, max_new=8).result(timeout=300)
        before = eng.stats()
        b = eng.submit(doc + q2, max_new=8).result(timeout=300)
        st = eng.stats()
    finally:
        eng.shutdown()
    assert before["prefix_hit_tokens"] == 0
    assert st["prefix_hit_tokens"] == 40
    assert st["prefix_blocks_adopted"] == 5
    assert st["prefill_tokens"] == 49 + 13
    # the engine's count of touched experts is exported for this layout
    assert st["expert_assignments_held"] == st["expert_assignments_total"] \
        == (49 + 13 + 7 + 7) * 4 * 2
    assert 0 < st["expert_touched_held_decode"] <= 14 * 8 * 2
    assert _margins(params, doc + q1, a).max() <= ATOL
    assert _margins(params, doc + q2, b).max() <= ATOL
    cold = _engine(cfg, params, prefix_cache=False)
    try:
        assert cold.submit(doc + q2, max_new=8).result(timeout=300) == b
    finally:
        cold.shutdown()


def test_engine_preemption_resumes_a_row_of_streams(cfg, params):
    """A pool too small for three rows' growth: a row is preempted, its
    clean chain goes to the radix index, it is re-admitted and its stream
    continues; every answer is still the reference's argmax."""
    eng = _engine(cfg, params, n_blocks=12)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, n).tolist() for n in (20, 22, 18)]
    try:
        reqs = [eng.submit(p, max_new=24) for p in prompts]
        outs = [r.result(timeout=600) for r in reqs]
        st = eng.stats()
    finally:
        eng.shutdown()
    assert st["preemptions"] >= 1
    for p, o in zip(prompts, outs):
        assert len(o) == 24
        assert _margins(params, p, o).max() <= ATOL
