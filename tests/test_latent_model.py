"""The hybrid layer function in its ``deepseek_v2`` layout (latent
attention over ONE latent pool at YaRN rotary positions, a leading dense
layer, shared + group-limited softmax-routed experts) at a tiny size on
the CPU, held to the plain reference (chipbench/reference/deepseek_v2.py,
float32, non-absorbed, no cache): the full forward, both attention forms
through the pool, the router's third form, the shares of a cut layer, the
one-token kernel in interpret mode against an oracle, and the engine —
cold, through an adopted prefix and across a preemption."""

import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import deepseek_v2 as ref
from ray_tpu.inference import EngineConfig, InferenceEngine
from ray_tpu.inference import recurrent
from ray_tpu.inference.cache import BlockPool, PoolLayout, RadixIndex
from ray_tpu.inference.decode import (SpeculationUnsupported, pack_chunk,
                                      pack_step)
from ray_tpu.models import hybrid
from ray_tpu.ops import routed_experts as rx

attention_mod = importlib.import_module("ray_tpu.ops.attention")

PUB = dict(
    model_type="deepseek_v2", vocab_size=256, hidden_size=64,
    num_hidden_layers=3, num_attention_heads=4, q_lora_rank=24,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, n_routed_experts=16,
    n_shared_experts=2, num_experts_per_tok=3, first_k_dense_replace=1,
    moe_layer_freq=1, n_group=4, topk_group=2,
    topk_method="group_limited_greedy", norm_topk_prob=False,
    scoring_func="softmax", routed_scaling_factor=16.0, rms_norm_eps=1e-6,
    rope_theta=10000,
    rope_scaling=dict(type="yarn", factor=40,
                      original_max_position_embeddings=16, beta_fast=32,
                      beta_slow=1, mscale=0.707, mscale_all_dim=0.707),
    max_position_embeddings=640, tie_word_embeddings=False)
F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32, max_seq=128)
ATOL = 1e-4


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


def _ref(params, toks, held=(0, 16), pub=PUB, **kw):
    return np.asarray(ref.logits(params, np.asarray(toks), pub, held, **kw))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n)


# ----------------------------------------------------------- the config

def test_config_from_published_keys(cfg):
    assert cfg.layer_types == (hybrid.LATENT,) * 3
    assert [k for _, k in cfg.sublayers] == [
        hybrid.LATENT, hybrid.DENSE, hybrid.LATENT, hybrid.EXPERTS,
        hybrid.LATENT, hybrid.EXPERTS]
    # ONE cached head of latent + rope lanes; the values are its first
    # kv_rank lanes; no recurrent layer, so no state
    assert cfg.kv_geometry == (3, 1, 40) and cfg.value_lanes == 32
    assert cfg.state_geometry is None
    assert cfg.shared_width == 64 and cfg.route_groups == (4, 2)
    assert not cfg.norm_topk and cfg.routed_scale == 16.0
    m = 0.1 * 0.707 * np.log(40) + 1
    assert cfg.attention_multiplier == pytest.approx(24 ** -0.5 * m * m)


def test_published_widths_give_the_stated_scale_and_pool():
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "deepseek-v2-7L-e20.json")) as f:
        config = json.load(f)
    from chipbench.traffic.open_loop_http_deepseek_v2 import model_config
    c, published, held = model_config(config)
    assert held == (0, 20) and c.n_experts == 160 and c.n_held == 20
    assert c.attention_multiplier == pytest.approx(0.11472, abs=2e-5)
    assert c.kv_geometry == (7, 1, 576) and c.value_lanes == 512
    lay = PoolLayout(7, 5, 16, 1, 576, 1, 512)
    assert lay.width == 640 and lay.shape == (35, 16, 640)
    shapes = jax.eval_shape(lambda: hybrid.init_params(
        c, jax.random.PRNGKey(0)))
    assert hybrid.num_params(shapes) == 4_483_671_040


@pytest.mark.parametrize("change, named", [
    ({"rope_scaling": {**PUB["rope_scaling"], "type": "linear"}},
     "rope_scaling.type"),
    ({"topk_method": "noaux_tc"}, "topk_method"),
    ({"scoring_func": "sigmoid"}, "scoring_func"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
    ({"q_lora_rank": None}, "q_lora_rank"),
])
def test_what_has_no_form_is_refused_by_name(change, named):
    with pytest.raises(ValueError, match=named):
        hybrid.HybridConfig.from_published({**PUB, **change}, **F32)


def test_the_other_layouts_keep_their_state(cfg):
    tiny = hybrid.HybridConfig.tiny()
    assert tiny.state_geometry is not None and tiny.value_lanes is None
    assert tiny.kv_geometry == (1, 2, 16)


# ------------------------------------------------------------- rotary

@pytest.mark.parametrize("pos", [0, 4095, 4096, 9471])
def test_yarn_tables_against_the_closed_form(pos):
    """At the published keys: low, high = 10, 23; pairs below 10 keep
    their frequency, pairs above 23 are divided by 40, a ramp between;
    the tables' multiplier is 1."""
    yarn = hybrid.Yarn(theta=10000, factor=40, original_max=4096,
                       beta_fast=32, beta_slow=1, mscale=0.707,
                       mscale_all_dim=0.707)
    c = hybrid.HybridConfig.tiny(rope_dim=64, yarn=yarn)
    cos, sin = hybrid.rotary_tables(c, jnp.asarray([pos]))
    i = np.arange(32)
    f = 10000.0 ** (-2 * i / 64)
    low = np.floor(64 * np.log(4096 / (2 * np.pi * 32))
                   / (2 * np.log(10000)))
    high = np.ceil(64 * np.log(4096 / (2 * np.pi * 1))
                   / (2 * np.log(10000)))
    assert (low, high) == (10, 23)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    inv = f / 40 * ramp + f * (1 - ramp)
    assert yarn.table_mscale == 1.0
    np.testing.assert_allclose(np.asarray(cos)[0], np.cos(pos * inv),
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(sin)[0], np.sin(pos * inv),
                               atol=2e-3)
    np.testing.assert_allclose(yarn.inv_freq(64), inv, rtol=1e-12)
    np.testing.assert_allclose(
        np.asarray(ref.yarn_inv_freq({
            "rope_theta": 10000, "qk_rope_head_dim": 64, "rope_scaling": {
                "factor": 40, "original_max_position_embeddings": 4096,
                "beta_fast": 32, "beta_slow": 1}})), inv, rtol=1e-5)


# ----------------------------------------------------------- the router

def _old_route(h, w_router, top_k, bias=None, scale=1.0):
    """``route`` as it stood before the third form, verbatim."""
    logits = jnp.dot(h, w_router.astype(h.dtype),
                     preferred_element_type=jnp.float32)
    if bias is None:
        top, experts = jax.lax.top_k(logits, top_k)
        return experts.astype(jnp.int32), jax.nn.softmax(top, axis=-1)
    scores = jax.nn.sigmoid(logits)
    _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    gates = chosen / chosen.sum(-1, keepdims=True) * scale
    return experts.astype(jnp.int32), gates


@pytest.mark.parametrize("form", ["softmax_of_chosen", "sigmoid_bias"])
def test_the_two_older_router_forms_unchanged_bit_for_bit(form):
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.standard_normal((40, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 16)) * 0.2, jnp.float32)
    kw = {} if form == "softmax_of_chosen" else {
        "bias": jnp.asarray(rng.standard_normal(16) * 0.1), "scale": 2.5}
    want = _old_route(h, w, 3, **kw)
    got = rx.route(h, w, 3, **kw)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_group_limited_router_against_the_reference():
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.standard_normal((200, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 16)) * 0.3, jnp.float32)
    experts, gates = rx.route(h, w, 3, scale=16.0, groups=(4, 2),
                              normalise=False)
    scores, idx = ref._choice(PUB, {"router": w}, h, None)
    np.testing.assert_array_equal(np.asarray(experts), np.asarray(idx))
    # chosen experts lie in <= topk_group groups
    groups = np.asarray(experts) // 4
    assert max(len(set(g)) for g in groups) <= 2
    # the group limit binds: the unlimited top-3 differ somewhere
    assert (np.asarray(jax.lax.top_k(scores, 3)[1])
            != np.asarray(experts)).any()
    # gates: the chosen softmax scores x 16, NOT normalised
    want = np.take_along_axis(np.asarray(scores), np.asarray(idx), -1) * 16
    np.testing.assert_allclose(np.asarray(gates), want, rtol=1e-6)
    assert not np.allclose(np.asarray(gates).sum(-1), 16.0)
    # greedy (no groups) with normalisation: the fourth combination
    e2, g2 = rx.route(h, w, 3, scale=2.0, groups=(1, 1), normalise=True)
    np.testing.assert_array_equal(
        np.asarray(e2), np.asarray(jax.lax.top_k(scores, 3)[1]))
    np.testing.assert_allclose(np.asarray(g2).sum(-1), 2.0, rtol=1e-6)


# --------------------------------------------------- forward = reference

def test_forward_equals_reference_logits(cfg, params):
    toks = np.stack([_tokens(40, 1), _tokens(40, 2)])
    got = np.asarray(hybrid.forward(params, jnp.asarray(toks), cfg))
    for i in range(2):
        np.testing.assert_allclose(got[i], _ref(params, toks[i]), atol=ATOL)


def test_reference_controls_move_the_logits(cfg, params):
    toks = _tokens(40, 5)
    full = _ref(params, toks)
    low = _ref(params, toks, round_to=jnp.float8_e4m3fn)
    cache = _ref(params, toks, round_cache_to=jnp.float8_e4m3fn)
    assert np.abs(full - low).max() > 100 * ATOL
    assert 10 * ATOL < np.abs(full - cache).max() < np.abs(full - low).max()


def test_the_shares_add_up(cfg, params):
    """Four held ranges of 4 experts (a group each, as a deployment
    holds them), the shared experts counted once = the uncut layer."""
    lp = params["layers"][1]
    x = jnp.asarray(np.random.default_rng(6).standard_normal((30, 64)),
                    jnp.float32)
    c = ref._static(PUB)
    whole = np.asarray(ref._layer(lp, x, c, (0, 16), None))
    attn_only = None
    parts = np.zeros_like(whole)
    for g in range(4):
        held = (4 * g, 4 * g + 4)
        cut = {**lp, "ffn": {**lp["ffn"],
                             "w_in": lp["ffn"]["w_in"][held[0]:held[1]],
                             "w_out": lp["ffn"]["w_out"][held[0]:held[1]]}}
        part = np.asarray(ref._layer(cut, x, c, held, None))
        # the program's share of the same range says the same
        hc = hybrid.HybridConfig.from_published(PUB, experts_held=held,
                                                **F32)
        h = hybrid._rms_norm(x, lp["ffn"]["norm"], hc.rms_eps)[None]
        mine, counts, total = hybrid._experts(
            hc, cut["ffn"], h, jnp.ones((1, 30), bool))
        # zero the routed part to find what every share repeats
        none = {**cut, "ffn": {**cut["ffn"],
                               "w_out": jnp.zeros_like(cut["ffn"]["w_out"])}}
        base = np.asarray(ref._layer(none, x, c, held, None))
        if attn_only is None:
            attn_only = base
        np.testing.assert_allclose(base, attn_only, atol=1e-6)
        parts += part - base
        assert int(total) == 30 * 3 and 0 < int(counts.sum()) < 90
    np.testing.assert_allclose(attn_only + parts, whole, atol=ATOL)


# -------------------------------------- the two forms, through the pool

def test_absorbed_form_equals_decompressed_form(cfg):
    """One query over the same cached latents: the window form
    (decompress a block of keys, attend) and the one-token form (W_uk
    absorbed into the query, W_uv into the output) are the same sums."""
    rng = np.random.default_rng(7)
    h, dn, dr, dv, rank, n = 4, 16, 8, 16, 32, 37
    lat = jnp.asarray(rng.standard_normal((48, rank + dr)), jnp.float32)
    q_nope = jnp.asarray(rng.standard_normal((1, h, dn)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((1, h, dr)), jnp.float32)
    w_uk = jnp.asarray(rng.standard_normal((h, rank, dn)), jnp.float32)
    w_uv = jnp.asarray(rng.standard_normal((h, rank, dv)), jnp.float32)
    window = attention_mod.latent_window_attention(
        q_nope, q_rope, lambda j, k: jax.lax.dynamic_slice_in_dim(
            lat, j * k, k), w_uk, w_uv, jnp.asarray([n - 1]), scale=0.3,
        key_block=16)
    lay = PoolLayout(1, 7, 8, 1, rank + dr, 1, rank)
    pool = lay.pack(jnp.zeros((7, 8, 1, rank + dr)).at[1:].set(
        lat.reshape(6, 8, 1, rank + dr)))
    q = jnp.concatenate([jnp.einsum("bhd,hcd->bhc", q_nope, w_uk), q_rope],
                        -1)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, lay.width - rank - dr)))
    o_lat = attention_mod.latent_decode_attention(
        q, pool, 0, jnp.arange(1, 7)[None], jnp.asarray([n]),
        value_lanes=rank, scale=0.3)
    absorbed = jnp.einsum("bhc,hcd->bhd", o_lat, w_uv).reshape(1, h * dv)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(window),
                               atol=2e-5, rtol=2e-5)


def test_one_pool_is_allocated(cfg):
    pool = BlockPool(cfg, n_blocks=12, block_size=8, max_seq=96,
                     state_rows=3)
    assert pool.v is None and pool.state is None
    assert len(pool.pools) == 1 and pool.k.shape == (3 * 13, 8, 128)
    assert pool.layout.value_lanes == 32
    assert pool.bytes_total() == pool.k.nbytes
    with pytest.raises(NotImplementedError, match="interchange"):
        pool.read_blocks([1])
    pool.copy_block(1, 2)
    pool.reset()
    assert pool.v is None and len(pool.pools) == 1
    two = BlockPool(hybrid.HybridConfig.tiny(), n_blocks=12, block_size=8,
                    max_seq=96, state_rows=3)
    assert len(two.pools) == 2 and two.state is not None


def test_programs_chunks_then_decode_equal_reference_logits(cfg, params):
    bs, C, n_rows = 8, 8, 3
    pool = BlockPool(cfg, n_blocks=12, block_size=bs, max_seq=96)
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
    feed = jnp.zeros(n_rows, jnp.int32)     # the rows' next tokens
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
        # two experts layers x top-3, the dense layer routes nothing
        assert load.tolist()[:2] == [n_q * 6] * 2
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
        logits, load, pools, state, feed = step(
            params, pools, state, feed,
            pack_step(tables, tokens, positions, active))
        np.testing.assert_allclose(np.asarray(logits)[row], want[pos],
                                   atol=ATOL)
        assert load.tolist()[:2] == [6, 6]


# ------------------------------------------------ the one-token kernel

BS, TABLE, LAYERS, LAYER = 8, 6, 3, 1      # 48 keys a row at most
HEADS, RANK, ROPE = 8, 128, 64             # 192 lanes stored at 256

KERNEL_CASES = {
    # kv_len a row (0 = the row sits out), blocks a wave
    "mixed-lengths": ([17, 0, 48, 30], 2),
    "one-key": ([1, 0, 0, 1], 2),
    "whole-table": ([48, 48, 48, 48], 4),
    "not-a-multiple-of-the-block": ([13, 29, 3, 41], 2),
    "idle-rows-between-live-ones": ([7, 0, 48, 0, 0, 13], 1),
    "no-row-live": ([0, 0, 0, 0], 2),
    "one-wave-holds-the-table": ([13, 0, 48, 30], TABLE),
    "waves-of-three-blocks": ([41, 8, 0, 48], 3),
    "waves-of-five-blocks": ([41, 8, 0, 48], 5),
}


def _kernel_setup(lens, dtype, seed=0):
    rng = np.random.default_rng(seed)
    b = len(lens)
    lay = PoolLayout(LAYERS, b * TABLE + 1, BS, 1, RANK + ROPE, 1, RANK)
    pool = lay.pack(jnp.asarray(rng.standard_normal(
        (*lay.shape[:2], 1, RANK + ROPE)), dtype))
    ids = rng.permutation(np.arange(1, lay.n_rows))
    tables = np.zeros((b, TABLE), np.int32)
    for r, n in enumerate(lens):
        held = -(-n // BS)
        tables[r, :held] = ids[r * TABLE:r * TABLE + held]
    q = jnp.asarray(rng.standard_normal((b, HEADS, RANK + ROPE)), dtype)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, lay.width - RANK - ROPE)))
    return lay, pool, jnp.asarray(tables), q


def _kernel(lay, q, pool, tables, lens, wave, monkeypatch):
    monkeypatch.setattr(
        attention_mod, "LATENT_WAVE_BYTES",
        2 * wave * BS * lay.width * pool.dtype.itemsize)
    return attention_mod.latent_decode_attention(
        q, pool, lay.rows(LAYER, 0), tables, jnp.asarray(lens, jnp.int32),
        value_lanes=RANK, scale=0.07)


def _oracle(lay, q, pool, tables, lens):
    """Gather each row's table, softmax over its first ``len`` keys in
    float32, the values the keys' first RANK lanes."""
    out = np.zeros((len(lens), HEADS, RANK), np.float32)
    pool = np.asarray(pool, np.float32)
    q = np.asarray(q, np.float32)
    for r, n in enumerate(lens):
        if not n:
            continue
        keys = pool[lay.rows(LAYER, np.asarray(tables[r]))].reshape(
            -1, lay.width)[:n]
        s = q[r] @ keys.T * 0.07
        p = np.exp(s - s.max(-1, keepdims=True))
        out[r] = (p / p.sum(-1, keepdims=True)) @ keys[:, :RANK]
    return out


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", KERNEL_CASES)
def test_latent_kernel_attends_what_the_oracle_attends(case, dtype,
                                                       monkeypatch):
    lens, wave = KERNEL_CASES[case]
    lay, pool, tables, q = _kernel_setup(lens, dtype)
    out = _kernel(lay, q, pool, tables, lens, wave, monkeypatch)
    assert out.shape == (len(lens), HEADS, RANK) and out.dtype == dtype
    want = _oracle(lay, q, pool, tables, lens)
    live = np.asarray(lens) > 0
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    got = np.asarray(out, np.float32)
    np.testing.assert_allclose(got[live], want[live], atol=tol, rtol=tol)
    assert not got[~live].any()


def test_latent_kernel_reads_what_a_row_holds_and_nothing_else(monkeypatch):
    """NaN in every block that no live row's table names within its
    length (the other layers, the scratch block, an idle row's blocks)
    and in the keys past ``kv_len`` of a row's last block: not one bit
    of the output changes."""
    lens = [0, 41, 8, 0, 48]
    lay, pool, tables, q = _kernel_setup(lens, jnp.float32, seed=1)
    clean = _kernel(lay, q, pool, tables, lens, 2, monkeypatch)
    assert np.isfinite(np.asarray(clean)).all()
    keep = np.zeros(lay.shape[:2], bool)
    for r, n in enumerate(lens):
        for j in range(-(-n // BS)):
            keep[lay.rows(LAYER, int(tables[r, j])),
                 :min(BS, n - j * BS)] = True
    assert keep.sum() == sum(lens)
    poisoned = jnp.where(keep[:, :, None], pool, jnp.nan)
    out = _kernel(lay, q, poisoned, tables, lens, 2, monkeypatch)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))


def test_window_form_ignores_what_lies_past_its_queries():
    """NaN latents at every key position past the window's last query:
    the window form's output does not change."""
    rng = np.random.default_rng(9)
    h, dn, dr, dv, rank = 4, 16, 8, 16, 32
    lat = rng.standard_normal((64, rank + dr)).astype(np.float32)
    args = [jnp.asarray(rng.standard_normal(s), jnp.float32) for s in (
        (5, h, dn), (5, h, dr), (h, rank, dn), (h, rank, dv))]
    pos = jnp.asarray([20, 21, 22, 23, 24])

    def run(lat):
        lat = jnp.asarray(lat)
        return np.asarray(attention_mod.latent_window_attention(
            args[0], args[1], lambda j, k: jax.lax.dynamic_slice_in_dim(
                lat, j * k, k), args[2], args[3], pos, scale=0.2,
            key_block=16))
    clean = run(lat)
    lat[25:] = np.nan
    np.testing.assert_array_equal(run(lat), clean)
    assert np.isfinite(clean).all()


def _window_oracle(q_nope, q_rope, lat, w_uk, w_uv, pos, scale):
    """Plain masked softmax over every key, decompressed at once."""
    rank = w_uk.shape[1]
    k_pos = np.arange(lat.shape[0])
    c = np.where((k_pos <= pos.max())[:, None], lat, 0.0)
    k = np.concatenate([np.einsum("kc,hcd->khd", c[:, :rank], w_uk),
                        np.broadcast_to(c[:, None, rank:],
                                        (len(c), w_uk.shape[0],
                                         c.shape[1] - rank))], -1)
    v = np.einsum("kc,hcd->khd", c[:, :rank], w_uv)
    s = np.einsum("qhd,khd->hqk", np.concatenate([q_nope, q_rope], -1), k)
    s = np.where(k_pos[None, None, :] <= pos[None, :, None], s * scale,
                 -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    o = np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True), v)
    return o.reshape(len(pos), -1)


# (the window's first position, lanes, real queries (None: all), keys a
# block, the kernel's tile (None: the code's own))
WINDOW_CASES = {
    "starts_at_0": (0, 48, None, 64, (32, 16)),
    "start_off_tile_and_block": (37, 48, None, 64, (32, 16)),
    "narrower_than_a_tile": (70, 5, None, 64, (32, 16)),
    "no_multiple_of_a_tile": (21, 40, None, 48, (32, 16)),
    "three_key_blocks_and_more": (100, 100, None, 64, (32, 16)),
    "padding_lanes": (64, 48, 20, 64, (32, 16)),
    "padding_lanes_mid_tile": (50, 64, 37, 32, (16, 16)),
    "one_query": (130, 1, None, 64, (32, 16)),
    "one_real_query_of_a_window": (130, 32, 1, 64, (32, 16)),
    "the_codes_own_tile": (700, 600, None, 1024, None),
    "the_codes_own_tile_padded": (1024, 512, 100, 1024, None),
}


@pytest.mark.parametrize("case", WINDOW_CASES)
def test_window_form_equals_plain_masked_softmax(case, monkeypatch):
    """``latent_window_attention`` (interpret mode) against a plain
    masked softmax, over windows that reach each kind of tile and each
    edge.  What lies past the last REAL query is NaN; a padding lane's
    output is finite, and nothing more is asked of it."""
    first, w, n_valid, key_block, tile = WINDOW_CASES[case]
    if tile is not None:
        monkeypatch.setattr(attention_mod, "WINDOW_TILE", tile)
    rng = np.random.default_rng(len(case))
    h, dn, dr, dv, rank = 2, 16, 8, 16, 32
    n_real = w if n_valid is None else n_valid
    pos = first + np.arange(w)
    keys = -(-(first + w) // key_block) * key_block
    lat = rng.standard_normal((keys, rank + dr)).astype(np.float32)
    lat[first + n_real:] = np.nan
    q_nope, q_rope, w_uk, w_uv = (
        rng.standard_normal(s).astype(np.float32)
        for s in ((w, h, dn), (w, h, dr), (h, rank, dn), (h, rank, dv)))
    tiles = attention_mod.window_tiles(first, n_real, w, key_block)
    got = np.asarray(attention_mod.latent_window_attention(
        jnp.asarray(q_nope), jnp.asarray(q_rope),
        lambda j, k: jax.lax.dynamic_slice_in_dim(jnp.asarray(lat),
                                                  j * k, k),
        jnp.asarray(w_uk), jnp.asarray(w_uv), jnp.asarray(pos), scale=0.2,
        key_block=key_block,
        n_valid=None if n_valid is None else jnp.asarray(n_valid)))
    assert got.shape == (w, h * dv) and np.isfinite(got).all()
    want = _window_oracle(q_nope[:n_real], q_rope[:n_real], lat, w_uk, w_uv,
                          pos[:n_real], 0.2)
    np.testing.assert_allclose(got[:n_real], want, atol=2e-5, rtol=2e-5)
    # the case reaches what its name says
    if "padding" in case or "one_real" in case:
        assert tiles["unseen"] > 0
    if case in ("start_off_tile_and_block", "three_key_blocks_and_more",
                "the_codes_own_tile", "the_codes_own_tile_padded"):
        assert min(tiles["plain"], tiles["diagonal"], tiles["unseen"]) > 0


@pytest.mark.parametrize("seed", range(6))
def test_tile_kinds_cover_the_walk_and_every_credited_pair(seed,
                                                           monkeypatch):
    """The classification the kernel makes, on the host
    (``window_tiles``): the three kinds are all of the walk's tiles, a
    credited (query, key) pair lies in a tile that is not skipped, a
    plain tile holds no masked pair, and the walked pairs are the sizes
    of the tiles not skipped."""
    rng = np.random.default_rng(seed)
    tile = [(32, 16), (16, 32), (64, 64), (8, 8), (48, 16), (32, 128)][seed]
    monkeypatch.setattr(attention_mod, "WINDOW_TILE", tile)
    for _ in range(20):
        key_block = int(rng.choice([32, 64, 96]))
        w = int(rng.integers(1, 100))
        pos, n_q = int(rng.integers(0, 300)), int(rng.integers(1, w + 1))
        got = attention_mod.window_tiles(pos, n_q, w, key_block)
        q_spans = attention_mod._spans(w, tile[1])
        k_spans = [(j * key_block + a, n)
                   for j in range((pos + n_q - 1) // key_block + 1)
                   for a, n in attention_mod._spans(key_block, tile[0])]
        assert got["unseen"] + got["plain"] + got["diagonal"] \
            == len(q_spans) * len(k_spans)
        walked = credited = 0
        for q0, tq in q_spans:
            lanes = pos + q0 + np.arange(tq)
            real = lanes[:max(min(q0 + tq, n_q) - q0, 0)]
            for k0, tk in k_spans:
                keys = k0 + np.arange(tk)
                seen = keys[:, None] <= lanes[None, :]
                pairs = int(seen[:, :len(real)].sum())
                unseen, plain = attention_mod._tile_kind(
                    k0, k0 + tk - 1, lanes[0],
                    real[-1] if len(real) else -1)
                assert not (unseen and plain)
                assert (pairs == 0) == bool(unseen)
                assert (seen.all() and pairs > 0) == bool(plain)
                credited += pairs
                walked += 0 if unseen else tk * tq
        assert credited == n_q * pos + n_q * (n_q + 1) // 2
        assert got["pairs"] == walked >= credited


# ------------------------------------------------------------ the engine

def _margins(params, prompt, emitted):
    seq = np.asarray(list(prompt) + list(emitted))
    step = _ref(params, seq)[len(prompt) - 1:len(seq) - 1]
    return step.max(-1) - step[np.arange(len(emitted)), emitted]


def _engine(cfg, params, **kw):
    ec = dict(max_slots=3, max_seq=96, n_blocks=30, kv_block_size=8,
              prefill_chunk=8)
    return InferenceEngine(params, cfg, EngineConfig(**{**ec, **kw}))


def test_engine_has_the_radix_index_without_recurrent_layers(cfg, params):
    eng = _engine(cfg, params)
    try:
        assert isinstance(eng.trie, RadixIndex)
        assert eng.pool.state is None and eng.pool.v is None
        assert eng._step_chunk is None and eng._prefill is None
    finally:
        eng.shutdown()
    off = _engine(cfg, params, prefix_cache=False)
    assert off.trie is None
    off.shutdown()
    # with recurrent layers a prefix is more than its blocks: no index
    tiny = hybrid.HybridConfig.tiny()
    rec = InferenceEngine(hybrid.init_params(tiny, jax.random.PRNGKey(0)),
                          tiny, EngineConfig(max_slots=2, max_seq=64,
                                             kv_block_size=8,
                                             prefill_chunk=8))
    try:
        assert rec.trie is None and rec.pool.state is not None
    finally:
        rec.shutdown()
    with pytest.raises(SpeculationUnsupported):
        _engine(cfg, params, speculate="ngram")


def test_engine_cold_then_adopted_prefix_equal_the_reference(cfg, params):
    """The same document asked twice: the second ask adopts the first's
    blocks from the radix index and prefills its question alone; both
    answers are the reference's argmax, token for token, and the second
    ask's first tokens equal what a cold engine gives it."""
    doc = _tokens(40, 11).tolist()
    q1, q2 = _tokens(9, 12).tolist(), _tokens(13, 13).tolist()
    eng = _engine(cfg, params)
    try:
        a = eng.submit(doc + q1, max_new=8).result(timeout=300)
        before = eng.stats()
        b = eng.submit(doc + q2, max_new=8).result(timeout=300)
        st = eng.stats()
    finally:
        eng.shutdown()
    assert before["prefix_hit_tokens"] == 0
    # 40 document tokens = 5 whole blocks of 8, adopted
    assert st["prefix_hit_tokens"] == 40
    assert st["prefix_blocks_adopted"] == 5
    assert st["prefill_tokens"] == 49 + 13
    assert st["chunk_query_keys"] > st["chunk_keys"] > 0
    assert st["kv_blocks_attended"] < st["kv_blocks_tabled"]
    assert _margins(params, doc + q1, a).max() <= ATOL
    assert _margins(params, doc + q2, b).max() <= ATOL
    cold = _engine(cfg, params, prefix_cache=False)
    try:
        assert cold.submit(doc + q2, max_new=8).result(timeout=300) == b
    finally:
        cold.shutdown()


def test_engine_counts_the_tiles_its_window_kernel_walks(cfg, params):
    """``chunk_pairs_walked`` / ``chunk_tiles_*`` are the classifier's
    sums over the chunk passes the engine ran (a cold prompt of 21
    tokens in chunks of 8, then 5 more behind 16 adopted ones), a head
    and layer: what the kernel walks is never less than what is
    credited."""
    doc = _tokens(21, 21).tolist()
    eng = _engine(cfg, params)
    try:
        eng.submit(doc, max_new=2).result(timeout=300)
        eng.submit(doc[:16] + _tokens(5, 22).tolist(),
                   max_new=2).result(timeout=300)
        st = eng.stats()
    finally:
        eng.shutdown()
    assert st["chunk_passes"] == 4 and st["prefix_hit_tokens"] == 16
    tiles = [attention_mod.window_tiles(pos, n_q, 8, 1024)
             for pos, n_q in ((0, 8), (8, 8), (16, 5), (16, 5))]
    for key, name in (("chunk_pairs_walked", "pairs"),
                      ("chunk_tiles_plain", "plain"),
                      ("chunk_tiles_diagonal", "diagonal")):
        assert st[key] == sum(t[name] for t in tiles), key
    # one block of 1,024 keys in two tiles of 512: the first crossed by
    # the causal edge, the second past every query
    assert tiles[0] == dict(unseen=1, plain=0, diagonal=1, pairs=512 * 8)
    assert st["chunk_pairs_walked"] >= st["chunk_query_keys"] > 0


def test_engine_rows_admitted_at_different_times(cfg, params):
    eng = _engine(cfg, params)
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
        assert _margins(params, p, o).max() <= ATOL
    assert st["tokens_greedy_on_device"] == st["generated_tokens"]
    assert st["expert_assignments_total"] == sum(
        n + m - 1 for n, m in plan) * 3 * 2


def test_engine_preemption_resumes_a_latent_row(cfg, params):
    """A pool too small for three rows' growth: a row is preempted, its
    clean chain goes to the radix index, it is re-admitted (adopting
    what survived) and its stream continues; every answer is still the
    reference's argmax."""
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
