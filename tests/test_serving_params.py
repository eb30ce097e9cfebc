"""The tree a serving engine hands its programs (gpt.serving_params):
float32 masters cast ONCE, when the engine takes them, never once a
pass.  Same bits out: every product already took the bfloat16 rounding
of its weight, so every program returns BITWISE the logits it returned
from the float32 tree, and an engine emits the oracle's tokens.

Runs on CPU at GPTConfig.tiny with bfloat16 activations (the cast is
real); what the chip's compiler makes of the served tree at GPT-2 XL
is pinned by tests/test_chip_compile.py."""

import functools
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.inference import EngineConfig, InferenceEngine
from ray_tpu.inference.cache import BlockPool
from ray_tpu.inference.decode import (make_chunk_prefill_fn,
                                      make_paged_decode_step,
                                      make_paged_draft_step,
                                      make_prefill_fn,
                                      make_spec_verify_step, pack_chunk,
                                      pack_step)
from ray_tpu.inference.engine import metrics_snapshot
from ray_tpu.models import gpt, hybrid
from ray_tpu.parallel.mesh import create_mesh

CONFIGS = {
    "tied": dict(),
    "untied": dict(tie_embeddings=False),
    # capacity never binds, so a window routes like the full sequence
    "moe": dict(n_experts=4, expert_top_k=2, capacity_factor=2.0),
}
PROGRAMS = ("chunk", "decode", "verify", "draft", "prefill")
ROWS, BS, T, CHUNK, WIDTH = 2, 8, 4, 16, 3


def _cfg(kind):
    return gpt.GPTConfig.tiny(max_seq=BS * T, **CONFIGS[kind])


def _params(kind):
    return gpt.init_params(_cfg(kind), jax.random.PRNGKey(7))


def _nbytes(tree):
    return sum(int(np.prod(p.shape)) * jnp.dtype(p.dtype).itemsize
               for p in jax.tree.leaves(tree))


def _run_programs(cfg, params):
    """Every serving program once, on one pool: two rows prefilled by a
    chunk each, then a decode step, a verify window, a draft burst on
    top, and the full-width prefill.  -> {program: what it returned}."""
    kw = dict(block_size=BS, n_table=T)
    pool = BlockPool(cfg, ROWS * T, BS)
    k, v = pool.k, pool.v
    tables = jnp.arange(1, ROWS * T + 1, dtype=jnp.int32).reshape(ROWS, T)
    toks = jax.random.randint(jax.random.PRNGKey(1), (ROWS, BS * T), 0,
                              cfg.vocab_size, jnp.int32)
    out = {}
    chunk = make_chunk_prefill_fn(cfg, chunk=CHUNK, **kw)
    rows = []
    feed = jnp.zeros(ROWS, jnp.int32)       # the rows' next tokens
    for r in range(ROWS):
        logits, first, k, v, feed = chunk(params, k, v, feed, pack_chunk(
            tables[r], toks[r, :CHUNK], 0, r, CHUNK))
        # the program's own greedy token: its last real position's, and
        # what the row feeds its first decode step
        assert first.dtype == jnp.int32 \
            and first.tolist() == [int(logits[CHUNK - 1].argmax())] \
            == [int(feed[r])]
        rows.append(logits)
    out["chunk"] = jnp.stack(rows)
    at = jnp.full((ROWS,), CHUNK, jnp.int32)
    live = jnp.ones((ROWS,), bool)
    out["decode"], greedy, k, v, feed = make_paged_decode_step(
        cfg, **kw)(
        params, k, v, feed, pack_step(tables, toks[:, CHUNK], at, live))
    assert greedy.dtype == jnp.int32 and greedy.tolist() \
        == out["decode"].argmax(-1).tolist() == feed.tolist()
    out["verify"], k, v = make_spec_verify_step(cfg, width=WIDTH, **kw)(
        params, k, v, tables, toks[:, CHUNK:CHUNK + WIDTH], at, live,
        jnp.full((ROWS,), WIDTH, jnp.int32))
    out["draft"], k, v = make_paged_draft_step(
        cfg, draft_layers=1, k=WIDTH, **kw)(
        params, k, v, tables, toks[:, CHUNK], at,
        jnp.full((ROWS,), WIDTH, jnp.int32))
    out["prefill"] = make_prefill_fn(cfg)(params, toks)[0]
    out["pools"] = (k, v)
    return jax.tree.map(np.asarray, out)


@functools.lru_cache(maxsize=None)
def _both_trees(kind):
    cfg, params = _cfg(kind), _params(kind)
    return (_run_programs(cfg, params),
            _run_programs(cfg, gpt.serving_params(params, cfg)))


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("kind", CONFIGS)
def test_served_tree_gives_bitwise_the_same_logits(kind, program):
    masters, served = _both_trees(kind)
    assert masters[program].dtype == served[program].dtype
    assert np.isfinite(masters[program].astype(np.float32)).all()
    assert np.array_equal(masters[program], served[program])
    if program == "draft":          # the last program: the pools it left
        for a, b in zip(masters["pools"], served["pools"]):
            assert np.array_equal(a, b)


@pytest.mark.parametrize("kind", CONFIGS)
def test_what_is_cast_what_is_kept_and_what_comes_back_as_it_was(kind):
    cfg, params = _cfg(kind), _params(kind)
    served = gpt.serving_params(params, cfg)
    assert (jax.tree.structure(served) == jax.tree.structure(
        gpt.param_logical_axes(cfg, served=True),
        is_leaf=lambda x: isinstance(x, tuple)))
    for name, leaf in served["layers"].items():
        if name in gpt.CAST_AT_USE:
            assert leaf.dtype == cfg.dtype, name
            assert leaf.shape == params["layers"][name].shape
        else:                       # LayerNorm leaves, the router
            assert leaf is params["layers"][name], name
    assert served["wpe"] is params["wpe"]
    assert served["ln_f_scale"] is params["ln_f_scale"]
    # the gather table keeps its precision; its rows fill whole lanes
    assert served["wte"].dtype == params["wte"].dtype
    assert served["wte"].shape == (cfg.vocab_size, 128)
    assert np.array_equal(served["wte"][:, :cfg.d_model], params["wte"])
    assert not np.asarray(served["wte"][:, cfg.d_model:]).any()
    head = params["wte"].T if cfg.tie_embeddings else params["lm_head"]
    assert served["lm_head"].dtype == cfg.dtype
    assert np.array_equal(served["lm_head"], head.astype(cfg.dtype))
    assert not [p for p in gpt.cast_at_use(served) if p.dtype != cfg.dtype]
    # a tree that arrives as the programs want it costs nothing
    again = gpt.serving_params(served, cfg)
    assert all(a is b for a, b in zip(jax.tree.leaves(again),
                                      jax.tree.leaves(served)))


def test_a_table_of_whole_lanes_is_kept_by_identity():
    cfg = gpt.GPTConfig.tiny(d_model=128)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    assert gpt.serving_params(params, cfg)["wte"] is params["wte"]


def test_training_tree_and_its_head_are_unchanged():
    """No ``lm_head`` in a tied training tree: ``_head`` contracts
    ``wte.T`` as before, and the axes tree matches ``init_params``."""
    cfg = _cfg("tied")
    params = _params("tied")
    assert "lm_head" not in params
    assert "lm_head" not in gpt.param_logical_axes(cfg)
    text = jax.jit(lambda p, x: gpt._head(p, x, cfg, None, None)).lower(
        params, jnp.zeros((1, 4, cfg.d_model), cfg.dtype)).as_text()
    assert "transpose" in text
    # and a table of the model's own width is gathered from as before
    rows = jax.make_jaxpr(lambda p, t: gpt._token_rows(p, t, cfg))(
        params, jnp.zeros((2, 4), jnp.int32))
    assert "slice" not in [e.primitive.name for e in rows.eqns]


def test_xl_masters_would_cast_six_gigabytes_a_pass():
    """The counter's two readings at the benchmark's GPT-2 XL, by
    shapes alone: 6.2 GB cast a pass from float32 masters (5.9 GB of
    layers and the tied table for the head), 0 served."""
    cfg = gpt.GPTConfig(d_model=1600, n_heads=25, n_layers=48, d_ff=6400)
    masters = jax.eval_shape(
        lambda: gpt.init_params(cfg, jax.random.PRNGKey(0)))
    served = jax.eval_shape(lambda: gpt.serving_params(
        gpt.init_params(cfg, jax.random.PRNGKey(0)), cfg))

    def cast(tree):
        return _nbytes([p for p in gpt.cast_at_use(tree)
                        if p.dtype != cfg.dtype])
    assert 6.2e9 < cast(masters) < 6.25e9
    assert cast(served) == 0
    assert served["wte"].shape == (cfg.vocab_size, 1664)
    assert served["lm_head"].shape == (cfg.d_model, cfg.vocab_size)
    assert 3.4e9 < _nbytes(served) < 3.5e9 < 6.2e9 < _nbytes(masters)


# ------------------------------------------------------------- the engine

def _ref_tokens(params, cfg, prompt, max_new):
    out = gpt.generate(params, cfg, jnp.asarray([prompt], jnp.int32),
                       max_new=max_new, temperature=0.0)
    return np.asarray(out)[0, len(prompt):].tolist()


@pytest.mark.parametrize("arrives", ["float32", "bfloat16"])
def test_engine_emits_the_oracles_tokens(arrives):
    """Built from float32 masters or from a tree already in bfloat16,
    the engine emits what ``gpt.generate`` emits from that tree, and
    reports what it holds."""
    cfg = _cfg("tied")
    params = _params("tied")
    if arrives == "bfloat16":
        params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=2, kv_block_size=BS, prefill_chunk=CHUNK))
    try:
        for prompt in ([3, 1, 4, 1, 5], list(range(40, 60))):
            assert (eng.generate(prompt, max_new=8, timeout=120)
                    == _ref_tokens(params, cfg, prompt, 8))
        st = eng.stats()
        assert st["weight_bytes"] == _nbytes(eng.params)
        assert st["weight_bytes"] == _nbytes(jax.eval_shape(
            lambda: gpt.serving_params(params, cfg)))
        assert st["weight_bytes_cast_per_pass"] == 0
        if arrives == "bfloat16":   # nothing to cast: the caller's arrays
            for name in gpt.CAST_AT_USE:
                assert eng.params["layers"][name] is params["layers"][name]
        by_name = {m[0]: m for m in metrics_snapshot()}
        key = (("engine", eng.name),)
        for name, want in (("weight_bytes", st["weight_bytes"]),
                           ("weight_bytes_cast_per_pass", 0)):
            _, kind, _, series = by_name["ray_tpu_inference_" + name]
            assert kind == "gauge" and series[key] == want
    finally:
        eng.shutdown()


def test_engine_keeps_no_master_it_replaced():
    """Whoever built the float32 masters may keep them; the engine does
    not: dropped by their maker, the replaced leaves are gone."""
    cfg = _cfg("tied")
    params = _params("tied")
    replaced = [weakref.ref(params["layers"][n]) for n in gpt.CAST_AT_USE]
    replaced.append(weakref.ref(params["wte"]))
    kept = weakref.ref(params["wpe"])
    eng = InferenceEngine(params, cfg, EngineConfig(max_slots=2))
    try:
        del params
        gc.collect()
        assert [r() for r in replaced] == [None] * len(replaced)
        assert kept() is eng.params["wpe"]
    finally:
        eng.shutdown()


def test_recurrent_family_serves_the_arrays_it_was_given():
    """No second copy of a tree that fills the chip: every leaf is the
    caller's array, and nothing is cast a pass."""
    cfg = hybrid.HybridConfig.tiny()
    params = hybrid.init_params(cfg, jax.random.PRNGKey(0))
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=2, max_seq=96, n_blocks=12, kv_block_size=8,
        prefill_chunk=8))
    try:
        mine, given = jax.tree.leaves(eng.params), jax.tree.leaves(params)
        assert len(mine) == len(given)
        assert all(a is b for a, b in zip(mine, given))
        st = eng.stats()
        assert st["weight_bytes"] == _nbytes(params)
        assert st["weight_bytes_cast_per_pass"] == 0
    finally:
        eng.shutdown()
    # float32 masters under bfloat16 activations WOULD be cast a pass
    # (the family is not published so): the counter says it
    masters = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    assert sum(p.nbytes for p in hybrid.cast_at_use(masters)
               if p.dtype != jnp.bfloat16) > 0.9 * _nbytes(masters)


def test_tp_engine_places_the_served_tree():
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 CPU devices")
    cfg = _cfg("tied")
    params = _params("tied")
    mesh = create_mesh({"tp": 2}, devices=jax.devices()[:2])
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=2, kv_block_size=BS, prefill_chunk=CHUNK), mesh=mesh)
    try:
        head = eng.params["lm_head"]
        assert head.dtype == cfg.dtype
        assert head.sharding.shard_shape(head.shape) == (
            cfg.d_model, cfg.vocab_size // 2)
        w_up = eng.params["layers"]["w_up"]
        assert w_up.dtype == cfg.dtype
        assert w_up.sharding.shard_shape(w_up.shape)[-1] == cfg.d_ff // 2
        prompt = [3, 1, 4, 1, 5]
        assert (eng.generate(prompt, max_new=6, timeout=120)
                == _ref_tokens(params, cfg, prompt, 6))
        assert eng.stats()["weight_bytes_cast_per_pass"] == 0
    finally:
        eng.shutdown()
