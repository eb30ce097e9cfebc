"""The ``lfm2_moe`` layout's state snapshots over their life, at a tiny
size on the CPU: a snapshot is freed, evicted and reset WITH its block,
a preempted row resumes from its last one, a chain that carries them has
no interchange format — and what the engine derives for the layouts
whose state has no snapshot form (tests/test_lfm2_engine.py holds the
programs and adoption to the reference)."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import lfm2 as ref
from ray_tpu.inference import EngineConfig, InferenceEngine
from ray_tpu.inference.cache import BlockPool, snapshot_geometry
from ray_tpu.models import hybrid
from tests.test_lfm2_model import F32, HELD, PUB, seeded

# float32 against float32 (tests/test_lfm2_model.py's tolerance, for the
# same reason: the order of the sums; bfloat16 products read 3e-2)
ATOL = 5e-5
BS, C = 8, 16


@pytest.fixture(scope="module")
def cfg():
    return hybrid.HybridConfig.from_published(PUB, **F32)


@pytest.fixture(scope="module")
def params(cfg):
    return seeded(cfg)


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _ref(params, toks):
    return np.asarray(ref.logits(params, np.asarray(toks), PUB, HELD))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n)


def _margins(params, prompt, emitted):
    seq = np.asarray(list(prompt) + list(emitted))
    step = _ref(params, seq)[len(prompt) - 1:len(seq) - 1]
    return step.max(-1) - step[np.arange(len(emitted)), emitted]


def _engine(cfg, params, **kw):
    ec = dict(max_slots=3, max_seq=160, kv_block_size=BS, prefill_chunk=C,
              n_blocks=60)
    return InferenceEngine(params, cfg, EngineConfig(**{**ec, **kw}))


def _serve(eng, prompt, n=10):
    req = eng.submit(list(prompt), max_new=n)
    return req, req.result(timeout=600)


# ------------------------------------------- a snapshot lives with its block

def test_snapshot_is_freed_evicted_and_reset_with_its_block(cfg, params):
    """Snapshots are indexed by block id: nothing of their own to
    allocate or free.  Under pressure the index evicts LRU chains, the
    freed blocks are written anew (K/V and snapshot), and what is served
    from them is still the reference's; ``reset`` zeroes them."""
    eng = _engine(cfg, params, n_blocks=20, max_slots=2)
    heads = [_tokens(40, seed=10 + i) for i in range(4)]
    try:
        for h in heads:             # 4 x 6 blocks > 20: the oldest go
            _serve(eng, np.concatenate([h, _tokens(3, 1)]), n=6)
        assert eng.trie.evicted_blocks > 0
        assert eng.pool.n_free + eng.trie.cached_blocks == 20
        for bid in range(1, 21):    # refcounts: the index's alone
            assert eng.pool.refcount(bid) in (0, 1)
        # the newest head is still cached; the oldest was evicted
        new = np.concatenate([heads[3], _tokens(5, 2)])
        old = np.concatenate([heads[0], _tokens(5, 2)])
        r_new, o_new = _serve(eng, new)
        r_old, o_old = _serve(eng, old)
        assert r_new.prefix_hit_tokens == 40 and r_old.prefix_hit_tokens == 0
        assert _margins(params, new, o_new).max() <= ATOL
        assert _margins(params, old, o_old).max() <= ATOL
        assert float(jnp.abs(eng.pool.state.snap[1:]).max()) > 0
        eng._run_op(lambda: (eng.trie.clear(), eng.pool.reset()))
        assert float(jnp.abs(eng.pool.state.snap).max()) == 0.0
        assert eng.pool.n_free == 20 and eng.trie.cached_blocks == 0
        r, o = _serve(eng, new)
        assert r.prefix_hit_tokens == 0 and o == o_new
    finally:
        eng.shutdown()


def test_preempted_row_resumes_from_its_last_snapshot(cfg, params):
    """A pool too small for three rows' growth preempts a row: its full
    blocks go to the index WITH their snapshots, and re-admitted it
    adopts them and re-prefills only what lies behind the last one."""
    eng = _engine(cfg, params, n_blocks=21)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, n).tolist() for n in (50, 44, 60)]
    try:
        reqs = [eng.submit(p, max_new=30) for p in prompts]
        outs = [r.result(timeout=900) for r in reqs]
        st = eng.stats()
    finally:
        eng.shutdown()
    assert st["preemptions"] >= 1
    for p, o in zip(prompts, outs):
        assert len(o) == 30
        assert _margins(params, p, o).max() <= ATOL
    resumed = [r for r in reqs if r.preemptions]
    assert resumed and all(r.prefix_hit_tokens >= BS for r in resumed)
    assert st["state_snapshots_restored"] >= len(resumed)
    # re-prefilled: less than the preempted rows' whole contexts
    assert st["prefill_tokens"] < sum(map(len, prompts)) + sum(
        len(r.prompt) for r in resumed)
    assert st["blocks_free"] + st["prefix_cached_blocks"] == 21


def test_snapshot_chain_has_no_interchange_format(cfg):
    pool = BlockPool(cfg, n_blocks=12, block_size=BS, max_seq=96,
                     state_rows=1)
    with pytest.raises(NotImplementedError, match="state snapshot"):
        pool.read_blocks([1])


# ------------------------------------------------ what the engine derives

def _layout(name):
    if name == "granite":
        return hybrid.HybridConfig.tiny()
    if name == "olmo":
        from tests.test_olmo_hybrid_model import PUB as P, F32 as F
    elif name == "deepseek":
        from tests.test_latent_model import PUB as P, F32 as F
    else:
        from tests.test_afmoe_model import PUB as P, F32 as F
    return hybrid.HybridConfig.from_published(P, **F)


@pytest.mark.parametrize("name,index", [
    ("granite", False), ("olmo", False), ("trinity", False),
    ("deepseek", True)])
def test_other_layouts_keep_their_derivation(name, index):
    """An SSM or matrix state has no snapshot form and a window pool's
    blocks are given back: no index; a latent model's past is blocks
    alone: an index, with tails."""
    c = _layout(name)
    assert snapshot_geometry(c) is None
    p = hybrid.init_params(c, jax.random.PRNGKey(0))
    kw = dict(n_window_blocks=12) if name == "trinity" else {}
    eng = InferenceEngine(p, c, EngineConfig(
        max_slots=2, max_seq=96, kv_block_size=8, prefill_chunk=16,
        n_blocks=24, **kw))
    try:
        assert (eng.trie is not None) == index
        assert not eng.pool.snapshots
        if index:
            assert eng.trie.tails
        st = eng.stats()
        assert st["state_snapshot_bytes"] == 0
        if eng.pool.state is not None:
            assert len(eng.pool.state.arrays) == 2
            assert eng.pool.state.snap is None
    finally:
        eng.shutdown()


def test_speculation_refused_with_the_snapshot_s_reason(cfg, params):
    from ray_tpu.inference import SpeculationUnsupported
    with pytest.raises(SpeculationUnsupported, match="block end"):
        _engine(cfg, params, speculate="ngram")


def test_stats_reach_the_metrics_series(cfg, params):
    from ray_tpu.inference import metrics_snapshot
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=2, max_seq=96, kv_block_size=BS, prefill_chunk=C,
        n_blocks=24), name="lfm2-series")
    try:
        head = _tokens(24, 1)
        eng.generate(np.concatenate([head, _tokens(5, 2)]).tolist(),
                     max_new=4, timeout=600)
        eng.generate(np.concatenate([head, _tokens(6, 3)]).tolist(),
                     max_new=4, timeout=600)
        series = {name: values for name, _, _, values in metrics_snapshot()}
    finally:
        eng.shutdown()

    def of(name):
        return {dict(k)["engine"]: v for k, v in series[name].items()}[
            "lfm2-series"]
    assert of("ray_tpu_inference_state_snapshots_restored_total") == 1
    # 29 + 3 fed = 32 tokens: 4 blocks; 24 adopted + 6 + 3: 1 more
    assert of("ray_tpu_inference_state_snapshots_written_total") == 5
    assert of("ray_tpu_inference_state_snapshot_bytes") == 5 * 25 * 128 * 4
