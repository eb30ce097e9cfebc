"""The ``afmoe`` layout through the serving path at a tiny size on the
CPU: the two paged programs (chunked prefill, then one-token steps)
through BOTH groups of K/V pools, and ``InferenceEngine`` with them,
held to the plain reference's full forward pass for contexts several
windows long; the window pool's occupancy stays bounded while the
context grows, its blocks go back exactly once, a preempted row resumes
exactly, and what the engine derives from window layers (no radix
index, no fused program, the packed table of two)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import afmoe as ref
from ray_tpu.inference import EngineConfig, InferenceEngine
from ray_tpu.inference import recurrent
from ray_tpu.inference.cache import BlockPool
from ray_tpu.inference.decode import pack_chunk, pack_step
from ray_tpu.models import hybrid
from tests.test_afmoe_model import F32, HELD, PUB

# float32 against float32 (tests/test_afmoe_model.py's tolerance, for
# the same reason: the order of the sums)
ATOL = 5e-5
# heads of a whole lane tile, as the published model's: the window form
# is the head-wise one at any head count for this layout
WIDE = {**PUB, "head_dim": 128}
WINDOW = PUB["sliding_window"]          # 12


@pytest.fixture(scope="module")
def cfg():
    return hybrid.HybridConfig.from_published(WIDE, **F32)


@pytest.fixture(scope="module")
def params(cfg):
    return hybrid.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _ref(params, toks):
    return np.asarray(ref.logits(params, np.asarray(toks), WIDE, HELD))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n)


def _margins(params, prompt, emitted):
    seq = np.asarray(list(prompt) + list(emitted))
    step = _ref(params, seq)[len(prompt) - 1:len(seq) - 1]
    return step.max(-1) - step[np.arange(len(emitted)), emitted]


def _engine(cfg, params, **kw):
    ec = dict(max_slots=3, max_seq=160, kv_block_size=8, prefill_chunk=16,
              n_blocks=60, n_window_blocks=18)
    return InferenceEngine(params, cfg, EngineConfig(**{**ec, **kw}))


# ------------------------------------------------------- the two programs

def test_programs_chunks_then_decode_through_both_pools(cfg, params):
    """Two rows of unequal length, both several windows long: each
    prefilled in chunks (the last one partial), then decoded TOGETHER;
    a third row sits every pass out.  The window layers' table names
    only the blocks the next query can still see, as the engine keeps
    it: everything behind is the scratch block, and NaN lies there."""
    bs, C, n_rows = 8, 16, 3
    pool = BlockPool(cfg, n_blocks=24, block_size=bs, max_seq=96,
                     n_window_blocks=24, window_span=WINDOW + C + bs)
    T = pool.blocks_per_seq
    step = recurrent.make_recurrent_decode_step(cfg, block_size=bs,
                                                n_table=T)
    chunk = recurrent.make_recurrent_chunk_fn(cfg, chunk=C, block_size=bs,
                                              n_table=T)
    seqs = {0: _tokens(70, 8), 2: _tokens(52, 9)}
    prompts = {0: 61, 2: 37}
    want = {r: _ref(params, s) for r, s in seqs.items()}
    tables = np.zeros((n_rows, 2 * T), np.int32)
    tables[0, :9] = [3, 7, 2, 9, 11, 4, 13, 15, 17]
    tables[2, :7] = [5, 1, 8, 6, 10, 12, 14]
    tables[0, T:T + 9] = [2, 4, 6, 8, 10, 12, 14, 16, 18]
    tables[2, T:T + 7] = [1, 3, 5, 7, 9, 11, 13]

    def behind(row, pos):
        """The row's tables with the window blocks no query from ``pos``
        on can see given back."""
        t = tables[row].copy()
        t[T:T + max(0, pos - WINDOW + 1) // bs] = 0
        return t

    pools = list(pool.pools)
    # the scratch block of the window layers' pools holds NaN
    for i in (2, 3):
        rows = pool.window.layout.rows(np.arange(4), 0)
        pools[i] = pools[i].at[rows].set(jnp.nan)
    pools = tuple(pools)
    feed = jnp.zeros(n_rows, jnp.int32)     # the rows' next tokens
    for row, n_prompt in prompts.items():
        for pos in range(0, n_prompt, C):
            n_q = min(C, n_prompt - pos)
            toks = np.zeros(C, np.int32)
            toks[:n_q] = seqs[row][pos:pos + n_q]
            logits, load, pools, _, feed = chunk(
                params, pools, (), feed,
                pack_chunk(behind(row, pos), toks, pos, row, n_q))
            np.testing.assert_allclose(np.asarray(logits)[:n_q],
                                       want[row][pos:pos + n_q], atol=ATOL)
            assert int(load[hybrid.N_LOAD]) == int(
                np.asarray(logits)[n_q - 1].argmax())
        # (a chunk's padding wrote into the scratch block: NaN again)
    active = np.array([True, False, True])
    for t in range(9):
        tokens = np.zeros(n_rows, np.int32)
        positions = np.zeros(n_rows, np.int32)
        now = tables.copy()
        for row, n_prompt in prompts.items():
            tokens[row], positions[row] = (seqs[row][n_prompt + t],
                                           n_prompt + t)
            now[row] = behind(row, n_prompt + t)
        logits, load, pools, _, feed = step(
            params, pools, (), feed,
            pack_step(now, tokens, positions, active))
        for row, n_prompt in prompts.items():
            np.testing.assert_allclose(np.asarray(logits)[row],
                                       want[row][n_prompt + t], atol=ATOL)
        assert not np.asarray(logits)[1].any() or np.isfinite(
            np.asarray(logits)[1]).all()


def test_no_fused_program_and_two_tables_for_this_layout(cfg, params):
    eng = _engine(cfg, params)
    try:
        assert eng._step_chunk is None and eng.trie is None
        T = eng.pool.blocks_per_seq
        assert eng._tables_all.shape == (3, 2 * T)
        assert np.shares_memory(eng._tables, eng._tables_all)
        assert np.shares_memory(eng._wtables, eng._tables_all)
    finally:
        eng.shutdown()
    tiny = hybrid.HybridConfig.tiny()
    one = InferenceEngine(
        hybrid.init_params(tiny, jax.random.PRNGKey(0)), tiny,
        EngineConfig(max_slots=2, max_seq=96, n_blocks=12, kv_block_size=8,
                     prefill_chunk=8))
    try:
        assert one._tables_all.shape == (2, one.pool.blocks_per_seq)
        assert one.pool.window is None and one._step_chunk is not None
    finally:
        one.shutdown()


# ------------------------------------------------------------- the engine

@pytest.mark.parametrize("n_prompt, n_new", [(5, 30), (40, 24), (130, 20)])
def test_engine_equals_the_reference_over_several_windows(cfg, params,
                                                          n_prompt, n_new):
    """Prompts shorter than the window that decode past it, and prompts
    ten windows long: every emitted token is the reference's argmax of
    the full forward pass."""
    eng = _engine(cfg, params)
    prompt = _tokens(n_prompt, n_prompt).tolist()
    try:
        out = eng.generate(prompt, max_new=n_new, timeout=600)
        st = eng.stats()
    finally:
        eng.shutdown()
    assert len(out) == n_new
    assert _margins(params, prompt, out).max() <= ATOL
    assert st["tokens_greedy_on_device"] == st["generated_tokens"]
    assert st["prefix_hit_tokens"] == 0 and st["chunks_in_step"] == 0


def test_window_pool_stays_bounded_while_the_context_grows(cfg, params):
    """One row from 8 to 150 tokens: the full layers' pool holds the
    whole context, the window layers' never more than a window, a chunk
    and a block's rounding; every window block goes back exactly once
    (handed out = given back behind the window + released at exit, and
    the pool is whole again)."""
    eng = _engine(cfg, params)
    bs, C = 8, 16
    held, full = [], []
    prompt = _tokens(100, 3).tolist()
    try:
        req = eng.submit(prompt, max_new=50)
        for _ in req.stream(timeout=600):
            st = eng.stats()
            held.append(st["window_blocks_held"])
            full.append(st["blocks_total"] - st["blocks_free"])
        out = list(req.tokens)
        st = eng.stats()
        cap = eng.pool.window.blocks_per_row
    finally:
        eng.shutdown()
    assert _margins(params, prompt, out).max() <= ATOL
    assert cap == -(-(WINDOW + C + bs) // bs) + 1 == 6
    assert max(held) <= cap
    # while decoding a row holds the window's blocks alone
    assert max(held[5:]) <= -(-WINDOW // bs) + 1 == 3
    assert max(full) == -(-150 // bs) == 19         # the whole context
    assert st["window_blocks_held"] == 0 and st["blocks_free"] == 60
    assert st["window_blocks_allocated"] == -(-150 // bs)
    released_at_exit = st["window_blocks_allocated"] \
        - st["window_blocks_returned"]
    assert 0 < released_at_exit <= 3
    assert eng.pool.window.n_free == 18
    assert st["window_blocks_resident_sum"] < 0.3 \
        * st["window_blocks_one_table_sum"]
    # what a decode pass reads of the window pool: the window's blocks
    assert st["window_blocks_attended"] <= 3 * st["decode_iterations"]
    assert st["kv_blocks_attended"] > 12 * st["decode_iterations"]


def test_engine_rows_of_mixed_length_admitted_at_different_times(cfg,
                                                                 params):
    """Short and long prompts in one queue, more than there are rows."""
    eng = _engine(cfg, params)
    rng = np.random.default_rng(4)
    plan = [(90, 8), (6, 20), (33, 12), (120, 6), (14, 16)]
    prompts = [rng.integers(0, 256, n).tolist() for n, _ in plan]
    try:
        reqs = [eng.submit(p, max_new=m) for p, (_, m) in zip(prompts, plan)]
        outs = [r.result(timeout=900) for r in reqs]
        st = eng.stats()
    finally:
        eng.shutdown()
    for p, o, (_, m) in zip(prompts, outs, plan):
        assert len(o) == m
        assert _margins(params, p, o).max() <= ATOL
    assert st["window_blocks_held"] == 0 and st["blocks_free"] == 60
    assert st["expert_assignments_total"] == sum(
        n + m - 1 for n, m in plan) * 3 * 4


@pytest.mark.parametrize("short_of", ["full", "window"])
def test_engine_preemption_resumes_a_row_with_window_layers(cfg, params,
                                                            short_of):
    """A pool too small for three rows' growth — the full layers', or
    the window layers' — preempts a row: both groups' blocks go back,
    nothing is adopted (no index), the row re-prefills with what it had
    emitted and its stream continues exactly."""
    kw = dict(n_blocks=26) if short_of == "full" else dict(
        n_window_blocks=8)
    eng = _engine(cfg, params, **kw)
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
    assert st["window_blocks_held"] == 0
    assert st["blocks_free"] == st["blocks_total"]
    assert eng.pool.window.n_free == eng.pool.window.n_blocks


def test_engine_stats_reach_the_metrics_series(cfg, params):
    from ray_tpu.inference import metrics_snapshot
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=2, max_seq=96, kv_block_size=8, prefill_chunk=16,
        n_blocks=24, n_window_blocks=12), name="afmoe-series")
    try:
        eng.generate(_tokens(40, 1).tolist(), max_new=4, timeout=600)
        series = {name: values for name, _, _, values in metrics_snapshot()}
    finally:
        eng.shutdown()

    def of(name):
        return {dict(k)["engine"]: v for k, v in series[name].items()}[
            "afmoe-series"]
    assert of("ray_tpu_inference_window_blocks_allocated_total") == 6
    assert of("ray_tpu_inference_window_blocks_returned_total") >= 3
    assert of("ray_tpu_inference_window_blocks_held") == 0
    assert of("ray_tpu_inference_kv_blocks_allocated_total") == 6
    assert of("ray_tpu_inference_window_blocks_attended_total") > 0
