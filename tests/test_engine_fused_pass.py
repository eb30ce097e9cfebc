"""A pass that holds a prompt chunk AND decoding rows runs them as ONE
program where the family has it (ISSUE 41): ``decode.
make_paged_step_chunk`` stands in for the chunk program and the decode
step back to back, the pass's last chunk is prepared and packed and the
step launches it, and a prompt that ends in it gets its first token from
the step's own integers.  ``eng._step_chunk = None`` is the only switch
there is: the engine then takes the pass of two programs, as the family
with a recurrent state always does.

Tiny CPU models at f32 (greedy parity must not hinge on bf16 ties), both
seams where the case applies."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.inference import EngineConfig, InferenceEngine, decode
from ray_tpu.inference import engine as engine_mod
from ray_tpu.inference.cache import BlockPool
from ray_tpu.models import gpt, hybrid
from ray_tpu.util import tracing

BS = C = 8


@pytest.fixture(scope="module")
def cfg():
    return gpt.GPTConfig.tiny(dtype=jnp.float32, max_seq=96)


@pytest.fixture(scope="module")
def params(cfg):
    return gpt.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module", params=["kv_only", "kv_and_state"])
def seam(request, cfg, params):
    """(cfg, params, engine config) of an engine of either family."""
    if request.param == "kv_only":
        return cfg, params, EngineConfig(max_slots=4, kv_block_size=BS,
                                         prefill_chunk=C)
    hcfg = hybrid.HybridConfig.tiny()
    return (hcfg, hybrid.init_params(hcfg, jax.random.PRNGKey(0)),
            EngineConfig(max_slots=3, max_seq=96, n_blocks=20,
                         kv_block_size=BS, prefill_chunk=C))


def _ref_tokens(params, cfg, prompt, max_new):
    out = gpt.generate(params, cfg, jnp.asarray([prompt], jnp.int32),
                       max_new=max_new, temperature=0.0)
    return np.asarray(out)[0, len(prompt):].tolist()


def _spy(eng):
    """Count the engine's three pass programs' launches, and keep what
    the fused one was handed -> {"step", "chunk", "step_chunk": n},
    [(start, row, n_valid) of every chunk that rode]."""
    calls, rode = {"step": 0, "chunk": 0, "step_chunk": 0}, []
    T = eng.pool.blocks_per_seq

    def counted(name):
        program = getattr(eng, "_" + name)
        if program is None:
            return

        def run(params, *rest):
            calls[name] += 1
            if name == "step_chunk":
                rode.append(tuple(int(x) for x in rest[-1][-3:]))
                assert rest[-1].shape == (
                    eng.engine_cfg.max_slots * (T + 3) + T + C + 3,)
            return program(params, *rest)
        setattr(eng, "_" + name, run)
    for name in calls:
        counted(name)
    return calls, rode


# ------------------------------------------------------- the program

def test_one_program_gives_what_the_two_give(cfg, params):
    """One fused call against the chunk program then the decode step on
    the same pools: the decode rows' logits, the chunk's last real
    position's, every greedy token, and both pools (the scratch block
    aside), within float32 tolerance."""
    served = gpt.serving_params(params, cfg)
    b = 4
    pool = BlockPool(cfg, 20, BS, max_seq=cfg.max_seq)
    T = pool.blocks_per_seq
    geometry = dict(block_size=BS, n_table=T)
    step = decode.make_paged_decode_step(cfg, **geometry)
    chunk = decode.make_chunk_prefill_fn(cfg, chunk=C, **geometry)
    fused = decode.make_paged_step_chunk(cfg, chunk=C, **geometry)
    assert fused is decode.make_paged_step_chunk(cfg, chunk=C, **geometry)

    def pools():
        return tuple(jax.random.normal(jax.random.PRNGKey(i), p.shape,
                                       p.dtype)
                     for i, p in ((1, pool.k), (2, pool.v)))
    rng = np.random.default_rng(0)
    tables = np.zeros((b, T), np.int32)
    tables[0, :3], tables[1, :2], tables[3, :1] = [1, 2, 3], [4, 5], [6]
    packed_step = decode.pack_step(
        tables, rng.integers(0, cfg.vocab_size, b).astype(np.int32),
        np.array([17, 9, 0, 3], np.int32), np.array([1, 1, 0, 1], bool))
    table = np.zeros(T, np.int32)
    table[:3] = [7, 8, 9]
    toks = np.zeros(C, np.int32)
    toks[:5] = rng.integers(0, cfg.vocab_size, 5)
    # positions 8 .. 12 of row 2's prompt: a partial chunk
    packed_chunk = decode.pack_chunk(table, toks, 8, 2, 5)

    l_c, g_c, k, v = chunk(served, *pools(), packed_chunk)
    l_s, g_s, k, v = step(served, k, v, packed_step)
    l_f, g_f, k_f, v_f = fused(
        served, *pools(), decode.pack_step_chunk(packed_step, packed_chunk))
    assert l_f.shape == (b + 1, cfg.vocab_size) and l_f.dtype == jnp.float32
    assert g_f.shape == (b + 1,) and g_f.dtype == jnp.int32
    np.testing.assert_allclose(l_f[:b], l_s, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(l_f[b], l_c[4], rtol=1e-5, atol=1e-5)
    assert g_f.tolist() == g_s.tolist() + g_c.tolist()
    lay = decode.PoolLayout.of(cfg, pool.k, 1)
    scratch = [int(lay.rows(layer, 0)) for layer in range(cfg.n_layers)]
    for got, want in ((k_f, k), (v_f, v)):
        keep = np.ones(got.shape[0], bool)
        keep[scratch] = False
        np.testing.assert_allclose(np.asarray(got)[keep],
                                   np.asarray(want)[keep],
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------- the pass

def _mix(cfg):
    """A seeded mix of requests to overlap with one long answer:
    (prompt, max_new, temperature).  Cold prompts of several chunks with
    a partial last one, two that share a 16-token head (the second
    re-matches and jumps), one of a single token's answer, one sampled,
    one that is a whole number of chunks."""
    rng = np.random.default_rng(41)

    def toks(n):
        return rng.integers(0, cfg.vocab_size, n).tolist()
    head = toks(16)
    return [(toks(19), 6, 0.0), (head + toks(5), 5, 0.0),
            (head + toks(9), 4, 0.0), (toks(11), 1, 0.0),
            (toks(21), 7, 0.9), (toks(16), 3, 0.0), (toks(3), 5, 0.0)]


def _serve_mix(params, cfg, fused: bool):
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=4, kv_block_size=BS, prefill_chunk=C))
    if not fused:
        eng._step_chunk = None
    calls, rode = _spy(eng)
    long_ = np.random.default_rng(40).integers(0, cfg.vocab_size, 6).tolist()
    mix = _mix(cfg)
    try:
        first = eng.submit(long_, max_new=70)
        it = first.stream(timeout=300)
        head = [next(it) for _ in range(2)]           # it is decoding now
        reqs = [eng.submit(p, max_new=m, temperature=t, seed=3)
                for p, m, t in mix]
        outs = [r.result(timeout=300) for r in reqs]
        assert not first.done                         # ... and still is
        whole = head + list(it)
        st = eng.stats()
        assert eng._first_pending == [] and st["active_slots"] == 0
    finally:
        eng.shutdown()
    return long_, whole, mix, reqs, outs, st, calls, rode


def test_streams_are_the_two_program_pass_s_and_the_oracle_s(cfg, params):
    long_, whole, mix, reqs, outs, st, calls, rode = _serve_mix(
        params, cfg, fused=True)
    _, whole2, _, _, outs2, st2, calls2, rode2 = _serve_mix(
        params, cfg, fused=False)
    # token for token, the sampled request's too
    assert whole == whole2 == _ref_tokens(params, cfg, long_, 70)
    assert outs == outs2
    for (p, m, t), out in zip(mix, outs):
        assert len(out) == m
        if t == 0.0:
            assert out == _ref_tokens(params, cfg, p, m)
    # what rode: every chunk that was its pass's last, while the long
    # answer decoded; among them partial ones, one past a re-matched
    # head, and the ends of prompts
    assert calls2["step_chunk"] == 0 == st2["chunks_in_step"] and not rode2
    assert st["chunks_in_step"] == calls["step_chunk"] == len(rode) >= 7
    assert st["prefix_hit_tokens"] >= 16
    ends = {len(p) for p, _, _ in mix}
    assert any(n < C for _, _, n in rode)
    ended = [(s, r, n) for s, r, n in rode if s + n in ends]
    assert len(ended) >= 3
    # the counters say what ran
    for s, c in ((st, calls), (st2, calls2)):
        assert s["decode_iterations"] == c["step"] + c["step_chunk"]
        assert s["chunk_passes"] == c["chunk"] + c["step_chunk"]
        assert s["generated_tokens"] == 70 + sum(m for _, m, _ in mix)
        # greedy tokens chosen by the programs, the sampled request's
        # by dispatches of its own, its first token among them
        assert s["tokens_sampled"] == 7
        assert s["tokens_greedy_on_device"] == s["generated_tokens"] - 7
    # a first token that came with the step's integers cost no transfer
    # of its own: 4 x (rows [+ 1]) a step, 4 a first token owed by a
    # chunk program, 4 a sampled token's wait outside a step
    assert st["fetch_bytes"] <= st2["fetch_bytes"] + 4 * calls["step_chunk"]


def test_a_pass_with_no_decoding_row_runs_the_chunk_program(seam):
    """Alone on the engine a prompt's chunks have no step to ride."""
    cfg, params, ec = seam
    eng = InferenceEngine(params, cfg, ec)
    calls, rode = _spy(eng)
    try:
        out = eng.generate(list(range(1, 20)), max_new=4, timeout=300)
        st = eng.stats()
    finally:
        eng.shutdown()
    assert len(out) == 4
    assert calls == {"step": 3, "chunk": 3, "step_chunk": 0} and not rode
    assert st["chunks_in_step"] == 0 and st["chunk_passes"] == 3
    assert st["decode_iterations"] == 3


def test_counters_spans_and_account_say_how_often_it_engaged(
        seam, monkeypatch):
    """``chunks_in_step`` in ``stats()``, on ``engine.account`` beside
    ``chunk_passes``, and ``chunk_tokens`` on ``engine.decode``;
    ``engine.prefill_chunk`` stays a chunk's host preparation whether or
    not it rides.  An engine of the family with a recurrent state has no
    fused program and never packs for one."""
    cfg, params, ec = seam
    monkeypatch.setattr(engine_mod, "ACCOUNT_EVERY_NS", 0)
    recurrent = cfg.state_geometry is not None
    if recurrent:
        def never(*a):
            raise AssertionError("a fused pass on the K/V-and-state seam")
        monkeypatch.setattr(engine_mod, "pack_step_chunk", never)
    rng = np.random.default_rng(2)
    tracing.clear()
    tracing.enable_tracing()
    eng = InferenceEngine(params, cfg, ec)
    assert (eng._step_chunk is None) == recurrent
    calls, rode = _spy(eng)
    try:
        first = eng.submit(rng.integers(0, cfg.vocab_size, 5).tolist(),
                           max_new=40)
        it = first.stream(timeout=300)
        next(it)
        reqs = [eng.submit(rng.integers(0, cfg.vocab_size, n).tolist(),
                           max_new=m) for n, m in ((19, 3), (9, 1))]
        for r in reqs:
            r.result(timeout=300)
        assert not first.done
        list(it)
        st = eng.stats()
    finally:
        tracing.disable_tracing()
        eng.shutdown()
    spans = tracing.get_finished_spans()
    tracing.clear()
    assert st["chunk_passes"] == calls["chunk"] + calls["step_chunk"] == 6
    assert st["decode_iterations"] == calls["step"] + calls["step_chunk"]
    assert st["chunks_in_step"] == calls["step_chunk"]
    # (two rows prefilling in a pass: the first's chunk runs alone)
    assert calls["step_chunk"] == 0 if recurrent else calls["step_chunk"] >= 3
    decodes = [s["attributes"] for s in spans if s["name"] == "engine.decode"]
    assert sum(a["chunk_tokens"] > 0 for a in decodes) == st["chunks_in_step"]
    assert sum(a["chunk_tokens"] for a in decodes) \
        == sum(n for _, _, n in rode)
    chunks = [s["attributes"] for s in spans
              if s["name"] == "engine.prefill_chunk"]
    assert len(chunks) == st["chunk_passes"]
    assert sum(a["tokens"] for a in chunks) == 5 + 19 + 9
    last = [s for s in spans if s["name"] == "engine.account"][-1]
    assert last["attributes"]["chunks_in_step"] == st["chunks_in_step"]
    assert last["attributes"]["chunk_passes"] == st["chunk_passes"]
    # every program launched is one entry of ``dispatch``
    acct = st["loop_account"]
    assert acct["count"]["dispatch"] == sum(calls.values())
    assert acct["starved_ns"]["wait"] == 0


def test_block_hunt_comes_before_the_chunk_is_packed(cfg, params):
    """The step's block hunt may preempt the very row whose chunk would
    ride (the youngest): with ONE program it is made before the chunk is
    prepared, the preempted row's chunk is never launched nor counted,
    and the request re-prefills and streams exactly."""
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=4, kv_block_size=BS, prefill_chunk=C))
    calls, rode = _spy(eng)
    rng = np.random.default_rng(5)
    long_ = rng.integers(0, cfg.vocab_size, 6).tolist()
    late = rng.integers(0, cfg.vocab_size, 13).tolist()
    took, sound = [], eng._grow_rows

    def hunted():
        # what ``_grow_row`` -> ``_take_block`` does when the pool is dry
        sound()
        if eng._prefilling and not took:
            row = next(iter(eng._prefilling))
            took.append((eng._slot_req[row], eng.stats()["chunk_passes"]))
            eng._preempt_row(row)
    eng._grow_rows = hunted
    try:
        first = eng.submit(long_, max_new=30)
        it = first.stream(timeout=300)
        head = [next(it) for _ in range(3)]
        victim = eng.submit(late, max_new=6)
        out = victim.result(timeout=300)
        whole = head + list(it)
        st = eng.stats()
    finally:
        del eng._grow_rows                  # the instance's reference cycle
        eng.shutdown()
    assert [r for r, _ in took] == [victim] and victim.preemptions == 1
    assert took[0][1] == 1                  # the long prompt's one chunk
    assert st["preemptions"] == 1 and st["admissions"] == 3
    # the victim's two chunks ran once each, after its re-admission
    assert st["chunk_passes"] == 3 and victim.chunk_passes == 2
    assert sorted((s, n) for s, _, n in rode) == [(0, 8), (8, 5)]
    assert out == _ref_tokens(params, cfg, late, 6)
    assert whole == _ref_tokens(params, cfg, long_, 30)


@pytest.mark.parametrize("fused", [True, False],
                         ids=["one_program", "two_programs"])
def test_streams_exact_under_block_pressure(cfg, params, fused):
    """A pool that runs dry: rows are preempted by the pass's own block
    hunt, prefilling ones among them, and every stream stays the
    oracle's."""
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=4, max_seq=32, kv_block_size=BS, n_blocks=6,
        prefill_chunk=C))
    if not fused:
        eng._step_chunk = None
    try:
        rng = np.random.default_rng(1)
        jobs = []
        for _ in range(6):
            p = rng.integers(0, cfg.vocab_size,
                             int(rng.integers(6, 20))).tolist()
            jobs.append((p, eng.submit(p, max_new=12)))
        for p, h in jobs:
            assert h.result(timeout=300) == _ref_tokens(params, cfg, p, 12)
        st = eng.stats()
    finally:
        eng.shutdown()
    assert st["preemptions"] > 0
    assert (st["chunks_in_step"] > 0) == fused
    assert st["blocks_free"] + st["prefix_cached_blocks"] \
        == st["blocks_total"]


# ---------------------------------------------------- before a request

def test_warm_up_brings_the_fused_program_to_the_device(seam):
    """Requests that arrive one at a time never overlap a chunk with a
    decoding row: ``warm_up`` runs the fused program once on nothing,
    and the first real overlap compiles nothing."""
    cfg, params, ec = seam
    decode.clear_fn_cache()
    eng = InferenceEngine(params, cfg, ec)
    try:
        eng.warm_up(timeout=300)
        warm = eng.stats()
        assert warm["chunks_in_step"] == 0 and warm["chunk_passes"] == 1
        assert warm["decode_iterations"] == 1 and eng._load == []
        if eng._step_chunk is None:
            return
        assert eng._step_chunk._cache_size() == 1
        first = eng.submit([3, 1, 4, 1, 5], max_new=30)
        next(first.stream(timeout=300))
        out = eng.generate(list(range(2, 21)), max_new=3, timeout=300)
        assert eng.stats()["chunks_in_step"] == 3
        assert eng._step_chunk._cache_size() == 1
        assert out == _ref_tokens(params, cfg, list(range(2, 21)), 3)
        assert first.result(timeout=300) \
            == _ref_tokens(params, cfg, [3, 1, 4, 1, 5], 30)
    finally:
        eng.shutdown()
        decode.clear_fn_cache()


def test_no_fused_program_where_a_pass_may_speculate_or_route(cfg, params):
    """The speculative iteration may stand in for the step, and routed
    experts' capacity is per window: such engines keep the two programs."""
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=2, kv_block_size=BS, prefill_chunk=C, speculate="ngram"))
    try:
        assert eng._step_chunk is None
    finally:
        eng.shutdown()
    moe = gpt.GPTConfig.tiny(dtype=jnp.float32, max_seq=32, n_experts=4,
                             expert_top_k=2)
    eng = InferenceEngine(gpt.init_params(moe, jax.random.PRNGKey(0)), moe,
                          EngineConfig(max_slots=2, kv_block_size=BS,
                                       prefill_chunk=C))
    try:
        assert eng._step_chunk is None
    finally:
        eng.shutdown()
