"""A pass that holds a prompt chunk AND decoding rows runs them as ONE
program where the family has it (ISSUE 41): ``decode.
make_paged_step_chunk`` stands in for the chunk program and the decode
step back to back, the pass's last chunk is prepared and packed and the
step launches it, and a prompt that ends in it gets its first token from
the step's own integers.  ``eng._step_chunk = None`` is the only switch
there is: the engine then takes the pass of two programs.  Since ISSUE 46
the hybrid family has the program too (``recurrent.
make_recurrent_step_chunk``) for the layouts whose every sublayer kind
takes a window in two parts, Mamba-2's recurrent state among them and,
since ISSUE 58, the delta rule's matrix state; a latent or
window-attention sublayer keeps the pass of two programs
(``recurrent.has_step_chunk``, derived from the sublayer kinds).

Tiny CPU models at f32 (greedy parity must not hinge on bf16 ties), both
seams where the case applies."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.inference import (EngineConfig, InferenceEngine, decode,
                               recurrent)
from ray_tpu.inference import engine as engine_mod
from ray_tpu.inference.cache import BlockPool, PoolLayout
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
    """Greedy tokens by full recompute, for either family."""
    if isinstance(cfg, gpt.GPTConfig):
        out = gpt.generate(params, cfg, jnp.asarray([prompt], jnp.int32),
                           max_new=max_new, temperature=0.0)
        return np.asarray(out)[0, len(prompt):].tolist()
    seq = np.zeros((1, 64), np.int32)       # causal: the padding is unseen
    seq[0, :len(prompt)] = prompt
    forward = jax.jit(lambda t: hybrid.forward(params, t, cfg))
    for n in range(len(prompt), len(prompt) + max_new):
        seq[0, n] = int(np.argmax(forward(seq)[0, n - 1]))
    return seq[0, len(prompt):len(prompt) + max_new].tolist()


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
    tokens = rng.integers(0, cfg.vocab_size, b).astype(np.int32)
    # the token array: row 1's input token is the device's (``FEED`` in
    # its token column), the other rows' the host's
    feed0 = np.array([40, tokens[1], 41, 42], np.int32)
    tokens[1] = decode.FEED
    packed_step = decode.pack_step(
        tables, tokens, np.array([17, 9, 0, 3], np.int32),
        np.array([1, 1, 0, 1], bool))
    table = np.zeros(T, np.int32)
    table[:3] = [7, 8, 9]
    toks = np.zeros(C, np.int32)
    toks[:5] = rng.integers(0, cfg.vocab_size, 5)
    # positions 8 .. 12 of row 2's prompt: a partial chunk
    packed_chunk = decode.pack_chunk(table, toks, 8, 2, 5)

    l_c, g_c, k, v, feed = chunk(served, *pools(), jnp.asarray(feed0),
                                 packed_chunk)
    # the chunk's greedy token at its own row, nothing else moved
    assert feed.tolist() == [40, int(feed0[1]), int(g_c[0]), 42]
    l_s, g_s, k, v, feed = step(served, k, v, feed, packed_step)
    # every stepped row's greedy token; the chunk's row sat the step out
    assert feed.tolist() == [int(g_s[0]), int(g_s[1]), int(g_c[0]),
                             int(g_s[3])]
    l_f, g_f, k_f, v_f, feed_f = fused(
        served, *pools(), jnp.asarray(feed0),
        decode.pack_step_chunk(packed_step, packed_chunk))
    assert feed_f.tolist() == feed.tolist() and feed_f.dtype == jnp.int32
    # ... and handed row 1's token by the host, the step gives the same
    by_host = packed_step.copy()
    by_host[1, T] = feed0[1]
    l_h, g_h, *_ = step(served, *pools(), jnp.zeros(b, jnp.int32), by_host)
    np.testing.assert_allclose(np.asarray(l_h)[[0, 1, 3]],
                               np.asarray(l_s)[[0, 1, 3]],
                               rtol=1e-5, atol=1e-5)
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


# ----------------------------------------- the hybrid family's program

def _nemotron_like():
    """Single-mixer layers ``MEM*E``, two B/C groups, sigmoid-routed
    relu^2 experts, an untied head."""
    return hybrid.HybridConfig.tiny(
        layer_types=tuple(hybrid.PATTERN_KINDS[k] for k in "MEM*E"),
        experts_in_every_layer=False, gated_experts=False, ssm_groups=2,
        routed_scale=2.5, tied_head=False, embedding_multiplier=1.0,
        residual_multiplier=1.0, logits_scaling=1.0)


def _no_experts():
    """A mixer and the dense MLP a layer: no experts sublayer at all
    (the load of either part is zeros, in the same places)."""
    return hybrid.HybridConfig.tiny(dense_layers=3, dense_width=32)


def _olmo_like():
    """Two delta-rule layers and a full-attention one, each followed by
    the dense MLP, every sublayer's norm on its output, an untied
    head."""
    return hybrid.HybridConfig.tiny(
        layer_types=(hybrid.LINEAR, hybrid.LINEAR, hybrid.ATTENTION),
        lin_heads=2, lin_key_dim=8, lin_value_dim=16, dense_layers=3,
        dense_width=32, norm_output=True, qk_norm=True, tied_head=False)


HYBRID_LAYOUTS = {"granite_like": hybrid.HybridConfig.tiny,
                  "nemotron_like": _nemotron_like,
                  "no_experts": _no_experts,
                  "olmo_like": _olmo_like}
# (the step's active rows, the chunk's decode row, its real tokens)
WINDOWS = {"partial_chunk": ([1, 1, 0, 1], 2, 5),
           "whole_chunk": ([1, 0, 0, 1], 1, C),
           "one_token_chunk": ([0, 1, 1, 1], 0, 1),
           "warm_up": ([0, 0, 0, 0], 0, 0)}


@pytest.fixture(scope="module", params=list(HYBRID_LAYOUTS))
def layout(request):
    cfg = HYBRID_LAYOUTS[request.param]()
    return cfg, hybrid.init_params(cfg, jax.random.PRNGKey(0))


@pytest.mark.parametrize("window", list(WINDOWS))
def test_hybrid_program_gives_what_the_two_give(layout, window):
    """One fused call against the chunk program then the decode step on
    the same pools, state and packed inputs: the active rows' logits
    and greedy tokens, the chunk's last real position's, the experts'
    load of each part, the K/V pools (the scratch block aside) and every
    row's convolution and SSM (or matrix) state, within float32
    tolerance.  An
    inactive row's logits are nobody's: the two programs hand the
    chunk's row to the step with the chunk's state, the fused one with
    the state before it."""
    cfg, params = layout
    active, row, n_valid = WINDOWS[window]
    b = len(active)
    pool = BlockPool(cfg, 20, BS, max_seq=96, state_rows=b)
    T = pool.blocks_per_seq
    assert recurrent.has_step_chunk(cfg)
    geometry = dict(block_size=BS, n_table=T)
    step = recurrent.make_recurrent_decode_step(cfg, **geometry)
    chunk = recurrent.make_recurrent_chunk_fn(cfg, chunk=C, **geometry)
    fused = recurrent.make_recurrent_step_chunk(cfg, chunk=C, **geometry)
    assert fused is recurrent.make_recurrent_step_chunk(cfg, chunk=C,
                                                        **geometry)

    def fresh():
        arrays = (*pool.pools, pool.state.conv, pool.state.ssm)
        k, v, conv, ssm = (
            jax.random.normal(jax.random.PRNGKey(i), a.shape, a.dtype)
            for i, a in enumerate(arrays, 1))
        return (k, v), (conv, ssm * 0.1)
    rng = np.random.default_rng(0)
    tables = np.zeros((b, T), np.int32)
    tables[0, :3], tables[1, :2], tables[3, :1] = [1, 2, 3], [4, 5], [6]
    tables[2, :2] = [10, 11]
    positions = np.array([17, 9, 12, 3], np.int32)
    packed_step = decode.pack_step(
        tables, rng.integers(0, cfg.vocab_size, b).astype(np.int32),
        positions, np.array(active, bool))
    table = np.zeros(T, np.int32)
    table[:3] = [7, 8, 9]
    toks = np.zeros(C, np.int32)
    toks[:n_valid] = rng.integers(0, cfg.vocab_size, n_valid)
    # positions 8 .. of the chunk row's prompt (nothing, at warm-up)
    packed_chunk = decode.pack_chunk(table, toks, 8 if n_valid else 0, row,
                                     n_valid)

    feed0 = jnp.arange(50, 50 + b, dtype=jnp.int32)    # the token array
    l_c, i_c, pools, state, feed = chunk(params, *fresh(), feed0,
                                         packed_chunk)
    l_s, i_s, pools, state, feed = step(params, pools, state, feed,
                                        packed_step)
    l_f, i_f, pools_f, state_f, feed_f = fused(
        params, *fresh(), jnp.arange(50, 50 + b, dtype=jnp.int32),
        decode.pack_step_chunk(packed_step, packed_chunk))
    n = hybrid.N_LOAD
    # the token array: a stepped row's greedy token, the chunk's at its
    # row (a chunk of nothing writes nothing), the rest as they were
    want_feed = [int(i_s[n + r]) if active[r] else 50 + r for r in range(b)]
    if n_valid:
        want_feed[row] = int(i_c[n])
    assert feed.tolist() == want_feed == feed_f.tolist()
    assert l_f.shape == (b + 1, cfg.vocab_size) and l_f.dtype == jnp.float32
    assert i_f.shape == (n + b + n + 1,) and i_f.dtype == jnp.int32
    live = np.flatnonzero(active)
    tol = dict(rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(l_f)[live], np.asarray(l_s)[live],
                               **tol)
    np.testing.assert_allclose(l_f[b], l_c[max(n_valid, 1) - 1], **tol)
    i_f, i_s, i_c = (np.asarray(i).tolist() for i in (i_f, i_s, i_c))
    # the load of each part apart, then that part's greedy tokens
    assert i_f[:n] == i_s[:n] and i_f[n + b:] == i_c
    assert [i_f[n + r] for r in live] == [i_s[n + r] for r in live]
    k = cfg.experts_per_token * sum(
        kind == hybrid.EXPERTS for _, kind in cfg.sublayers)
    assert i_f[1] == k * len(live) and i_f[n + b + 1] == k * n_valid
    lay = PoolLayout.of(cfg, pool.k)
    scratch = [int(lay.rows(layer, 0)) for layer in range(lay.n_layers)]
    for got, want in zip(pools_f, pools):
        keep = np.ones(got.shape[0], bool)
        keep[scratch] = False
        np.testing.assert_allclose(np.asarray(got)[keep],
                                   np.asarray(want)[keep], **tol)
    for got, want in zip(state_f, state):
        np.testing.assert_allclose(got, want, **tol)


@pytest.mark.parametrize("kinds, kw, fused", [
    ((hybrid.MAMBA, hybrid.ATTENTION), {}, True),
    ((hybrid.ATTENTION,), {}, True),
    ((hybrid.MAMBA, hybrid.ATTENTION),
     dict(dense_layers=2, dense_width=32), True),
    ((hybrid.MAMBA, hybrid.ATTENTION, hybrid.MAMBA),
     dict(experts_in_every_layer=False), True),
    ((hybrid.LINEAR, hybrid.ATTENTION), {}, True),
    ((hybrid.LATENT,), {}, False),
    ((hybrid.WINDOW, hybrid.ATTENTION), {}, False)],
    ids=["mamba_attention", "attention_alone", "dense_mlp_no_experts",
         "mixers_alone", "linear_attention", "latent", "window_attention"])
def test_which_layouts_get_the_program_follows_from_the_sublayer_kinds(
        kinds, kw, fused):
    """Every sublayer kind must take a window in two parts: Mamba-2, the
    delta rule, attention over K/V blocks, routed experts and the dense
    MLP do, with or without an experts sublayer among them; a latent or
    a window-attention sublayer keeps the pass of two programs.  No
    engine option and no model name says so.  ``warm_up`` brings up and
    runs what there is."""
    kw = dict(layer_types=kinds, **kw)
    if hybrid.LINEAR in kinds:
        kw.update(lin_heads=2, lin_key_dim=8, lin_value_dim=16,
                  dense_layers=len(kinds), dense_width=32)
    if hybrid.LATENT in kinds:
        kw.update(q_rank=16, kv_rank=32, rope_dim=8, v_head_dim=16,
                  n_kv_heads=1, yarn=hybrid.Yarn())
    if hybrid.WINDOW in kinds:
        kw.update(window=16, rope_theta=10000.0)
    cfg = hybrid.HybridConfig.tiny(**kw)
    eng = InferenceEngine(
        hybrid.init_params(cfg, jax.random.PRNGKey(0)), cfg,
        EngineConfig(max_slots=2, max_seq=32, kv_block_size=BS,
                     prefill_chunk=C))
    try:
        assert recurrent.has_step_chunk(cfg) == fused
        assert (eng._step_chunk is not None) == fused
        eng.warm_up(timeout=300)
        assert eng.stats()["chunks_in_step"] == 0 and eng._load == []
        if fused:
            assert eng._step_chunk._cache_size() == 1
    finally:
        eng.shutdown()


def test_the_fused_program_does_not_depend_on_the_window_form():
    """Which form attends the chunk's queries follows from the table's
    span (``decode.window_by_head``: the walk over key blocks where the
    table is more than one, packed on a table of one, and on a sharded
    pool), whatever the heads; the fused program takes either, so many
    K/V heads of whole lane tiles — attended head by head and kept on
    the two programs until PR 53 — get it too."""
    def layout_of(cfg):
        layers, heads, head_dim = cfg.kv_geometry
        return PoolLayout(layers, 5, BS, heads, head_dim)
    many = hybrid.HybridConfig.tiny(n_heads=12, n_kv_heads=12, head_dim=128)
    few = hybrid.HybridConfig.tiny(n_heads=8, n_kv_heads=8, head_dim=128)
    block = decode.window_key_block(BS) // BS
    for cfg in (many, few):
        lay = layout_of(cfg)
        assert not decode.window_by_head(lay, block)
        assert decode.window_by_head(lay, block + 1)
        assert recurrent.has_step_chunk(cfg)


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


_SERVED = {}


def _serve_mix(params, cfg, fused: bool):
    """(The module's one model; served once a ``fused``.)"""
    if fused not in _SERVED:
        _SERVED[fused] = _serve_mix_once(params, cfg, fused)
    return _SERVED[fused]


def _serve_mix_once(params, cfg, fused: bool):
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
        assert eng._flight is None and not eng._pass.owes \
            and eng.stats()["active_slots"] == 0
    finally:
        eng.shutdown()
    # the loop thread has ended: the last pass is in the account
    return long_, whole, mix, reqs, outs, eng.stats(), calls, rode


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


@pytest.mark.parametrize("fused", [True, False])
def test_the_account_s_kinds_are_the_programs_the_passes_ran(cfg, params,
                                                            fused):
    """A pass's kind (``engine._PASS_KIND``) is derived from what it
    launched: over the mix the kinds' counts are the launches the spy
    saw, their tokens the tokens emitted, and ``gaps`` weighs every
    token but a request's first."""
    _, whole, mix, _reqs, outs, st, calls, _ = _serve_mix(params, cfg, fused)
    acct = st["loop_account"]
    kinds = acct["by_kind"]

    def count(*names):
        return sum(kinds[k]["count"] for k in names if k in kinds)
    assert count(*kinds) - count("idle") == acct["passes"]
    assert count("step_chunk", "chunk+step_chunk") == calls["step_chunk"]
    assert count("step", "chunk+step") == calls["step"]
    # (a pass whose chunks stop short of its last keeps the plain step)
    assert (count("step_chunk", "chunk+step_chunk") > 0) == fused
    assert fused or count("chunk+step") > 0
    # every lone chunk program ran in a pass of a ``chunk..`` kind
    assert count("chunk", "chunk+step", "chunk+step_chunk") \
        <= calls["chunk"] == st["chunk_passes"] - calls["step_chunk"]
    assert (calls["chunk"] > 0) == (
        count("chunk", "chunk+step", "chunk+step_chunk") > 0)
    assert sum(k.get("tokens", 0) for k in kinds.values()) \
        == st["tokens_greedy_on_device"] + st["tokens_sampled"] \
        == st["generated_tokens"]
    assert sum(acct["gaps"].values()) \
        == len(whole) - 1 + sum(len(out) - 1 for out in outs)
    assert all(k["host_ns"] + k["wait_ns"] == k["ns"] > 0
               for k in kinds.values())
    assert sum(k["ns"] for k in kinds.values()) \
        == acct["t_ns"] - acct["t_made_ns"]
    assert kinds["idle"]["wait_ns"] == acct["ns"]["parked"]


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
    not it rides.  A recurrent state does not stand in the way: the
    tiny hybrid model's sublayer kinds all take a window in two parts."""
    cfg, params, ec = seam
    monkeypatch.setattr(engine_mod, "ACCOUNT_EVERY_NS", 0)
    rng = np.random.default_rng(2)
    tracing.clear()
    tracing.enable_tracing()
    eng = InferenceEngine(params, cfg, ec)
    assert eng._step_chunk is not None
    if cfg.state_geometry is not None:
        assert recurrent.has_step_chunk(cfg)
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
    assert calls["step_chunk"] >= 3
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
    and the first real overlap compiles nothing.  It brings the pass's
    three programs up side by side first, and the calls that follow find
    each done: the compiler is handed every program ONCE (jax's own
    count, ``jax.monitoring``: one event a lowering and one a compile,
    whichever thread makes it)."""
    cfg, params, ec = seam
    decode.clear_fn_cache()
    eng = InferenceEngine(params, cfg, ec)
    made = []

    def note(event, duration, fun_name=None, **kw):
        if event.endswith(("/jaxpr_to_mlir_module_duration",
                           "/backend_compile_duration")):
            made.append((fun_name, event.rsplit("/", 1)[1]))
    jax.monitoring.register_event_duration_secs_listener(note)
    try:
        eng.warm_up(timeout=300)
        for name in ("step", "chunk_fn", "step_chunk"):
            assert sorted(what for fn, what in made
                          if fn == f"jit({name})") \
                == ["backend_compile_duration",
                    "jaxpr_to_mlir_module_duration"], name
        warm = eng.stats()
        assert warm["chunks_in_step"] == 0 and warm["chunk_passes"] == 1
        assert warm["decode_iterations"] == 1 and eng._load == []
        assert eng._step_chunk._cache_size() == 1
        first = eng.submit([3, 1, 4, 1, 5], max_new=30)
        next(first.stream(timeout=300))
        out = eng.generate(list(range(2, 21)), max_new=3, timeout=300)
        assert eng.stats()["chunks_in_step"] == 3
        assert eng._step_chunk._cache_size() == 1
        assert out == _ref_tokens(params, cfg, list(range(2, 21)), 3)
        assert first.result(timeout=300) \
            == _ref_tokens(params, cfg, [3, 1, 4, 1, 5], 30)
        # ... and nothing of the pass since
        assert len([fn for fn, _ in made if fn in (
            "jit(step)", "jit(chunk_fn)", "jit(step_chunk)")]) == 6
    finally:
        jax.monitoring.unregister_event_duration_listener(note)
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
