"""The engine loop ONE pass ahead of what it has read (ISSUE 55): pass
N+1 is dispatched before pass N's tokens are fetched, the rows' next
tokens stay on the device (the programs' token array), and the host's
bookkeeping runs a pass behind.  Held here, on the CPU at tiny sizes and
at BOTH seams (``_KVOnly``: the GPT family; ``_KVAndState``: the hybrid
family, in a layout with an SSM state and in the one whose state has a
snapshot form and adopts prefixes): every stream is the plain
reference's whatever overlaps; what the loop cannot know a pass early
(EOS, a sampled row) and everything off the hot path (preemption, a
cancelled row, cross-thread ops, ``drain()``, ``shutdown()``, a program
that raises) behaves as it did with a synchronous loop; and the two
counters say what happened.
"""

import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import lfm2 as lfm2_ref
from ray_tpu.inference import (EngineConfig, EngineDrainingError,
                               EngineStoppedError, InferenceEngine)
from ray_tpu.models import gpt, hybrid
from test_hybrid_model import CFG as GRANITE
from test_hybrid_model import HELD, PUBLISHED, _init
from test_hybrid_model import ref as granite_ref
from tests.test_lfm2_model import F32, PUB, seeded
from tests.test_lfm2_model import HELD as LFM2_HELD

GPT = gpt.GPTConfig.tiny(dtype=jnp.float32, max_seq=96)
BS, C = 8, 8


def _lively(params):
    """The seeded tiny GPT repeats its prompt's last token for ever (a
    tied head over N(0, 0.02) embeddings): with the layers' matrices 16
    times larger its greedy streams wander over the vocabulary, so a
    token fed to the wrong row or a pass late shows."""
    layers = {k: v * 16.0 if k.startswith("w") else v
              for k, v in params["layers"].items()}
    return {**params, "layers": layers}


def _margins(logits_of, prompt, emitted):
    """How far each emitted token's reference logit lies below that
    position's maximum (teacher-forced full forward)."""
    seq = np.asarray(list(prompt) + list(emitted))
    step = logits_of(seq)[len(prompt) - 1:len(seq) - 1]
    return step.max(-1) - step[np.arange(len(emitted)), emitted]


@pytest.fixture(scope="module")
def gpt_seam():
    params = _lively(gpt.init_params(GPT, jax.random.PRNGKey(0)))

    def make(**kw):
        ec = dict(max_slots=4, kv_block_size=BS, prefill_chunk=C)
        return InferenceEngine(params, GPT, EngineConfig(**{**ec, **kw}))

    def verify(prompt, out):
        want = np.asarray(gpt.generate(
            params, GPT, jnp.asarray(prompt, jnp.int32)[None], len(out),
            temperature=0.0))[0, len(prompt):]
        assert list(out) == want.tolist()
    return SimpleNamespace(name="gpt", make=make, verify=verify, vocab=512)


@pytest.fixture(scope="module")
def granite_seam():
    params = _init(GRANITE)

    def make(**kw):
        ec = dict(max_slots=4, max_seq=96, n_blocks=40, kv_block_size=BS,
                  prefill_chunk=C)
        return InferenceEngine(params, GRANITE,
                               EngineConfig(**{**ec, **kw}))

    def logits_of(seq):
        return np.asarray(granite_ref.logits(params, np.asarray(seq),
                                             PUBLISHED, HELD))

    def verify(prompt, out):
        assert _margins(logits_of, prompt, out).max() <= 1e-5
    return SimpleNamespace(name="granite", make=make, verify=verify,
                           vocab=256)


@pytest.fixture(scope="module")
def lfm2_seam():
    cfg = hybrid.HybridConfig.from_published(PUB, **F32)
    params = seeded(cfg)

    def make(**kw):
        ec = dict(max_slots=4, max_seq=96, n_blocks=40, kv_block_size=BS,
                  prefill_chunk=C)
        return InferenceEngine(params, cfg, EngineConfig(**{**ec, **kw}))

    def logits_of(seq):
        with jax.default_matmul_precision("highest"):
            return np.asarray(lfm2_ref.logits(params, np.asarray(seq), PUB,
                                              LFM2_HELD))

    def verify(prompt, out):
        assert _margins(logits_of, prompt, out).max() <= 5e-5
    return SimpleNamespace(name="lfm2", make=make, verify=verify, vocab=256)


@pytest.fixture(params=["gpt", "granite", "lfm2"])
def seam(request):
    return request.getfixturevalue(request.param + "_seam")


@pytest.fixture(params=["gpt", "granite"])
def two_seams(request):
    """Both seams, one layout each."""
    return request.getfixturevalue(request.param + "_seam")


@pytest.fixture(params=["gpt", "lfm2"])
def adopting(request):
    """The seams' layouts that adopt prefixes: the radix index over K/V
    blocks, and over blocks that carry a state snapshot."""
    return request.getfixturevalue(request.param + "_seam")


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _prompts(seam, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, seam.vocab, n).tolist() for n in lengths]


def _account_holds(st, requests):
    """The loop's account after a quiesced run: the units by kind add up
    to the passes and the tokens, the gaps to every token but each
    request's first."""
    acct = st["loop_account"]
    kinds = {k: row for k, row in acct["by_kind"].items() if k != "idle"}
    assert sum(row["count"] for row in kinds.values()) == acct["passes"]
    emitted = st["tokens_greedy_on_device"] + st["tokens_sampled"]
    assert sum(row.get("tokens", 0)
               for row in acct["by_kind"].values()) == emitted
    assert emitted == sum(len(r.tokens) for r in requests)
    assert sum(acct["gaps"].values()) == sum(
        max(len(r.tokens) - 1, 0) for r in requests)
    for row in acct["by_kind"].values():
        assert row["ns"] == row["host_ns"] + row["wait_ns"]
        assert min(row.values()) >= 0


# -------------------------------------------------- the overlapping mix

def test_overlapping_mix_is_the_reference_s_token_for_token(seam):
    """Prompts that end inside a chunk that rides a decode step, on a
    chunk boundary and in a partial chunk; ``max_new`` 1 and 2 (the row
    is out again before its first token is read); arrivals while others
    decode.  Every stream is the reference's, nothing was drained, and
    the loop ran ahead of what it had read."""
    eng = seam.make()
    plan = [(5, 14), (19, 1), (16, 2), (8, 9), (27, 7), (3, 1), (11, 12),
            (24, 2), (9, 1), (13, 5)]
    prompts = _prompts(seam, [n for n, _ in plan])
    reqs = []
    for i, (p, (_, m)) in enumerate(zip(prompts, plan)):
        reqs.append(eng.submit(p, max_new=m))
        if i % 3 == 2:
            time.sleep(0.05)
    outs = [r.result(timeout=170) for r in reqs]
    eng.shutdown()
    st = eng.stats()
    for p, o, (_, m) in zip(prompts, outs, plan):
        assert len(o) == m
        seam.verify(p, o)
    assert eng._flight is None and not eng._pass.owes
    assert st["active_slots"] == 0 and not eng._owed.any()
    assert st["passes_drained"] == 0 and st["preemptions"] == 0
    assert st["loop_account"]["drained_by"] == {}
    assert st["passes_launched_ahead"] > 0
    assert st["chunks_in_step"] > 0
    # first tokens are their chunks', the rest the decode steps'
    assert st["tokens_sampled"] == 0
    assert st["tokens_greedy_on_device"] == sum(m for _, m in plan)
    assert st["row_tokens"] == st["row_steps"] \
        == sum(m - 1 for _, m in plan)
    # a fetch brings the step's integers (and the load's), a first
    # token's vector: never the logits
    assert st["fetch_bytes"] < 64 * (st["decode_iterations"]
                                     + st["chunk_passes"])
    _account_holds(st, reqs)


def test_a_long_decode_is_launched_ahead_pass_after_pass(two_seams):
    """Steady decoding: all but the first pass after the park is
    dispatched while the one before it is unread, and the device is
    never left without a program between two of them."""
    eng = two_seams.make()
    prompts = _prompts(two_seams, (6, 7), seed=3)
    reqs = [eng.submit(p, max_new=40) for p in prompts]
    outs = [r.result(timeout=170) for r in reqs]
    eng.shutdown()
    st = eng.stats()
    for p, o in zip(prompts, outs):
        two_seams.verify(p, o)
    acct = st["loop_account"]
    assert st["passes_drained"] == 0
    assert st["passes_launched_ahead"] >= acct["passes"] - 3
    assert st["passes_launched_ahead"] >= 0.9 * st["decode_iterations"]
    # starved only around the park: the waits for the device never are
    assert acct["starved_ns"]["wait"] <= 0.2 * acct["ns"]["wait"]
    _account_holds(st, reqs)


# ----------------------------------------------------- prefix adoption

def test_adoption_right_after_the_donor_s_last_token(adopting):
    """A request that arrives as its donor's LAST token lands adopts the
    donor's chain — prompt and generated tokens — while the loop's
    bookkeeping of that last pass has just run; and two cold copies of
    one prompt, submitted together, serialize: the second re-matches
    what the first published and jumps."""
    eng = adopting.make()
    donor, tail, cold = _prompts(adopting, (24, 5, 33), seed=5)
    first = eng.submit(donor, max_new=12)
    out = first.result(timeout=170)
    adopter = donor + out[:8] + tail        # 4 full blocks of the donor's
    second = eng.submit(adopter, max_new=6)
    twins = [eng.submit(cold, max_new=4) for _ in range(2)]
    outs = [r.result(timeout=170) for r in (second, *twins)]
    eng.shutdown()
    st = eng.stats()
    adopting.verify(donor, out)
    adopting.verify(adopter, outs[0])
    for o in outs[1:]:
        adopting.verify(cold, o)
    assert second.prefix_hit_tokens >= 24
    assert st["prefix_hit_tokens"] >= 24 + 32
    if adopting.name == "lfm2":
        assert max(t.prefix_hit_tokens for t in twins) >= 32
        assert st["state_snapshots_restored"] >= 2
    assert st["passes_drained"] == 0
    _account_holds(st, [first, second, *twins])


# ------------------------------------------------------------------ EOS

def test_eos_mid_stream_ends_the_stream_there_and_nothing_else(two_seams):
    """With ``eos_token`` set the loop STILL runs ahead: a row that emits
    EOS at pass N was stepped in pass N+1 already.  That one token is
    dropped (never emitted, never counted), the other rows' streams are
    untouched by the stray step, and the freed row serves the next
    request from a clean state."""
    plan = [(7, 24), (12, 24), (21, 24)]
    prompts = _prompts(two_seams, [n for n, _ in plan], seed=11)
    eng = two_seams.make()
    whole = [eng.submit(p, max_new=m).result(timeout=170)
             for p, (_, m) in zip(prompts, plan)]
    eng.shutdown()
    # a token the first stream emits in its middle, the others anywhere
    eos = whole[0][9]
    cut = [o[:o.index(eos) + 1] if eos in o else o for o in whole]
    assert 1 <= len(cut[0]) <= 10

    eng = two_seams.make(eos_token=eos, max_slots=3)
    reqs = [eng.submit(p, max_new=m) for p, (_, m) in zip(prompts, plan)]
    outs = [r.result(timeout=170) for r in reqs]
    # the rows that EOS freed serve again
    again = eng.submit(prompts[1], max_new=plan[1][1])
    outs.append(again.result(timeout=170))
    eng.shutdown()
    st = eng.stats()
    assert outs == cut + [cut[1]]
    for r, o in zip(reqs + [again], outs):
        assert r.tokens == o and eos not in o[:-1]
    # (the last EOS's stray step may still be in flight at shutdown)
    assert set(st["loop_account"]["drained_by"]) <= {"shutdown"}
    assert st["passes_launched_ahead"] > 0 and st["passes_drained"] <= 1
    firsts = len(outs)
    assert st["tokens_greedy_on_device"] == sum(map(len, outs))
    assert st["row_tokens"] == sum(map(len, outs)) - firsts
    assert st["generated_tokens"] == sum(map(len, outs))
    assert st["active_slots"] == 0 and st["blocks_free"] \
        + st["prefix_cached_blocks"] == st["blocks_total"]
    _account_holds(st, reqs + [again])


# --------------------------------------------------------- sampled rows

def test_sampled_rows_beside_greedy_ones_give_today_s_streams(two_seams):
    """A row with temperature > 0 takes its token on the host from the
    pass's logits with its own rng: a pass with such a row is not
    launched ahead.  Its stream is bit for bit what it is served ALONE
    (every pass synchronous, the loop of before), the greedy rows beside
    it are the reference's, and the loop goes ahead again once it has
    left."""
    prompts = _prompts(two_seams, (9, 14, 6, 17), seed=21)
    alone = two_seams.make()
    want_a = alone.submit(prompts[0], max_new=10, temperature=0.8,
                          seed=5).result(timeout=170)
    want_b = alone.submit(prompts[3], max_new=1, temperature=1.3,
                          seed=9).result(timeout=170)
    alone.shutdown()
    assert alone.stats()["passes_launched_ahead"] == 0

    eng = two_seams.make()
    long_ = eng.submit(prompts[1], max_new=36)
    it = long_.stream(timeout=170)
    head = [next(it) for _ in range(3)]           # it is decoding now
    sampled = eng.submit(prompts[0], max_new=10, temperature=0.8, seed=5)
    greedy = eng.submit(prompts[2], max_new=8)
    one = eng.submit(prompts[3], max_new=1, temperature=1.3, seed=9)
    outs = [r.result(timeout=170) for r in (sampled, greedy, one)]
    whole = head + list(it)
    eng.shutdown()
    st = eng.stats()
    assert outs[0] == want_a and outs[2] == want_b
    two_seams.verify(prompts[1], whole)
    two_seams.verify(prompts[2], outs[1])
    assert st["tokens_sampled"] == 10 + 1
    assert st["loop_account"]["drained_by"].get("sampled", 0) >= 1
    assert st["passes_launched_ahead"] > 0       # before and after it
    _account_holds(st, [long_, sampled, greedy, one])


def test_a_speculating_engine_stays_synchronous(gpt_seam):
    eng = gpt_seam.make(speculate="ngram", speculate_k=3)
    prompts = _prompts(gpt_seam, (9, 14, 6), seed=2)
    reqs = [eng.submit(p, max_new=12) for p in prompts]
    outs = [r.result(timeout=170) for r in reqs]
    eng.shutdown()
    st = eng.stats()
    for p, o in zip(prompts, outs):
        gpt_seam.verify(p, o)
    assert st["passes_launched_ahead"] == 0 and st["passes_drained"] == 0
    assert eng._step_chunk is None


# ---------------------------------------------------- off the hot path

def test_preemption_under_block_pressure_lands_the_pass_first(two_seams):
    """A pool too small for all rows: before a row is preempted the pass
    in flight is read (a victim's tokens are the stream's before it is
    requeued), and every stream goes on exactly."""
    eng = two_seams.make(n_blocks=12, max_slots=3)
    prompts = _prompts(two_seams, (30, 28, 26), seed=1)
    reqs = [eng.submit(p, max_new=24) for p in prompts]
    outs = [r.result(timeout=170) for r in reqs]
    eng.shutdown()
    st = eng.stats()
    for p, o in zip(prompts, outs):
        assert len(o) == 24
        two_seams.verify(p, o)
    assert st["preemptions"] >= 1
    assert st["loop_account"]["drained_by"].get("preempt", 0) >= 1
    assert st["passes_drained"] == sum(
        st["loop_account"]["drained_by"].values())
    assert st["passes_launched_ahead"] > 0
    _account_holds(st, reqs)


def test_a_cancelled_row_leaves_with_what_was_in_flight_read(two_seams):
    eng = two_seams.make()
    prompts = _prompts(two_seams, (8, 13, 5), seed=4)
    victim = eng.submit(prompts[0], max_new=60)
    others = [eng.submit(p, max_new=30) for p in prompts[1:]]
    it = victim.stream(timeout=170)
    head = [next(it) for _ in range(4)]
    victim.cancel()
    rest = list(it)
    outs = [r.result(timeout=170) for r in others]
    eng.shutdown()
    st = eng.stats()
    assert victim.done and len(head + rest) < 60
    two_seams.verify(prompts[0], head + rest)     # a prefix of its stream
    for p, o in zip(prompts[1:], outs):
        assert len(o) == 30
        two_seams.verify(p, o)
    assert st["loop_account"]["drained_by"].get("cancel", 0) == 1
    assert st["active_slots"] == 0 and not eng._owed.any()
    _account_holds(st, [victim, *others])


def test_cross_thread_ops_run_with_nothing_in_flight(gpt_seam):
    """``prefix_extract`` while rows decode: the op runs on the loop
    thread between passes, after the pass in flight has landed."""
    eng = gpt_seam.make()
    head, other = _prompts(gpt_seam, (24, 7), seed=8)
    eng.submit(head + [1], max_new=2).result(timeout=170)   # publishes it
    gen = eng.pool.generation
    running = [eng.submit(other, max_new=50),
               eng.submit(other[::-1], max_new=50)]
    it = running[0].stream(timeout=170)
    first = [next(it) for _ in range(3)]
    seen = []

    def op():
        assert eng._flight is None      # on the loop thread, drained
        seen.append(True)
    got = eng.prefix_extract(head, gen)
    eng._run_op(op)
    outs = [first + list(it), running[1].result(timeout=170)]
    eng.shutdown()
    st = eng.stats()
    assert got["n_tokens"] == 24 and np.shape(got["k"])[1] == 3 and seen
    gpt_seam.verify(other, outs[0])
    gpt_seam.verify(other[::-1], outs[1])
    assert st["loop_account"]["drained_by"].get("op", 0) >= 1
    assert st["passes_launched_ahead"] > 0


def test_drain_lets_what_is_in_flight_decode_to_the_end(two_seams):
    eng = two_seams.make(max_slots=2)
    prompts = _prompts(two_seams, (8, 13, 5), seed=6)
    reqs = [eng.submit(p, max_new=30) for p in prompts]
    it = reqs[0].stream(timeout=170)
    head = [next(it) for _ in range(3)]
    eng.drain()
    with pytest.raises(EngineDrainingError):
        eng.submit(prompts[0], max_new=2)
    outs = [head + list(it), reqs[1].result(timeout=170)]
    with pytest.raises(EngineDrainingError):     # queued: handed back
        reqs[2].result(timeout=170)
    deadline = time.monotonic() + 30
    while eng.stats()["active_slots"] and time.monotonic() < deadline:
        time.sleep(0.01)
    st = eng.stats()
    eng.shutdown()
    assert st["active_slots"] == 0 and st["draining"]
    for p, o in zip(prompts, outs):
        assert len(o) == 30
        two_seams.verify(p, o)


def test_shutdown_with_a_pass_in_flight(two_seams):
    eng = two_seams.make()
    prompts = _prompts(two_seams, (8, 13, 5), seed=7)
    reqs = [eng.submit(p, max_new=80) for p in prompts]
    it = reqs[0].stream(timeout=170)
    for _ in range(3):
        next(it)
    eng.shutdown(timeout=60)
    assert not eng._thread.is_alive() and eng._flight is None
    for p, r in zip(prompts, reqs):
        assert r.done and isinstance(r.error, EngineStoppedError)
        assert 0 < len(r.tokens) < 80
        two_seams.verify(p, r.tokens)             # whole up to the stop
    with pytest.raises(EngineStoppedError):
        eng.submit(prompts[0], max_new=2)


def test_a_program_that_raises_when_launched_ahead(two_seams):
    """The decode step raises on a call made while the pass before it
    is unread.  What that earlier pass owed still reaches the streams (a
    request whose last token it carried FINISHES), the requests in the
    failed pass fail with the program's error, a request still waiting
    for a row does not, the pools are rebuilt and the engine serves the
    next request exactly."""
    eng = two_seams.make(max_slots=2)
    prompts = _prompts(two_seams, (8, 13, 5, 10), seed=9)
    calls = []

    def raising(program):
        def step(*args):
            calls.append(eng._flight is not None)
            # the 5th decode step (with a chunk inside it or not):
            # ``short``'s 5 tokens are its chunk's and four steps', the
            # last of them still unread here
            if len(calls) == 5:
                raise RuntimeError("boom")
            return program(*args)
        return step
    eng._step, eng._step_chunk = map(raising, (eng._step, eng._step_chunk))
    short = eng.submit(prompts[0], max_new=5)
    long_ = eng.submit(prompts[1], max_new=40)
    waiting = eng.submit(prompts[2], max_new=4)   # no row for it yet
    out_short = short.result(timeout=170)
    with pytest.raises(RuntimeError, match="boom"):
        long_.result(timeout=170)
    out_waiting = waiting.result(timeout=170)
    after = eng.submit(prompts[3], max_new=6)
    out_after = after.result(timeout=170)
    eng.shutdown()
    st = eng.stats()
    assert calls[4] is True                 # it WAS launched ahead
    assert len(out_short) == 5
    two_seams.verify(prompts[0], out_short)
    two_seams.verify(prompts[1], long_.tokens)    # whole up to the failure
    assert 1 <= len(long_.tokens) < 40
    two_seams.verify(prompts[2], out_waiting)
    two_seams.verify(prompts[3], out_after)
    assert st["pool_generation"] == 1 and st["active_slots"] == 0
    assert st["blocks_free"] + st["prefix_cached_blocks"] \
        == st["blocks_total"]
    acct = st["loop_account"]
    kinds = {k: row for k, row in acct["by_kind"].items() if k != "idle"}
    assert sum(row["count"] for row in kinds.values()) == acct["passes"]


# ------------------------------------------------------ the token array

def test_the_next_tokens_stay_on_the_device(gpt_seam):
    """What the host hands a launched-ahead step for a stepped row is
    ``FEED``, not a token: the packed arrays of a steady decode carry
    the sentinel in the token column of every row that was stepped the
    pass before."""
    from ray_tpu.inference import decode, engine
    eng = gpt_seam.make()
    seen, real = [], engine.pack_step

    def pack_step(tables, tokens, positions, active):
        seen.append((tokens.copy(), active.copy()))
        return real(tables, tokens, positions, active)
    prompts = _prompts(gpt_seam, (6, 7), seed=3)
    try:
        engine.pack_step = pack_step
        reqs = [eng.submit(p, max_new=20) for p in prompts]
        outs = [r.result(timeout=170) for r in reqs]
    finally:
        engine.pack_step = real
        eng.shutdown()
    for p, o in zip(prompts, outs):
        gpt_seam.verify(p, o)
    stepped = [tokens[active] for tokens, active in seen if active.any()]
    assert len(stepped) >= 19
    assert sum((t == decode.FEED).all() for t in stepped) >= len(stepped) - 2
    assert eng._feed.shape == (4,) and eng._feed.dtype == jnp.int32
