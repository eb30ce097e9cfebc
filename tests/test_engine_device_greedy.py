"""The GPT serving programs pick their own greedy tokens (ISSUE 39): a
greedy pass fetches integers and leaves the logits on the device, a
sampled row indexes them there with its own rng, and a prompt's greedy
first token is the chunk program's own argmax, emitted behind the
pass's decode step.  The hybrid family's engine tests
(tests/test_hybrid_model.py) hold the same host path on the other seam.

Everything runs on CPU with GPTConfig.tiny at f32 (greedy argmax parity
must not hinge on bf16 ties)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.inference import (EngineConfig, InferenceEngine,
                               metrics_snapshot)
from ray_tpu.inference import engine as engine_mod
from ray_tpu.models import gpt
from ray_tpu.util import tracing


@pytest.fixture(scope="module")
def cfg():
    return gpt.GPTConfig.tiny(dtype=jnp.float32, max_seq=64)


@pytest.fixture(scope="module")
def params(cfg):
    return gpt.init_params(cfg, jax.random.PRNGKey(0))


def _engine(params, cfg, **kw):
    ec = dict(max_slots=4, kv_block_size=8, prefill_chunk=8)
    return InferenceEngine(params, cfg, EngineConfig(**{**ec, **kw}))


def _ref_tokens(params, cfg, prompt, max_new):
    out = gpt.generate(params, cfg, jnp.asarray([prompt], jnp.int32),
                       max_new=max_new, temperature=0.0)
    return np.asarray(out)[0, len(prompt):].tolist()


def _ref_sampled(params, cfg, prompt, max_new, temperature, seed):
    """The sampled stream by full recompute: the request's key, split
    once a token, on the full forward's last logits."""
    key = jax.random.PRNGKey(seed)
    seq = list(prompt)
    for _ in range(max_new):
        key, sub = jax.random.split(key)
        logits = gpt.forward(params, jnp.asarray([seq], jnp.int32), cfg)
        seq.append(int(gpt.sample_token(logits[0, -1],
                                        temperature=temperature, rng=sub)))
    return seq[len(prompt):]


@pytest.fixture
def sampling_calls(monkeypatch):
    """Every ``gpt.sample_token`` call, by its temperature: each one the
    engine makes is a program of its own (``jit__argmax`` or a
    categorical draw).  The oracles call it too: read the list before
    running one."""
    calls = []
    sound = gpt.sample_token

    def counted(logits, *, temperature=1.0, rng=None):
        calls.append(temperature)
        return sound(logits, temperature=temperature, rng=rng)
    monkeypatch.setattr(engine_mod.gpt, "sample_token", counted)
    return calls


# ------------------------------------------- greedy beside sampled rows

# prompts of at most half the cache take the chunk path, whose own
# argmax is the first token; a cold longer one on an idle engine takes
# the full-width prefill, which has no greedy output and samples
@pytest.mark.parametrize("first_long", [False, True],
                         ids=["chunked", "full_width_first"])
def test_mixed_batch_greedy_exact_and_sampled_reproduce(
        params, cfg, sampling_calls, first_long):
    rng = np.random.default_rng(11)
    lens = [40 if first_long else 21, 9, 17, 5]
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in lens]
    temps = [0.0, 0.8, 0.0, 1.3]
    max_new = 10
    eng = _engine(params, cfg)
    try:
        reqs = []
        for i, (p, t) in enumerate(zip(prompts, temps)):
            reqs.append(eng.submit(p, max_new=max_new, temperature=t,
                                   seed=7 + i))
            if first_long and i == 0:
                # "on an idle engine" is the loop's to see: it prefills
                # the shortest remaining prompt first, so once it sees
                # all four the three short ones decode before the long
                # one starts and it chunks (the every-run failure on a
                # quiet box, 0 == 1).  The others follow its first token.
                next(reqs[0].stream(timeout=300))
        outs = [r.result(timeout=300) for r in reqs]
        st, calls = eng.stats(), sorted(sampling_calls)
    finally:
        eng.shutdown()
    for i, (p, t, out) in enumerate(zip(prompts, temps, outs)):
        if t == 0.0:       # unaffected by the sampled rows beside it
            assert out == _ref_tokens(params, cfg, p, max_new)
        else:              # its own rng, on its own row's logits
            assert out == _ref_sampled(params, cfg, p, max_new, t, 7 + i)
    full_width = sum(r.full_width_prefill for r in reqs)
    assert full_width == int(first_long)
    # greedy rows cost no sampling dispatch: every call is a sampled
    # row's token, or the full-width prefill's greedy first token
    assert calls == [0.0] * full_width + [0.8] * max_new + [1.3] * max_new
    assert st["tokens_sampled"] == len(calls)
    assert st["tokens_greedy_on_device"] == 2 * max_new - full_width
    assert st["generated_tokens"] == 4 * max_new


def test_sampled_row_alone_gives_the_same_stream(params, cfg):
    """Neighbours change nothing: the sampled request of the mixed batch
    alone on a fresh engine."""
    p = np.random.default_rng(11).integers(0, cfg.vocab_size, 21).tolist()
    outs = []
    for beside in (True, False):
        eng = _engine(params, cfg)
        try:
            hot = eng.submit(p, max_new=8, temperature=0.9, seed=5)
            if beside:
                eng.submit(list(range(30, 41)), max_new=8)
            outs.append(hot.result(timeout=300))
        finally:
            eng.shutdown()
    assert outs[0] == outs[1] \
        == _ref_sampled(params, cfg, p, 8, 0.9, 5)


# -------------------------------- a first token behind the decode step

@pytest.mark.parametrize("fused", [True, False],
                         ids=["one_program", "two_programs"])
def test_first_token_behind_a_running_decode(params, cfg, sampling_calls,
                                             fused):
    """A prompt that ends while other rows decode: its first token is
    its program's own argmax — the last integer of the step that ran
    the chunk (ISSUE 41), or the chunk program's, not waited for before
    the pass's decode step is dispatched (it is read in that pass's
    fetch, which since ISSUE 55 comes after the NEXT pass's dispatch) —
    the row joins the batch a pass later, its token on the device, and
    a request that its first token ends never decodes.  Streams are the
    oracle's, token for token."""
    eng = _engine(params, cfg)
    if not fused:
        eng._step_chunk = None
    rng = np.random.default_rng(3)
    long_ = rng.integers(0, cfg.vocab_size, 6).tolist()
    plan = [(11, 1), (17, 5), (4, 1), (23, 7)]
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n, _ in plan]
    tracing.clear()
    tracing.enable_tracing()
    try:
        first = eng.submit(long_, max_new=40)
        it = first.stream(timeout=300)
        head = [next(it) for _ in range(3)]           # it is decoding now
        reqs = [eng.submit(p, max_new=m)
                for p, (_, m) in zip(prompts, plan)]
        outs = [r.result(timeout=300) for r in reqs]
        whole = head + list(it)
        st, calls = eng.stats(), list(sampling_calls)
        assert eng._flight is None and not eng._pass.owes
        assert st["active_slots"] == 0
    finally:
        tracing.disable_tracing()
        eng.shutdown()
    assert whole == _ref_tokens(params, cfg, long_, 40)
    for p, o, (_, m) in zip(prompts, outs, plan):
        assert o == _ref_tokens(params, cfg, p, m)
    assert calls == [] and st["tokens_sampled"] == 0
    assert st["tokens_greedy_on_device"] == st["generated_tokens"] \
        == 40 + sum(m for _, m in plan)
    spans = {s["span_id"]: s for s in tracing.get_finished_spans()}
    tracing.clear()
    # every decode step's fetch brought its rows' integers, nothing
    # else ...  (the fetch of a pass comes a pass later, inside the
    # span of the step dispatched meanwhile, or the pass's own where
    # nothing was left to dispatch: it says itself what it read)
    steps = [s for s in spans.values() if s["name"] == "engine.fetch"
             and s["attributes"].get("stepped")]
    assert {spans[s["parent_id"]]["name"] for s in steps} \
        <= {"engine.decode", "engine.pass"}
    def rode(s):          # a chunk ran inside the step: one integer more
        return s["attributes"]["rode"]
    assert steps and all(
        s["attributes"]["bytes"]
        == 4 * (4 + rode(s)) + 4 * s["attributes"]["first_tokens"]
        for s in steps)
    assert sum(map(rode, steps)) == st["chunks_in_step"]
    # ... but for a first token read INSIDE a step's fetch: the step was
    # dispatched behind the chunk before anyone waited for the chunk.
    # The read is that span's own (``first_tokens``), no span inside it.
    # A prompt that ended inside the step owes no read of its own
    if fused:         # the last chunk of every pass that had a chunk
        assert 1 <= st["chunks_in_step"] < st["chunk_passes"]
    else:
        assert st["chunks_in_step"] == 0
        assert sum(s["attributes"]["first_tokens"] for s in steps) >= 1
    assert not [s for s in spans.values() if s["name"] == "engine.fetch"
                and spans.get(s["parent_id"], {}).get("name") == "engine.fetch"]
    # and no chunk waited for its own token while a row decoded (a
    # chunk PROGRAM does wait, before it is launched, for the unread
    # pass's chunk programs to have ended: a fetch of no bytes)
    for s in spans.values():
        if s["name"] == "engine.fetch" and s["attributes"]["bytes"] \
                and spans.get(s["parent_id"], {}).get(
                    "name") == "engine.prefill_chunk":
            chunk = spans[s["parent_id"]]
            assert spans[chunk["parent_id"]]["attributes"]["active"] == 0


def test_row_preempted_between_its_chunk_and_the_step_re_prefills(
        params, cfg):
    """The block hunt of a pass's decode step may take the row whose
    prompt ended in that pass's chunk (the youngest): its first token is
    then neither read nor emitted, it re-prefills and streams exactly."""
    eng = _engine(params, cfg)
    # the pass of two programs (where ONE runs both, the hunt comes
    # before the chunk is packed: tests/test_engine_fused_pass.py)
    eng._step_chunk = None
    rng = np.random.default_rng(5)
    long_ = rng.integers(0, cfg.vocab_size, 6).tolist()
    late = rng.integers(0, cfg.vocab_size, 13).tolist()
    took = []
    sound = eng._paged_decode_iteration

    def hunted(ride=None):
        # what ``_grow_row`` -> ``_take_block`` does when the pool is dry
        if eng._pass.joining and not took:
            row, req = eng._pass.joining[0]
            assert not req.tokens           # nothing emitted yet
            took.append(req)
            eng._drain("preempt")           # as ``_take_block`` does
            eng._preempt_row(row)
        return sound(ride)
    eng._paged_decode_iteration = hunted
    try:
        first = eng.submit(long_, max_new=30)
        it = first.stream(timeout=300)
        head = [next(it) for _ in range(3)]
        victim = eng.submit(late, max_new=6)
        out = victim.result(timeout=300)
        whole = head + list(it)
        st = eng.stats()
        assert eng._flight is None and not eng._pass.owes
        assert st["active_slots"] == 0
    finally:
        del eng._paged_decode_iteration     # the instance's reference cycle
        eng.shutdown()
    assert took == [victim] and victim.preemptions == 1
    assert st["preemptions"] == 1 and st["admissions"] == 3
    assert out == _ref_tokens(params, cfg, late, 6)
    assert whole == _ref_tokens(params, cfg, long_, 30)
    assert st["tokens_greedy_on_device"] == st["generated_tokens"] == 36


# ------------------------------------------------------ the counters

def test_counters_in_stats_and_metrics_snapshot(params, cfg):
    """``tokens_greedy_on_device`` + ``tokens_sampled`` partition the
    generated tokens by where each was chosen; ``fetch_bytes`` sums what
    the loop brought to the host: 4 bytes a row and decode pass, 4 a
    first token."""
    eng = _engine(params, cfg)
    try:
        zero = eng.stats()
        assert zero["tokens_greedy_on_device"] == zero["tokens_sampled"] \
            == zero["fetch_bytes"] == 0
        eng.generate([5, 3, 8], max_new=6, timeout=300)
        greedy = eng.stats()
        assert greedy["tokens_greedy_on_device"] == 6
        assert greedy["tokens_sampled"] == 0
        # 5 decode passes of 4 rows, and the chunk's one token
        assert greedy["decode_iterations"] == 5
        assert greedy["fetch_bytes"] == 5 * 4 * 4 + 4
        eng.generate([5, 3, 8], max_new=6, temperature=0.7, seed=1,
                     timeout=300)
        st = eng.stats()
        assert st["tokens_greedy_on_device"] == 6
        assert st["tokens_sampled"] == 6
        assert st["generated_tokens"] == 12
        # the same integers a pass, and the sampled first token's wait
        # (a sampled decode token is read inside ``engine.sample``)
        assert st["fetch_bytes"] - greedy["fetch_bytes"] == 5 * 4 * 4 + 4
        snap = {name: (kind, series)
                for name, kind, _help, series in metrics_snapshot()}
        key = (("engine", eng.name),)
        for counter in ("tokens_greedy_on_device", "tokens_sampled",
                        "fetch_bytes"):
            kind, series = snap[f"ray_tpu_inference_{counter}_total"]
            assert kind == "counter" and series[key] == st[counter]
    finally:
        eng.shutdown()
