"""The hybrid family through the engine, in both published layouts
(``fam``, from tests/test_hybrid_model.py, which holds the model to its
references): continuous batching, a first token behind a running decode,
preemption, what a recurrent state refuses, the counters, the
deployment, sampled rows beside greedy ones.
"""

import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from ray_tpu.inference import (EngineConfig, InferenceEngine,
                               SpeculationUnsupported, metrics_snapshot)
from ray_tpu.models import hybrid
from test_hybrid_model import (_ref_logits, fam, params,  # noqa: F401
                               params_n)

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
    assert eng._flight is None and not eng._pass.owes
    assert eng.stats()["active_slots"] == 0
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


# ------------------------------ a chunk inside the decode step (ISSUE 46)

def _serve_behind(fam, fused, plan, seed, long_new=40):
    """One long answer, then the prompts of ``plan`` (length, max_new)
    while it streams -> (its tokens, theirs, stats, the prompts, how
    often each of the pass's three programs was launched)."""
    eng = _engine(fam, max_slots=4, n_blocks=20)
    assert eng._step_chunk is not None
    if not fused:
        eng._step_chunk = None
    calls = {}
    for name in ("_step", "_chunk", "_step_chunk"):
        def run(*args, program=getattr(eng, name), name=name):
            calls[name] = calls.get(name, 0) + 1
            return program(*args)
        if getattr(eng, name) is not None:
            setattr(eng, name, run)
    rng = np.random.default_rng(seed)
    long_ = rng.integers(0, 256, 6).tolist()
    prompts = [rng.integers(0, 256, n).tolist() for n, _ in plan]
    try:
        first = eng.submit(long_, max_new=long_new)
        it = first.stream(timeout=300)
        head = [next(it) for _ in range(2)]           # it is decoding now
        reqs = [eng.submit(p, max_new=m) for p, (_, m) in zip(prompts, plan)]
        outs = [r.result(timeout=300) for r in reqs]
        assert not first.done                         # ... and still is
        whole = head + list(it)
    finally:
        eng.shutdown()
    # (read once the loop has ended: a pass books its row steps after
    # it has handed its tokens out)
    return whole, outs, eng.stats(), [long_] + prompts, calls


@pytest.mark.parametrize("fused", [True, False],
                         ids=["one_program", "two_programs"])
def test_prompts_behind_decoding_rows_stream_the_reference(fam, fused):
    """Prompts of several chunks (partial last ones, one a whole number
    of chunks, one whose first token ends it) arrive behind a decoding
    row and then decode beside it: every stream is the reference's
    whether the pass's last chunk rides the step or not."""
    plan = [(19, 6), (11, 1), (16, 3), (3, 5), (27, 4)]
    whole, outs, st, prompts, calls = _serve_behind(fam, fused, plan, 46)
    for p, o, m in zip(prompts, [whole] + outs, [40] + [m for _, m in plan]):
        assert len(o) == m and _margins(fam, p, o).max() <= fam.atol
    rode = calls.get("_step_chunk", 0)
    assert st["chunks_in_step"] == rode and (rode >= 5) == fused
    assert st["chunk_passes"] == calls["_chunk"] + rode
    assert st["decode_iterations"] == calls["_step"] + rode
    tokens = sum(len(p) + len(o) - 1 for p, o in zip(prompts, [whole] + outs))
    assert st["expert_assignments_total"] == st["expert_assignments_held"] \
        == tokens * fam.top_k * fam.expert_layers


def test_a_fused_pass_books_the_chunk_s_experts_to_the_chunk(fam):
    """What the roofline readers divide by keeps its meaning: with ONE
    row decoding (the prompts behind it end with their first token),
    every decode pass touches that row's top-k experts a layer and no
    more, however many experts the chunk that rode it touched; and every
    counter is the two-program engine's."""
    plan = [(19, 1), (9, 1), (24, 1)]
    runs = {fused: _serve_behind(fam, fused, plan, 7, long_new=30)
            for fused in (True, False)}
    (whole, outs, st, _, calls), (whole2, outs2, st2, _, calls2) = \
        runs[True], runs[False]
    assert whole == whole2 and outs == outs2
    assert calls["_step_chunk"] >= 4 and "_step_chunk" not in calls2
    assert st["chunks_in_step"] == calls["_step_chunk"]
    assert st2["chunks_in_step"] == 0
    for key in ("row_steps", "decode_iterations", "chunk_passes",
                "expert_touched_held_decode", "expert_touched_held",
                "expert_assignments_held", "expert_assignments_total",
                "expert_load_max", "generated_tokens"):
        assert st[key] == st2[key], key
    assert st["row_steps"] == st["decode_iterations"] == 29
    assert st["expert_touched_held_decode"] \
        == 29 * fam.top_k * fam.expert_layers
    assert st["expert_touched_held"] > st["expert_touched_held_decode"] + \
        3 * fam.expert_layers * fam.top_k


@pytest.mark.parametrize(
    "kw", [dict(dense_layers=3, dense_width=32),
           dict(experts_in_every_layer=False)],
    ids=["dense_mlp", "mixers_alone"])
def test_a_model_with_no_experts_sublayer_rides_too(kw):
    """The fused program is derived from the sublayer kinds, and a
    layout of mixers with the dense MLP, or of mixers alone, has them
    all: its load is zeros in the places the engine reads.  Streams are
    the two-program engine's and the full forward's, and no expert is
    ever counted."""
    cfg = hybrid.HybridConfig.tiny(**kw)
    assert hybrid.EXPERTS not in {kind for _, kind in cfg.sublayers}
    model = SimpleNamespace(
        cfg=cfg, params=hybrid.init_params(cfg, jax.random.PRNGKey(2)))
    plan = [(19, 4), (11, 1), (16, 3)]
    (whole, outs, st, prompts, calls), (whole2, outs2, st2, _, calls2) = (
        _serve_behind(model, fused, plan, 5, long_new=30)
        for fused in (True, False))
    assert whole == whole2 and outs == outs2
    assert st["chunks_in_step"] == calls["_step_chunk"] >= 3
    assert st2["chunks_in_step"] == 0 and "_step_chunk" not in calls2
    forward = jax.jit(lambda t: hybrid.forward(model.params, t, cfg))
    for p, o in zip(prompts, [whole] + outs):
        seq = np.zeros((1, 64), np.int32)   # causal: the padding is unseen
        seq[0, :len(p) + len(o)] = p + o
        logits = np.asarray(forward(seq))[0, len(p) - 1:len(p) + len(o) - 1]
        assert (logits.max(-1) - logits[np.arange(len(o)), o]).max() <= 1e-5
    for key in ("row_steps", "decode_iterations", "chunk_passes",
                "generated_tokens"):
        assert st[key] == st2[key], key
    for key in ("expert_touched_held_decode", "expert_touched_held",
                "expert_assignments_held", "expert_assignments_total",
                "expert_load_max"):
        assert st[key] == st2[key] == 0, key
