"""Every cell of ``BENCHMARK.json`` finds its files; the retired cell is
gone; what decides ``correct`` fails when it must: the control (the
reference in the nearest lower precision, in the program's place) and a
timed path broken underneath a rehearsed run.  CPU only; no number here
is a device number.  ``python -m pytest chipbench/tests -q``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import traffic_gen                         # noqa: E402
from chipbench.readers import load_reader                 # noqa: E402

RETIRED = "serve-xl-chat-r80"
NEW = "serve-xl-chat-r80-v2"


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


BENCH = load("BENCHMARK.json")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_finds_its_files_readers_and_traffic(cell):
    w = next(x for x in BENCH["workloads"] if x["name"] == cell)
    entry = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    cfg = load(*entry["file"].split("/"))
    mix = load("chipbench", "traffic", w["traffic"] + ".json")
    assert os.path.exists(os.path.join(ROOT, "chipbench", "traffic",
                                       mix["kind"] + ".py"))
    assert os.path.exists(os.path.join(ROOT, cfg["reference"]))
    assert cfg["source"] == entry["source"]
    assert isinstance(cfg["reduced"], list)
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    assert all(key in cfg for key in cfg["reduced"])
    e2e = [m for m in BENCH["end_to_end"]
           if cell in m.get("workloads", [cell])]
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    per_layer = [m for m in BENCH["per_layer"]
                 if cell in m.get("workloads", [cell])]
    assert per_layer
    for m in per_layer:
        assert callable(load_reader(m["name"]).read)
        assert m["moves"] in {e["name"] for e in e2e}
    if "rate_per_s" in mix:       # a serving cell: the window's requests
        vocab = cfg.get("vocab_size_padded", cfg["vocab_size"])
        reqs = [r for r in traffic_gen.chat_requests(
            mix, BENCH["run_seconds"], 3_000_000_021, vocab)
            if not r["lead"]]
        assert len(reqs) == round(mix["rate_per_s"] * BENCH["run_seconds"])
        assert all(len(r["prompt"]) + r["max_tokens"] <= mix["max_total"]
                   for r in reqs)
        assert all(0 <= t < vocab for r in reqs for t in r["prompt"])


# a cell re-based at a newly swept knee: (the retired name and mix, the
# new name, config and mix, the per-layer metrics the old cell had and
# the new one still reports, the mix keys that carried over)
REBASED = {
    # PR 37
    NEW: (RETIRED, "chat-r80", "gpt2-xl", "chat-r80-v2", {
        "device_idle_share.serve", "decode_step_ms.serve",
        "batch_occupancy.serve", "prefix_hit_rate.serve",
        "queue_wait_p90_ms.serve", "prefill_p90_ms.serve",
        "front_overhead_p90_ms.serve", "decode_pass_ms.serve",
        "prefill_pass_share.serve", "engine_host_ms_per_pass.serve",
        "decode_program_ms.serve", "chunk_program_ms.serve"}, {
        "prompt_len": {"lo": 32, "hi": 768, "median": 200, "sigma": 0.8},
        "output_len": {"lo": 16, "hi": 128, "median": 64, "sigma": 0.7},
        "shared_heads": {"n": 4, "len": 128, "share": 0.5, "zipf_a": 1.0},
        "max_total": 1024, "order_seed": 0, "checked_requests": 4,
        "trace_s": 4.0}),
    # PR 59: the rate alone moved, off the edge the p95's rank lay on
    "serve-granite-h-chat2k-r50": (
        "serve-granite-h-chat2k-r80", "chat2k-r80",
        "granite-4.0-h-small-10L-e36", "chat2k-r50", {
            "device_idle_share.serve", "decode_step_ms.serve",
            "batch_occupancy.serve", "decode_program_ms.serve",
            "expert_ms_per_decode.serve", "ssm_ms_per_decode.serve",
            "expert_roofline_share.serve",
            "ssm_update_roofline_share.serve",
            "expert_load_max_over_mean.serve", "state_rows_share.serve",
            "step_chunk_pass_ms.serve", "step_pass_ms.serve",
            "engine_gap_p95_ms.serve", "front_gap_p95_ms.serve",
            "chunk_key_blocks_per_chunk.serve",
            "step_chunk_program_ms.serve", "chunk_pass_gap_share.serve"}, {
            "kind": "open_loop_http_recurrent",
            "prompt_len": {"lo": 32, "hi": 2048, "median": 256,
                           "sigma": 0.9},
            "output_len": {"lo": 32, "hi": 256, "median": 96,
                           "sigma": 0.7},
            "shared_heads": {"n": 0, "len": 0, "share": 0.0,
                             "zipf_a": 1.0},
            "max_total": 2304, "lead_s": 30, "drain_s": 70,
            "order_seed": 0, "checked_requests": 4, "trace_s": 4.0,
            "tie_tolerance": 0.003}),
}


@pytest.mark.parametrize("new", sorted(REBASED))
def test_retired_cell_is_gone_and_the_new_one_reports_what_it_did(new):
    retired, old_mix, config, traffic, kept, carried = REBASED[new]
    assert retired not in {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert retired not in m.get("workloads", [])
    assert not os.path.exists(os.path.join(ROOT, "chipbench", "traffic",
                                           old_mix + ".json"))
    cell = next(w for w in BENCH["workloads"] if w["name"] == new)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        config, traffic, 1)
    assert [w["config"] for w in BENCH["workloads"]].count(config) == 1
    names = {m["name"] for m in BENCH["per_layer"]
             if new in m.get("workloads", [])}
    assert kept <= names, kept - names
    assert {m["name"] for m in BENCH["end_to_end"]
            if new in m.get("workloads", [new])} == {
        "ttft_p90_ms", "itl_p95_ms", "serve_tokens_per_s", "setup_s"}
    mix = load("chipbench", "traffic", traffic + ".json")
    assert round(mix["rate_per_s"] * BENCH["run_seconds"]) >= 120
    # the mix as the retired file had it, but for the rate and its note
    assert {k: mix[k] for k in carried} == carried
    assert mix["rate_note"] and str(mix["rate_per_s"]) in cell["why"]


def test_bounds_follow_the_contract():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
    assert next(m for m in BENCH["end_to_end"]
                if m["name"] == "setup_s")["bound"] == 0.1


def test_sample_holds_the_longest_and_is_drawn_from_the_seed():
    from chipbench.traffic.open_loop_http import pick_checked
    done = [{"id": i, "prompt": [0] * (10 + (i * 7) % 50),
             "max_tokens": 5 + i % 3} for i in range(40)]
    a, b = pick_checked(done, 5, 4), pick_checked(done, 6, 4)
    longest = max(done, key=lambda r: len(r["prompt"]) + r["max_tokens"])
    assert len(a) == len(b) == 4 and a[0] is longest and b[0] is longest
    assert len({r["id"] for r in a}) == 4
    assert [r["id"] for r in a] == [r["id"] for r in pick_checked(done, 5, 4)]
    assert [r["id"] for r in a] != [r["id"] for r in b]
    assert pick_checked([], 5, 4) == [] and len(pick_checked(done[:2], 5, 4)) == 2


def test_verdict_holds_every_number_to_its_limit():
    from chipbench.traffic.open_loop_http import verdict
    ok, checks = verdict(0, 0, 0.01, 0.125, 4, True)
    assert ok and checks["worst_margin"] == {"value": 0.01, "limit": 0.125}
    assert not verdict(1, 0, 0.01, 0.125, 4, True)[0]      # a failed request
    assert not verdict(0, 1, 0.01, 0.125, 4, True)[0]      # a compile
    assert not verdict(0, 0, 0.2, 0.125, 4, True)[0]       # a wrong token
    assert not verdict(0, 0, 0.01, 0.125, 0, True)[0]      # nothing compared
    assert not verdict(0, 0, 0.01, 0.125, 4, False)[0]     # nothing measured


# ------------------------------------------------------------ the control

def test_control_reference_in_float8_reads_apart_from_the_stated_precision():
    """The control of the GPT serving check at a size a test can hold:
    the reference with float8 e4m3 inputs to every product, in the
    program's place, judged as a served stream is.  It has to read at
    least three times what the stated precision (bfloat16) reads, so a
    limit between them holds one and fails the other."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.model import gpt_config, make_params
    from chipbench.reference import gpt2 as ref
    from chipbench.traffic.open_loop_http import verdict

    # 1,024 positions over a vocabulary of 8,192: enough near-ties for
    # 8-bit products to pick a token the float32 reference puts second
    config = {**load("chipbench", "configs", "gpt2-xl.json"),
              **load("chipbench", "tests", "rehearse.json")["config"],
              "vocab_size_padded": 8192, "n_layer": 4}
    cfg = gpt_config(config)
    params = make_params(cfg, 7, config["weights_served_as"])
    assert params["layers"]["wqkv"].dtype == jnp.bfloat16
    assert params["layers"]["ln1_scale"].dtype == jnp.float32
    tokens = np.random.default_rng(3).integers(
        0, cfg.vocab_size, (8, cfg.max_seq), dtype=np.int32)

    def logits(dt=None):
        return np.asarray(ref.forward(params, tokens, config["n_head"],
                                      dt)).reshape(-1, cfg.vocab_size)
    full = logits()

    def reading(dt):
        pick = logits(dt).argmax(-1)
        return float((full.max(-1) - full[np.arange(len(pick)), pick]).max())
    stated, control = reading(jnp.bfloat16), reading(jnp.float8_e4m3fn)
    assert control >= 3 * stated and control > 0.01
    limit = max(stated, control / 4)
    assert verdict(0, 0, stated, limit, 4, True)[0]
    assert not verdict(0, 0, control, limit, 4, True)[0]


# ------------------------------------- a timed path broken underneath

BROKEN = """
import runpy, sys
import numpy as np
from ray_tpu.inference import engine
calls = [0]
def altered(tokens, vocab):
    calls[0] += 1
    out = np.array(tokens)
    if calls[0] % 3 == 0:            # every third decode pass
        out = (out + vocab // 2) % vocab
    return out
{patch}
sys.argv = ["run.py"] + sys.argv[1:]
runpy.run_path({run_py!r}, run_name="__main__")
"""

PATCHES = {
    NEW: """
sound = engine._KVOnly.greedy
engine._KVOnly.greedy = staticmethod(
    lambda eng, logits: altered(sound(eng, logits), logits.shape[-1]))
""",
    "serve-granite-h-chat2k-r50": """
sound = engine._KVAndState.greedy
engine._KVAndState.greedy = staticmethod(
    lambda eng, logits: altered(sound(eng, logits), logits.shape[-1]))
""",
}


@pytest.mark.parametrize("cell", sorted(PATCHES))
def test_a_token_altered_where_it_is_produced_comes_out_not_correct(
        cell, tmp_path):
    """The rest of a run after the look for a chip (``--rehearse``), with
    the engine's greedy sampling altered every third pass: the served
    streams are whole and in time, and only the reference can tell."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    for name in ("RAY_TPU_TRACING", "RAY_TPU_TRACE_DIR", "XLA_FLAGS"):
        env.pop(name, None)
    code = BROKEN.format(patch=PATCHES[cell], run_py=os.path.join(
        ROOT, "chipbench", "run.py"))
    p = subprocess.run(
        [sys.executable, "-c", code, "--workload", cell, "--seed",
         "3000000023", "--seconds", "3", "--trace", "0", "--rehearse"],
        cwd=ROOT, env={**env, "PYTHONPATH": ROOT}, capture_output=True,
        text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert line["failed"] == 0
    assert line["rehearsal_verdict_not_a_result"] is False
    worst = line["checks"]["worst_margin"]
    assert worst["value"] > worst["limit"]
    assert line["checks"]["compiles_in_window"]["value"] == 0
    # the same numbers, each beside its limit, end the standard error
    assert "check worst_margin: value" in p.stderr.splitlines()[-4]
