"""The cell ``serve-lfm2-agent4k-r80`` and what it brought: found by name
with no edit, its configuration's widths (the catalog's row; the cut is
depth alone), the window's requests as the mix states them (tenants'
shared tool prompts, a fifth that shares nothing), which requests the
check follows (adopters AND cold ones), its label table on ops' texts,
its bytes functions against the issue's arithmetic, its readers on a
parent's observations (nothing, and no raise) and a CPU rehearsal at a
fixture of its own (``rehearse_lfm2.json``).  ``python -m pytest
chipbench/tests -q``; not part of tier-1; no number here is a device
number.
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

from chipbench import lfm2_bytes, lfm2_trace                   # noqa: E402
from chipbench.readers import load_reader                     # noqa: E402
from chipbench.tests import by_name                           # noqa: E402

CELL = "serve-lfm2-agent4k-r80"
CONFIG = "lfm2-8b-a1b-12L"
NEW = {"short_conv_ms_per_decode.serve": "itl_p95_ms",
       "short_conv_prefill_ms_per_chunk.serve": "ttft_p90_ms",
       "short_conv_prefill_roofline.serve": "ttft_p90_ms",
       "bias_routed_expert_ms_per_decode.serve": "itl_p95_ms",
       "bias_routed_expert_roofline.serve": "itl_p95_ms",
       "state_restored_share.serve": "ttft_p90_ms"}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def test_cell_is_found_by_name_with_its_files():
    bench = load("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert config["file"] == f"chipbench/configs/{CONFIG}.json"
    assert config["reduced"] == ["num_hidden_layers"]
    mix = load("chipbench", "traffic", cell["traffic"] + ".json")
    assert os.path.exists(os.path.join(
        ROOT, "chipbench", "traffic", mix["kind"] + ".py"))
    # by name: the cell may be listed under more, a metric may list more
    by_name.check_listed(bench, CELL, NEW)
    for name in NEW:
        assert load_reader(name).read({}) is None    # a parent: nothing
    assert set(by_name.metrics_of(bench, CELL, "end_to_end")) == {
        "ttft_p90_ms", "itl_p95_ms", "serve_tokens_per_s", "setup_s"}


def test_configuration_is_the_catalog_row_cut_in_depth_alone():
    from tests.test_lfm2_model import CATALOG
    config = load("chipbench", "configs", CONFIG + ".json")
    differs = {k for k, v in CATALOG.items() if config.get(k) != v}
    assert differs == {"num_hidden_layers"} == set(config["reduced"])
    assert config["num_hidden_layers"] == 12
    assert config["layers_held"] == list(range(12))
    from chipbench.traffic.open_loop_http_lfm2 import model_config
    cfg, published, held = model_config(config)
    assert published["layer_types"] == CATALOG["layer_types"][:12]
    assert (cfg.n_short_conv, cfg.n_attention, cfg.dense_layers) == (9, 3, 2)
    assert held == (0, 32) and cfg.vocab_size == 65536
    assert cfg.max_seq == config["engine"]["max_seq"] == 4608
    for key in ("deployment", "assumed", "memory_arithmetic", "engine_note",
                "seeded_values", "selection_bias"):
        assert config[key], key


def test_mix_is_the_issue_s_traffic_and_states_its_cold_share():
    from chipbench.traffic.open_loop_http_lfm2 import adopters, cold_share
    from chipbench.traffic_gen import chat_requests
    mix = load("chipbench", "traffic", "agent4k-r80.json")
    assert mix["shared_heads"] == {"n": 12, "len": 2048, "share": 0.8,
                                   "zipf_a": 1.0}
    assert mix["prompt_len"] == {"lo": 2080, "hi": 4096, "median": 2560,
                                 "sigma": 0.15}
    assert mix["output_len"] == {"lo": 32, "hi": 512, "median": 128,
                                 "sigma": 0.7}
    assert mix["max_total"] == 4608 and mix["order_seed"] == 0
    reqs = chat_requests(mix, 51, 7, 65536)
    window = [r for r in reqs if not r["lead"]]
    assert len(window) == round(mix["rate_per_s"] * 51)
    shared = [r for r in window if r["head"] is not None]
    assert abs(len(shared) / len(window) - 0.8) < 0.01
    for r in shared:
        assert r["prompt"][:2048] == shared[0]["prompt"][:2048] \
            or r["head"] != shared[0]["head"]
    assert all(2080 <= len(r["prompt"]) <= 4096 for r in window)
    share = cold_share(reqs)
    assert share == pytest.approx(mix["cold_request_share"], abs=1e-9)
    assert 0.15 < share < 0.35          # p90 INSIDE the cold group
    adopts = adopters(reqs)
    assert sum(adopts[r["id"]] for r in window) / len(window) > 0.6
    # another seed: the same sizes at the same times
    again = chat_requests(mix, 51, 8, 65536)
    assert [(len(r["prompt"]), r["max_tokens"], r["head"]) for r in again] \
        == [(len(r["prompt"]), r["max_tokens"], r["head"]) for r in reqs]


def test_check_follows_adopters_and_cold_requests():
    from chipbench.traffic.open_loop_http_lfm2 import pick_checked
    done = [{"id": i, "prompt": [0] * (100 + i), "max_tokens": 8}
            for i in range(20)]
    adopts = {i: i % 3 != 0 for i in range(20)}
    picks = pick_checked(done, adopts, 5, 8)
    assert len(picks) == 8 == len({r["id"] for r in picks})
    assert sum(adopts[r["id"]] for r in picks) == 4
    assert picks[0]["id"] == 19 and picks[4]["id"] == 18    # the longest
    assert pick_checked(done, adopts, 5, 8) == picks
    assert pick_checked(done, adopts, 6, 8) != picks
    # a run with no cold request finished: the group is simply empty
    assert all(adopts[r["id"]] for r in pick_checked(
        [r for r in done if adopts[r["id"]]], adopts, 5, 8))


OPS = {
    "%fusion.1 = bf16[48,6144]{1,0} fusion(%p, %params__layers___0___mixer"
    "____in_proj__.1)": "short_conv_proj",
    "%convolution.5 = bf16[1072,6144]{1,0} convolution(%a, %b)":
        "short_conv_proj",
    "%fusion.7 = bf16[48,2048]{1,0} fusion(bf16[2048,6144]{1,0} %w)":
        "short_conv_proj",
    "%fusion.9 = bf16[9,48,2,2048]{3,2,1,0} fusion(%x)": "short_conv_taps",
    "%scatter.1 = bf16[8193,36864]{1,0} scatter(%snap, %ids, %new)":
        "short_conv_taps",
    "%fusion.2 = bf16[48,2048] fusion(%params__layers___3___mixer____conv_"
    "w__.1)": "short_conv_taps",
    "%fusion.3 = bf16[48,3072] fusion(%params__layers___2___mixer____wqkv"
    "__.1)": "attention_proj",
    "%fusion.4 = bf16[48,2048] fusion(bf16[2048,2048]{1,0} %copy.3)":
        "square_proj",
    "%fusion.5 = bf16[48,2048] fusion(%params__layers___2___mixer____wo__"
    ".1)": "attention_proj",
    "%gmm.3 = bf16[256,3584] custom-call(%x, bf16[32,2048,3584]{2,1,0} %w"
    ", %sizes), custom_call_target=\"tpu_custom_call\"": "routed_experts",
    "%fusion.6 = f32[48,32] fusion(%params__layers___4___ffn____router__."
    "1)": "routed_experts",
    "%fusion.8 = s32[192] fusion(%iota)": "routed_experts",
    "%fusion.10 = bf16[48,14336] fusion(bf16[2048,14336]{1,0} %w)":
        "dense_mlp",
    "%paged.1 = bf16[48,32,64] custom-call(bf16[24579,64,512]{2,1,0} %k, "
    "%t), custom_call_target=\"tpu_custom_call\"": "decode_attention",
    "%dus.1 = bf16[24579,64,512]{2,1,0} dynamic-update-slice(%k, %new)":
        "kv_pool_ops",
    "%fusion.11 = f32[48,65536] fusion(%h, %wte)": "other",
}


def test_label_table_on_op_texts():
    from chipbench.traffic.open_loop_http_lfm2 import model_config
    config = load("chipbench", "configs", CONFIG + ".json")
    marks = lfm2_trace.marks_of(config["engine"], model_config(config)[0])
    assert marks["kv_pool"][0] == "bf16[24579,64,512]"
    for text, label in OPS.items():
        assert lfm2_trace.label_of(text, marks) == label, text


def _obs():
    config = load("chipbench", "configs", CONFIG + ".json")
    published = {**config, "layer_types": config["layer_types"][:12]}
    step = {"short_conv_proj": 0.010, "short_conv_taps": 0.002,
            "square_proj": 0.004, "routed_experts": 0.300}
    counters = {"decode_iterations": 100, "occupancy_sum": 100 * 16 / 48,
                "expert_touched_held_decode": 100 * 10 * 28,
                "chunk_passes": 20, "prefill_tokens": 20 * 800,
                "admissions": 40, "state_snapshots_restored": 30}
    return {"published": published, "peaks": PEAKS, "max_slots": 48,
            "conv_layers": 9, "counters": counters,
            "scoped": {"jit_step": {"runs": 50, "label_seconds": step},
                       "jit_step_chunk": {"runs": 10, "label_seconds": {
                           "short_conv_proj": 0.030, "square_proj": 0.004}}}}


def test_readers_on_made_observations():
    obs = _obs()
    ms = load_reader("short_conv_ms_per_decode.serve").read(obs)
    assert ms == pytest.approx(1e3 * (0.012 + 0.75 * 0.004) / 50)
    # (its one-token bytes are stated, and no share is read from them:
    # ``lfm2_bytes.short_conv_step_work`` says why)
    flops, bytes_ = lfm2_bytes.short_conv_step_work(obs["published"], 16)
    assert bytes_ == pytest.approx(9 * (16_783_360 * 2 + 2 * 16 * 8192))
    chunk = load_reader("short_conv_prefill_ms_per_chunk.serve").read(obs)
    assert chunk == pytest.approx(1e3 * (0.030 + 0.75 * 0.004) / 10)
    flops, bytes_ = lfm2_bytes.short_conv_prefill_work(obs["published"], 800)
    assert flops == pytest.approx(9 * 2 * 800 * 16_783_360)
    assert flops / 197e12 > bytes_ / 819e9            # compute bound
    assert load_reader("short_conv_prefill_roofline.serve").read(obs) \
        == pytest.approx(100 * flops / 197e12 / (chunk / 1e3))
    ms = load_reader("bias_routed_expert_ms_per_decode.serve").read(obs)
    assert ms == pytest.approx(6.0)
    need = lfm2_bytes.routed_expert_bytes_per_decode(obs["published"], 280)
    assert need == 2 * (280 * 3 * 2048 * 1792 + 10 * 2048 * 32) + 4 * 320
    assert load_reader("bias_routed_expert_roofline.serve").read(obs) \
        == pytest.approx(100 * need / 819e9 / 6e-3)
    assert load_reader("state_restored_share.serve").read(obs) == 75.0
    # the traced seconds' counters take the window's place
    obs["traced_counters"] = {**obs["counters"],
                              "expert_touched_held_decode": 100 * 10 * 14}
    assert load_reader("bias_routed_expert_roofline.serve").read(obs) \
        < 0.6 * 100 * need / 819e9 / 6e-3


def test_readers_find_nothing_on_a_parent_s_observations():
    obs = {"counters": {"decode_iterations": 10, "admissions": 4},
           "scoped": {"jit_step": {"runs": 3, "label_seconds": {
               "other": 0.1}}}, "peaks": PEAKS, "max_slots": 48}
    for name in NEW:
        assert load_reader(name).read(obs) is None, name


def test_rehearsal_runs_the_cell_s_code_on_the_cpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", CELL, "--seed", "3000052001", "--seconds", "3",
         "--trace", "1", "--rehearse"], capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["metrics"] == {}
    assert line["rehearsal_verdict_not_a_result"] is True
    assert line["failed"] == 0
    c = line["notes"]["counters"]
    assert c["state_snapshots_restored"] > 0 and c["prefix_hit_tokens"] > 0
    assert line["notes"]["checked_adopted"] >= 1
    assert line["notes"]["checked_cold"] >= 1
    got = line["rehearsal_metrics_not_device_numbers"]
    assert "state_restored_share.serve" in got
    assert "prefix_hit_rate.serve" in got
