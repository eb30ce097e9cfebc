"""The cell ``serve-granite-h-chat2k-r50`` (until PR 59, at a stale
rate, ``serve-granite-h-chat2k-r80``) and what it brought: found by
name with no edit, its readers on a recorded excerpt, its bytes
functions, and a CPU rehearsal at a fixture of its own
(``rehearse_recurrent.json``).  ``python -m pytest chipbench/tests -q``;
not part of tier-1; no number here is a device number.
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

from chipbench import hybrid_bytes, scoped_trace        # noqa: E402
from chipbench.readers import load_reader               # noqa: E402
from chipbench.tests import by_name                     # noqa: E402

CELL = "serve-granite-h-chat2k-r50"
NEW = ("expert_ms_per_decode.serve", "ssm_ms_per_decode.serve",
       "expert_roofline_share.serve", "ssm_update_roofline_share.serve",
       "expert_load_max_over_mean.serve", "state_rows_share.serve")


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def test_cell_is_found_by_name_with_its_files():
    bench = load("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load(*entry["file"].split("/"))
    mix = load("chipbench", "traffic", cell["traffic"] + ".json")
    assert os.path.exists(os.path.join(ROOT, "chipbench", "traffic",
                                       mix["kind"] + ".py"))
    assert os.path.exists(os.path.join(ROOT, cfg["reference"]))
    assert cfg["source"] == entry["source"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == sorted(
        cfg["changed"]) == sorted(k for k in cfg["published"]
                                  if k != "parameters")
    # the cell reports the three serving metrics and set-up, every
    # .serve per-layer metric but the prefix cache's, and the six new
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"ttft_p90_ms", "itl_p95_ms", "serve_tokens_per_s",
                   "setup_s"}
    # by name: the cell may be listed under more, a metric may list more
    by_name.check_listed(bench, CELL, NEW, moves="itl_p95_ms")
    assert "prefix_hit_rate.serve" not in by_name.metrics_of(bench, CELL)
    for name in NEW:
        assert callable(load_reader(name).read)
    # traffic as the issue gives it; enough requests for a p90
    assert mix["prompt_len"] == {"lo": 32, "hi": 2048, "median": 256,
                                 "sigma": 0.9}
    assert mix["output_len"] == {"lo": 32, "hi": 256, "median": 96,
                                 "sigma": 0.7}
    assert mix["max_total"] == 2304 == cfg["engine"]["max_seq"]
    assert mix["shared_heads"]["n"] == 0 and mix["order_seed"] == 0
    assert (mix["lead_s"], mix["drain_s"], mix["trace_s"],
            mix["checked_requests"]) == (30, 70, 4.0, 4)
    assert round(mix["rate_per_s"] * bench["run_seconds"]) >= 100


def test_configuration_holds_the_published_widths_and_the_share():
    from chipbench.traffic.open_loop_http_recurrent import model_config
    config = load("chipbench", "configs", "granite-4.0-h-small-10L-e36.json")
    cfg, published, held = model_config(config)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        4096, 32, 8, 128)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.conv_width,
            cfg.ssm_chunk) == (128, 64, 128, 4, 256)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.expert_width,
            cfg.shared_width) == (72, 10, 768, 1536)
    assert cfg.layer_types == ("mamba",) * 5 + ("attention",) + \
        ("mamba",) * 4
    assert held == cfg.experts_held == (0, 36) and cfg.vocab_size == 50176
    assert published["num_local_experts"] == 72
    assert config["engine"]["prefill_chunk"] == config["mamba_chunk_size"]
    assert cfg.kv_geometry == (1, 8, 128)
    assert cfg.state_geometry == (9, (3, 8448), (8192, 128))


def test_bytes_functions_by_hand():
    config = load("chipbench", "configs", "granite-4.0-h-small-10L-e36.json")
    pub = {**config, "num_local_experts": 72}
    # every held expert touched in every layer (360 a pass): 10 layers x
    # (36 experts x 9,437,184 + shared 18,874,368 + router 294,912) x 2
    # bytes
    full = hybrid_bytes.expert_bytes_per_decode(pub, 360)
    assert full == pytest.approx(10 * 2 * (36 * 9_437_184 + 18_874_368
                                           + 294_912))
    # one row touches 10 of 72 experts, half of them held on average:
    # 50 a pass over the 10 layers
    one = hybrid_bytes.expert_bytes_per_decode(pub, 50)
    assert one == pytest.approx(10 * 2 * (5 * 9_437_184 + 18_874_368
                                          + 294_912))
    # the touched experts are the engine's count, not an estimate from
    # the rows: the mean of a concave curve lies under the curve at the
    # mean, so the old estimate at the MEAN rows read over 100 %
    counted = {"counters": {"decode_iterations": 10,
                            "expert_touched_held_decode": 1370}}
    assert hybrid_bytes.touched_per_decode(counted) == 137.0
    assert hybrid_bytes.touched_per_decode({"counters": {
        "decode_iterations": 10}}) is None
    assert hybrid_bytes.touched_per_decode({"counters": {}}) is None
    # a row: 9 layers x (8192 x 128 x 4 + 3 x 8448 x 2), read + written
    assert hybrid_bytes.ssm_state_bytes_per_decode(pub, 1) == 2 * 9 * (
        4_194_304 + 50_688)
    obs = {"counters": {"decode_iterations": 10, "row_steps": 250}}
    assert hybrid_bytes.mean_active_rows(obs) == 25.0
    assert hybrid_bytes.mean_active_rows({"counters": {}}) is None


def test_scoped_reduction_on_hand_made_rows():
    ops, mods, dev = "XLA Ops", "XLA Modules", "/device:TPU:0"
    rows = [[dev, mods, "jit_step", 0, 100], [dev, mods, "jit_step", 200, 100],
            [dev, mods, "jit_chunk_fn", 400, 100],
            [dev, ops, "other", 0, 100],            # a while: encloses two
            [dev, ops, "routed_experts", 10, 30], [dev, ops, "mixer_ssm", 50, 20],
            [dev, ops, "routed_experts", 210, 50],
            [dev, ops, "shared_expert", 410, 40]]
    s = scoped_trace.summarize(rows)
    assert s["jit_step"]["runs"] == 2 and s["jit_chunk_fn"]["runs"] == 1
    step = s["jit_step"]["label_seconds"]
    assert step["routed_experts"] == pytest.approx(80e-9)
    assert step["mixer_ssm"] == pytest.approx(20e-9)
    assert step["other"] == pytest.approx(50e-9)    # 100 minus its children
    obs = {"scoped": s}
    assert scoped_trace.ms_per_run(obs, "jit_step", ("routed_experts",)) \
        == pytest.approx(40e-6)
    assert scoped_trace.ms_per_run(obs, "jit_step", ("nothing",)) is None
    assert scoped_trace.ms_per_run({}, "jit_step", ("mixer_ssm",)) is None


def test_labels_from_an_ops_text():
    lab = scoped_trace.label_of
    assert lab("%ragged-dot-none.3 = bf16[640,1536]{1,0} custom-call(s32[1] "
               "%g, bf16[640,4096] %x)") == "routed_experts"
    assert lab("%fusion.9 = bf16[64,3072]{1,0} fusion(bf16[64,4096] %x, "
               "bf16[4096,3072]{1,0} %params__layers___9___ffn____shared_in"
               "__.1)") == "shared_expert"
    assert lab("%convolution_bitcast_fusion.2 = bf16[64,1,16768]{2,0,1} "
               "fusion(bf16[4096,16768]{1,0} %params__layers___2___mixer____"
               "in_proj__.1, bf16[64,4096] %x)") == "mixer_ssm_proj"
    assert lab("%f = f32[9,64,8192,128]{3,2,1,0} fusion(%x)") == "mixer_ssm"
    assert lab("%m = f32[64,8192]{1,0} fusion(f32[64,8192,128] %s)") \
        == "mixer_ssm"
    assert lab("%c = bf16[64,3,8448]{2,1,0} fusion(%a)") == "mixer_ssm"
    # the per-assignment arrays are told by their leading dim, given
    assert lab("%sort.1 = (s32[640]{0}, s32[640]{0}) sort(%a, %b)",
               (640, 2560)) == "routed_experts"
    assert lab("%sort.1 = (s32[640]{0}, s32[640]{0}) sort(%a, %b)") == "other"
    assert lab("%x = bf16[64,4096]{1,0} fusion(%a)") == "other"
    # and every text kept from the chip says what its label says
    rec = load("chipbench", "tests", "recorded_scoped_trace.json")
    for label, text in rec["texts"].items():
        assert lab(text, (640, 2560)) == label


def test_new_readers_on_the_recorded_excerpt():
    """Rows recorded on the chip (one decode program of the cell, PR 29)
    through the second stage, and the six readers on them."""
    rec = load("chipbench", "tests", "recorded_scoped_trace.json")
    s = scoped_trace.summarize(rec["rows"])
    assert s["jit_step"]["runs"] == rec["expect"]["runs"]
    labels = s["jit_step"]["label_seconds"]
    assert set(labels) >= {"routed_experts", "mixer_ssm"}
    config = load("chipbench", "configs", "granite-4.0-h-small-10L-e36.json")
    obs = {"scoped": s, "published": {**config, "num_local_experts": 72},
           "held": (0, 36), "peaks": load("chipbench", "peaks.json")[
               "TPU v5 lite"], "max_slots": 64, "state_rows_mean": 16.0,
           "counters": {"decode_iterations": 100, "row_steps": 2000,
                        "expert_assignments_held": 36_000,
                        "expert_touched_held_decode": 33_000,
                        "expert_load_max": 1_500}}
    got = {name: load_reader(name).read(obs) for name in NEW}
    assert got["expert_ms_per_decode.serve"] == pytest.approx(
        rec["expect"]["expert_ms"], rel=1e-3)
    assert got["ssm_ms_per_decode.serve"] == pytest.approx(
        rec["expect"]["ssm_ms"], rel=1e-3)
    assert 0 < got["expert_roofline_share.serve"] <= 100
    # 330 of the 360 held experts touched a pass, their bytes at the
    # peak bandwidth over the recorded time
    assert got["expert_roofline_share.serve"] == pytest.approx(
        100 * hybrid_bytes.expert_bytes_per_decode(obs["published"], 330)
        / obs["peaks"]["hbm_bytes_per_s"]
        / (rec["expect"]["expert_ms"] / 1e3), rel=1e-3)
    assert 0 < got["ssm_update_roofline_share.serve"] <= 105
    # the work is that of the passes whose time it is: where the kind
    # took the traced seconds' own counters, those are read (the same
    # cycle's same 4 s every run: not the window's mean rows)
    thin = {**obs, "traced_counters": {
        "decode_iterations": 10, "row_steps": 100,
        "expert_touched_held_decode": 1_650}}
    for name in ("expert_roofline_share.serve",
                 "ssm_update_roofline_share.serve"):
        assert load_reader(name).read(thin) == pytest.approx(
            got[name] / 2, rel=0.2)
        assert load_reader(name).read(thin) < got[name]
    assert got["expert_load_max_over_mean.serve"] == pytest.approx(1.5)
    assert got["state_rows_share.serve"] == 25.0
    # a program without the spans or counters (a parent commit): nothing
    for name in NEW:
        assert load_reader(name).read({"counters": {}, "window_s": 1.0}) \
            is None


def test_rehearsal_of_the_cell_at_its_own_fixture(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "3",
         "--trace", "1", "--rehearse"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["metrics"] == {}
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    assert line["notes"]["worst_margin"] <= line["notes"]["tie_tolerance"]
    assert line["notes"]["compiles_in_window"] == 0
    assert line["rehearsal_verdict_not_a_result"] is True, line["checks"]
    assert set(line["notes"]["setup_stamps"]) >= {
        "import_s", "weights_s", "programs_s", "warmup_s", "lead_in_s",
        "reference_check_s"}
    seen = line["rehearsal_metrics_not_device_numbers"]
    assert "expert_load_max_over_mean.serve" in seen
    assert "state_rows_share.serve" in seen
    assert "prefix_hit_rate.serve" not in seen
    c = line["notes"]["counters"]
    assert 0 < c["expert_assignments_held"] < c["expert_assignments_total"]
