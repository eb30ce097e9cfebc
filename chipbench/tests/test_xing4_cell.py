"""The cell ``serve-xing4-code4k`` and what it brought: found by name
with no edit, its configuration's published widths and cut, the window's
requests as the mix states them (cold, unshared), its label table, its
bytes functions by hand, and a CPU rehearsal at a fixture of its own
(``rehearse_xing4.json``) — sound, and with a token altered where it is
produced; the control at a size a test can hold.  ``python -m pytest
chipbench/tests -q``; not part of tier-1; no number here is a device
number.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import traffic_gen, xing4_bytes, xing4_trace   # noqa: E402
from chipbench.readers import load_reader                     # noqa: E402
from chipbench.tests import by_name                           # noqa: E402
from chipbench.tests.test_deepseek_v2_cell import BROKEN      # noqa: E402

CELL = "serve-xing4-code4k"
CONFIG = "xing4.0-29b-a4b-6L"
NEW = {"mhc_ms_per_chunk.serve": "ttft_p90_ms",
       "mhc_chunk_roofline.serve": "ttft_p90_ms",
       "mhc_ms_per_decode.serve": "itl_p95_ms",
       "noaux_expert_ms_per_decode.serve": "itl_p95_ms",
       "noaux_expert_roofline.serve": "itl_p95_ms"}
# the accepted metrics whose readers read this cell's ``obs`` as it is
SHARED = (
    "device_idle_share.serve", "decode_step_ms.serve",
    "batch_occupancy.serve", "queue_wait_p90_ms.serve",
    "prefill_p90_ms.serve", "decode_pass_ms.serve",
    "decode_program_ms.serve", "chunk_program_ms.serve",
    "chunk_then_step_pass_ms.serve", "chunk_then_step_pass_host_ms.serve",
    "chunk_then_step_pass_wait_ms.serve", "chunk_pass_gap_share.serve",
    "latent_decode_ms_per_step.serve", "latent_decode_roofline.serve",
    "latent_prefill_ms_per_chunk.serve", "latent_prefill_roofline.serve",
    "experts_touched_share.serve", "expert_load_max_over_mean.serve")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def test_cell_is_found_by_name_with_its_files():
    bench = load("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == (CONFIG, "code4k")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cfg = load(*entry["file"].split("/"))
    mix = load("chipbench", "traffic", cell["traffic"] + ".json")
    assert mix["kind"] == "open_loop_http_xing4"
    assert os.path.exists(os.path.join(ROOT, cfg["reference"]))
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace",
        "num_nextn_predict_layers"]
    assert sorted(cfg["reduced"]) == sorted(cfg["changed"]) == sorted(
        k for k in cfg["published"] if k != "parameters")
    assert (cfg["deployment"]["chips"],
            cfg["deployment"]["chips_sharing_a_layer"]) == (8, 1)
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"ttft_p90_ms", "itl_p95_ms", "serve_tokens_per_s",
                   "setup_s"}
    by_name.check_listed(bench, CELL, NEW, source="device_trace")
    by_name.check_listed(bench, CELL, SHARED)
    for name in NEW:
        # a reader that finds nothing to read gives nothing
        assert load_reader(name).read({}) is None
        assert by_name.metric(bench, name)["workloads"] == [CELL]
    # the catalog's numbers, every one under its own key
    catalog = None
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        with open(path) as f:
            catalog = next(json.loads(line)["config"] for line in f
                           if '"Xing4.0-29B-A4B"' in line)
    else:
        catalog = {"hidden_size": 3584, "intermediate_size": 9216,
                   "kv_lora_rank": 512, "q_lora_rank": 768,
                   "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                   "v_head_dim": 128, "num_attention_heads": 32,
                   "moe_intermediate_size": 1024, "n_routed_experts": 64,
                   "n_shared_experts": 1, "num_experts_per_tok": 4,
                   "hc_mult": 4, "hc_sinkhorn_iters": 20,
                   "vocab_size": 131072, "routed_scaling_factor": 2,
                   "max_position_embeddings": 262144}
    same = {k: v for k, v in catalog.items() if k not in cfg["reduced"]}
    assert {k: cfg[k] for k in same} == same
    assert cfg["rope_scaling"]["factor"] == 64
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["num_nextn_predict_layers"]) == (6, 1, 0)
    assert cfg["published"]["num_hidden_layers"] == 40


def test_the_mix_is_the_issues_traffic_cold_and_unshared():
    mix = load("chipbench", "traffic", "code4k.json")
    assert mix["prompt_len"]["lo"] == 512 and mix["prompt_len"]["hi"] == 16384
    assert mix["prompt_len"]["median"] == 4096
    assert (mix["output_len"]["lo"], mix["output_len"]["hi"],
            mix["output_len"]["median"]) == (16, 256, 48)
    assert mix["shared_heads"]["n"] == 0
    reqs = traffic_gen.chat_requests(mix, 51.0, 7, 131072)
    window = [r for r in reqs if not r["lead"]]
    assert len(window) == round(mix["rate_per_s"] * 51)
    assert all(r["head"] is None for r in reqs)
    lens = sorted(len(r["prompt"]) for r in window)
    assert lens[0] >= 512 and lens[-1] <= 16384
    assert 3500 < lens[len(lens) // 2] < 4700
    # ids drawn anew a request: no two prompts share a first block
    assert len({tuple(r["prompt"][:16]) for r in reqs}) == len(reqs)
    engine = load("chipbench", "configs", CONFIG + ".json")["engine"]
    assert mix["max_total"] <= engine["max_seq"]


def test_bytes_functions_by_hand():
    pub = load("chipbench", "configs", CONFIG + ".json")
    assert xing4_bytes.sublayers(pub) == 12
    assert xing4_bytes.expert_layers(pub) == 5
    flops, bytes_ = xing4_bytes.mhc_work(pub, 1024)
    # a sublayer and token: 4 passes of 4 x 3,584 bf16 lanes, the mixed
    # stream out and F's output in; Phi [24, 14336] f32 + the norm's
    # weight once a sublayer
    token = 4 * 4 * 3584 * 2 + 2 * 3584 * 2
    fixed = 24 * 14336 * 4 + 14336 * 2
    assert bytes_ == 12 * (1024 * token + fixed)
    assert abs(bytes_ / 12 / 1e6 - 133.5) < 0.1       # 0.163 ms a sublayer
    assert flops < 0.05 * bytes_ * 197e12 / 819e9      # memory bound
    one = 3 * 3584 * 1024
    assert xing4_bytes.routed_expert_bytes_per_decode(pub, 100) == 2 * (
        100 * one + 5 * (one + 3584 * 64)) + 4 * 5 * 64


def test_labels_from_an_ops_text():
    pub = load("chipbench", "configs", CONFIG + ".json")
    marks = xing4_trace.marks_of(pub, 32, 1024)
    lab = xing4_trace.label_of
    assert lab('%tpu_custom_call.26 = bf16[32,32,512]{2,1,0} '
               'custom-call(s32[1], s32[32], bf16[32,32,640], '
               'bf16[147462,16,640]), custom_call_target="tpu_custom_call"',
               marks) == "latent_decode_attention"
    assert lab("%fusion.9 = f32[24,1024]{1,0} fusion(bf16[1024,14336] %x, "
               "f32[24,14336] %params__layers___2___ffn____hc____phi)",
               marks) == "mhc_pre_map"
    assert lab("%convolution.3 = f32[24,32]{1,0} convolution(f32[24,14336] "
               "%copy-done.4, f32[32,14336] %fusion.8)", marks) \
        == "mhc_pre_map"
    assert lab("%fusion.4 = f32[4,4,1024]{2,1,0} fusion(f32[4,4,1024] %a)",
               marks) == "mhc_sinkhorn"
    assert lab("%fusion.5 = bf16[1024,3584]{1,0} fusion(bf16[1024,14336] %x,"
               " f32[1024,4] %g)", marks) == "mhc_mix_in"
    # behind the embedding the streams are four arrays; the way out is
    # the epilogue of the sublayer's last product (from the chip's trace)
    e = "f32[1024]{0:T(1024)S(1)} %bitcast."
    out = ("%fusion.545 = (bf16[1024,3584]{1,0:T(8,128)(2,1)}, bf16[1024,"
           "3584]{1,0:T(8,128)(2,1)}, bf16[1024,3584]{1,0:T(8,128)(2,1)}) "
           "fusion(" + ", ".join(e + str(i) for i in range(5)) + ", bf16["
           "1024,14336]{1,0:T(8,128)(2,1)} %bitcast.1002, bf16[1024,4096]"
           "{0,1:T(8,128)(2,1)S(1)} %bitcast.1011, bf16[4096,3584]{1,0:T(8,"
           "128)(2,1)} %params__layers___0___mixer____wo__.1), kind=kOutput")
    assert lab(out, marks) == "mhc_mix_out"
    way_in = ("%fusion.530 = (f32[1024]{0:T(1024)S(1)}, bf16[1024,3584]{1,0:"
              "T(8,128)(2,1)S(1)}) fusion(" + ", ".join(
                  e + f"{i}, bf16[1024,3584]{{1,0:T(8,128)(2,1)S(1)}} %g.{i}"
                  for i in range(4)) + "), kind=kLoop")
    assert lab(way_in, marks) == "mhc_mix_in"
    assert lab("%add_rsqrt_fusion.3 = f32[1024]{0:T(1024)S(1)} fusion(f32["
               "1024]{0:T(1024)S(1)} %fusion.894), kind=kLoop", marks) \
        == "other"
    assert lab("%fusion.6 = bf16[1024,14336]{1,0} fusion(bf16[1024,14336] "
               "%x, bf16[1024,3584] %f, f32[1024,4,4] %h)", marks) \
        == "mhc_mix_out"
    assert lab("%f = bf16[32,18432] fusion(%params__layers___0___ffn____w_in"
               " bf16[3584,18432])", marks) == "dense_mlp"
    assert lab("%gmm.4 = bf16[128,2048] custom-call(bf16[64,3584,2048] "
               "%params__layers___3___ffn____w_in)", marks) \
        == "routed_experts"
    assert lab("%f = bf16[32,2048] fusion(bf16[3584,2048] %copy-done.3)",
               marks) == "shared_expert"
    assert lab("%f = bf16[32,768] fusion(bf16[3584,768] %copy-done.9)",
               marks) == "mixer_latent_proj"
    assert lab("%fusion.3 = bf16[32,1024,128] fusion(bf16[1024,512] %x, "
               "bf16[32,512,128] %params__layers___2___mixer____w_uk)",
               marks) == "latent_kvb"
    assert lab("%gather = bf16[64,16,640] gather(bf16[147462,16,640] %p)",
               marks) == "latent_window"
    assert lab("%s = s32[128] sort(s32[128] %x)", marks) == "routed_experts"
    assert lab("%n = f32[32,3584] fusion(f32[32,3584] %x)", marks) == "other"
    obs = {"scoped": {
        "jit_step": {"runs": 4, "label_seconds": {
            "routed_experts": 0.012, "shared_expert": 0.004,
            "mhc_pre_map": 0.001, "mhc_sinkhorn": 0.002,
            "mhc_mix_in": 0.0005, "mhc_mix_out": 0.0005}},
        "jit_chunk_fn": {"runs": 2, "label_seconds": {
            "mhc_pre_map": 0.002, "mhc_sinkhorn": 0.001,
            "mhc_mix_in": 0.003, "mhc_mix_out": 0.004}}}}
    assert load_reader("mhc_ms_per_decode.serve").read(obs) == 1.0
    assert load_reader("mhc_ms_per_chunk.serve").read(obs) == 5.0
    assert load_reader("noaux_expert_ms_per_decode.serve").read(obs) == 4.0
    full = {**obs, "published": pub, "peaks": PEAKS, "expert_layers": 5,
            "held": (0, 64),
            "counters": {"decode_iterations": 4, "chunk_passes": 2,
                         "prefill_tokens": 2048,
                         "expert_touched_held_decode": 4 * 5 * 40}}
    share = load_reader("mhc_chunk_roofline.serve").read(full)
    _, bytes_ = xing4_bytes.mhc_work(pub, 1024)
    assert abs(share - 100 * (bytes_ / 819e9) / 5e-3) < 1e-9
    assert 0 < share < 100
    # the traced seconds' own counters where the kind took them
    half = load_reader("mhc_chunk_roofline.serve").read(
        {**full, "traced_counters": {"chunk_passes": 2,
                                     "prefill_tokens": 1024}})
    assert half < share
    got = load_reader("noaux_expert_roofline.serve").read(full)
    want = xing4_bytes.routed_expert_bytes_per_decode(pub, 200) / 819e9
    assert abs(got - 100 * want / 4e-3) < 1e-9


def _rehearse(tmp_path, code=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    for name in ("RAY_TPU_TRACING", "RAY_TPU_TRACE_DIR", "XLA_FLAGS"):
        env.pop(name, None)
    run_py = os.path.join(ROOT, "chipbench", "run.py")
    head = [sys.executable, run_py] if code is None else [
        sys.executable, "-c", code.format(run_py=run_py)]
    p = subprocess.run(
        head + ["--workload", CELL, "--seed", "3000000061", "--seconds", "4",
                "--trace", "1", "--rehearse"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def test_rehearsal_of_the_cell_at_its_own_fixture(tmp_path):
    line, _ = _rehearse(tmp_path)
    assert line["correct"] is False and line["metrics"] == {}
    assert line["rehearsal_verdict_not_a_result"] is True
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    assert line["checks"]["margin_p90"]["value"] \
        <= line["notes"]["tie_tolerance"]
    assert line["checks"]["margin_p99"]["value"] \
        <= line["notes"]["tail_tolerance"]
    assert line["notes"]["compiles_in_window"] == 0
    c = line["notes"]["counters"]
    # every prompt is cold: nothing is adopted; one pool is walked;
    # every expert is held
    assert c["prefix_hit_tokens"] == 0 and c["prefix_blocks_adopted"] == 0
    assert 0 < c["kv_blocks_attended"] <= c["kv_blocks_tabled"]
    assert c["chunk_query_keys"] >= c["chunk_keys"] > 0
    assert c["expert_assignments_held"] == c["expert_assignments_total"] > 0
    got = line["rehearsal_metrics_not_device_numbers"]
    # the accepted readers that need no device trace read this cell's obs
    for name in ("experts_touched_share.serve",
                 "expert_load_max_over_mean.serve"):
        assert name in got, name
    assert 0 < got["experts_touched_share.serve"]["value"] <= 100
    assert 0 < line["notes"]["chunk_pass_share"] < 1


def test_a_token_altered_in_the_greedy_step_comes_out_not_correct(tmp_path):
    line, err = _rehearse(tmp_path, BROKEN)
    assert list(line)[-1] == "checks" and line["failed"] == 0
    assert line["rehearsal_verdict_not_a_result"] is False
    tail = line["checks"]["margin_p99"]
    assert tail["value"] > tail["limit"]
    assert "check margin_p99: value" in err


def test_controls_through_the_judge_at_the_fixtures_size():
    """The controls at a size a test can hold: the reference with float8
    e4m3 inputs to every product picks its own greedy tokens; judged as
    a served stream is it is NOT correct, while the stated precision
    (bfloat16) is; and with ONLY the maps in bfloat16 the logits move
    by more than the fixture's limits (the second control)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import hybrid
    from chipbench.reference import xing4 as ref
    from chipbench.traffic.open_loop_http_xing4 import (judge, model_config,
                                                        seeded_maps)
    fixture = load("chipbench", "tests", "rehearse_xing4.json")
    config = {**load("chipbench", "configs", CONFIG + ".json"),
              **fixture["config"]}
    cfg, pub, held = model_config(config)
    params = jax.jit(lambda k: seeded_maps(
        cfg, hybrid.init_params(cfg, k), config["map_std"]))(
        jax.random.PRNGKey(3))
    tokens = np.random.default_rng(5).integers(0, cfg.vocab_size, 512)
    full = np.asarray(ref.logits(params, tokens, pub, held))

    def verdict(**kw):
        pick = np.asarray(ref.logits(params, tokens, pub, held,
                                     **kw)).argmax(-1)
        margins = full.max(-1) - full[np.arange(len(pick)), pick]
        limits = {**fixture["traffic"], "tie_tolerance": 1e-4,
                  "tail_tolerance": 1e-3}
        judged = judge(margins, limits)
        return all(v["value"] <= v["limit"] for v in judged.values())
    assert verdict(round_to=jnp.bfloat16)
    assert not verdict(round_to=jnp.float8_e4m3fn)
    moved = np.abs(np.asarray(ref.logits(
        params, tokens, pub, held, round_maps_to=jnp.bfloat16)) - full).max()
    assert moved > fixture["traffic"]["tail_tolerance"]
