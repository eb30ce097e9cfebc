"""The cell ``serve-deepseek-v2-docqa8k-r80`` and what it brought: found
by name with no edit, its configuration's widths and share, the window's
requests as the mix states them (documents, asks, cold share), its label
table, its bytes functions by hand, and a CPU rehearsal at a fixture of
its own (``rehearse_deepseek_v2.json``) — sound, and with a token altered
where it is produced.  ``python -m pytest chipbench/tests -q``; not part
of tier-1; no number here is a device number.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import deepseek_v2_bytes, deepseek_v2_trace    # noqa: E402
from chipbench.readers import load_reader                     # noqa: E402
from chipbench.tests import by_name                           # noqa: E402

CELL = "serve-deepseek-v2-docqa8k-r80"
CONFIG = "deepseek-v2-7L-e20"
NEW = {"latent_decode_ms_per_step.serve": "itl_p95_ms",
       "latent_decode_roofline.serve": "itl_p95_ms",
       "latent_prefill_ms_per_chunk.serve": "ttft_p90_ms",
       "latent_prefill_roofline.serve": "ttft_p90_ms",
       "group_routed_expert_ms_per_decode.serve": "itl_p95_ms",
       "adopted_prefix_share.serve": "ttft_p90_ms"}
SHAPE_FREE = (
    "device_idle_share.serve", "decode_step_ms.serve",
    "batch_occupancy.serve", "queue_wait_p90_ms.serve",
    "prefill_p90_ms.serve", "front_overhead_p90_ms.serve",
    "decode_pass_ms.serve", "prefill_pass_share.serve",
    "engine_host_ms_per_pass.serve", "decode_program_ms.serve",
    "chunk_program_ms.serve", "loop_host_ms_per_pass.serve",
    "device_starved_share.serve", "block_hunt_ms_per_pass.serve",
    "emit_ms_per_pass.serve", "loop_unaccounted_share.serve",
    "prefix_hit_rate.serve")


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def published():
    config = load("chipbench", "configs", CONFIG + ".json")
    return {**config, "n_routed_experts":
            config["published"]["n_routed_experts"]}


def test_cell_is_found_by_name_with_its_files():
    bench = load("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == (CONFIG, "docqa8k-r80")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cfg = load(*entry["file"].split("/"))
    mix = load("chipbench", "traffic", cell["traffic"] + ".json")
    assert mix["kind"] == "open_loop_http_deepseek_v2"
    assert os.path.exists(os.path.join(ROOT, cfg["reference"]))
    assert cfg["source"] == entry["source"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == sorted(
        cfg["changed"]) == sorted(k for k in cfg["published"]
                                  if k != "parameters")
    assert cfg["deployment"]["chips_sharing_a_layer"] == 8
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"ttft_p90_ms", "itl_p95_ms", "serve_tokens_per_s",
                   "setup_s"}
    # by name: the cell may be listed under more, a metric may list more
    by_name.check_listed(bench, CELL, NEW)
    by_name.check_listed(bench, CELL, SHAPE_FREE)
    for name in NEW:
        # a reader that finds nothing to read gives nothing
        assert load_reader(name).read({}) is None
    # the catalog's numbers, every one under its own key
    catalog = {"hidden_size": 5120, "intermediate_size": 12288,
               "kv_lora_rank": 512, "q_lora_rank": 1536,
               "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
               "v_head_dim": 128, "num_attention_heads": 128,
               "moe_intermediate_size": 1536, "n_shared_experts": 2,
               "num_experts_per_tok": 6, "n_group": 8, "topk_group": 3,
               "routed_scaling_factor": 16, "first_k_dense_replace": 1,
               "max_position_embeddings": 163840, "rms_norm_eps": 1e-06}
    assert {k: cfg[k] for k in catalog} == catalog
    assert cfg["rope_scaling"]["factor"] == 40
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (7, 20, 12800)


def test_the_mix_is_the_issues_traffic_and_the_window_is_built_as_stated():
    from chipbench.traffic.open_loop_http_deepseek_v2 import cold_share
    from chipbench.traffic_gen import chat_requests
    bench = load("BENCHMARK.json")
    mix = load("chipbench", "traffic", "docqa8k-r80.json")
    cfg = load("chipbench", "configs", CONFIG + ".json")
    assert mix["prompt_len"] == {"lo": 8224, "hi": 9216, "median": 8448,
                                 "sigma": 0.03}
    assert mix["output_len"] == {"lo": 32, "hi": 256, "median": 96,
                                 "sigma": 0.6}
    assert mix["max_total"] == 9472 == cfg["engine"]["max_seq"]
    sh = mix["shared_heads"]
    due = round(mix["rate_per_s"] * bench["run_seconds"])
    assert sh == {"n": due // 3, "len": 8192, "share": 1.0, "zipf_a": 0.0}
    assert mix["lead_s"] <= 6 and mix["order_seed"] == 0
    reqs = chat_requests(mix, bench["run_seconds"], 7, cfg["vocab_size"])
    window = [r for r in reqs if not r["lead"]]
    assert len(window) == due
    asks = {}
    for r in window:
        assert r["head"] is not None
        assert r["prompt"][:8192] == next(
            q["prompt"][:8192] for q in window if q["head"] == r["head"])
        assert 32 <= len(r["prompt"]) - 8192 <= 1024
        assert 32 <= r["max_tokens"] <= 256
        asks[r["head"]] = asks.get(r["head"], 0) + 1
    # every document asked 3 times (the remainder of due / 3 once more)
    assert len(asks) == sh["n"] and set(asks.values()) <= {3, 4}
    # the pool holds the window's whole document set
    tokens = len(asks) * 8192 + sum(
        len(r["prompt"]) - 8192 + r["max_tokens"] for r in reqs)
    assert tokens < cfg["engine"]["n_blocks"] * 16
    # the cold share the mix's note states, clear of the p90's edge
    stated = mix["cold_request_share"]
    assert abs(cold_share(reqs) - stated) < 5e-4 and stated >= 0.15


def test_configuration_holds_the_published_widths_and_the_share():
    from chipbench.traffic.open_loop_http_deepseek_v2 import model_config
    config = load("chipbench", "configs", CONFIG + ".json")
    cfg, pub, held = model_config(config)
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.rope_dim,
            cfg.v_head_dim, cfg.q_rank, cfg.kv_rank) == (
        5120, 128, 128, 64, 128, 1536, 512)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.expert_width,
            cfg.shared_width, cfg.route_groups, cfg.norm_topk,
            cfg.routed_scale) == (160, 6, 1536, 3072, (8, 3), False, 16)
    assert (cfg.dense_layers, cfg.dense_width) == (1, 12288)
    assert held == (0, 20) == cfg.experts_held and cfg.vocab_size == 12800
    assert cfg.kv_geometry == (7, 1, 576) and cfg.value_lanes == 512
    assert cfg.state_geometry is None and cfg.max_seq == 9472
    assert abs(cfg.attention_multiplier - 0.11472) < 2e-5
    assert pub["n_routed_experts"] == 160


def test_bytes_functions_by_hand():
    pub = published()
    # decode: 1,000 blocks of 16 keys a layer, 7 layers.  A key is 576
    # cached lanes of 2 B, read once; a head's scores 576 products, its
    # values 512, times 2 (multiply, add), times 128 heads
    flops, nbytes = deepseek_v2_bytes.decode_kernel_work(pub, 7, 16, 1000.0)
    assert nbytes == 7 * 16000 * 576 * 2 == 129_024_000
    assert flops == 7 * 16000 * 128 * (576 + 512) * 2 == 31_195_136_000
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # ~242 flop a byte against a ridge of 240.5: compute bound, just
    least = deepseek_v2_bytes.least_seconds((flops, nbytes), peaks)
    assert least == flops / 197e12 > nbytes / 819e9
    # the window form: 4,096 keys in reach decompressed once (512 ->
    # 128 heads x 256), 1,000,000 (query, key) pairs over 192 + 128
    flops, nbytes = deepseek_v2_bytes.window_work(pub, 7, 4096.0, 1e6)
    assert flops == 7 * (2 * 4096 * 512 * 128 * 256
                         + 2 * 1e6 * 128 * 320)
    assert nbytes == 7 * (4096 * 576 * 2 + 512 * 128 * 256 * 2)
    obs = {"counters": {"decode_iterations": 4, "kv_blocks_attended": 4000,
                        "chunk_passes": 2, "chunk_keys": 8192}}
    assert deepseek_v2_bytes.per_decode(obs, "kv_blocks_attended") == 1000
    assert deepseek_v2_bytes.per_chunk(obs, "chunk_keys") == 4096
    assert deepseek_v2_bytes.per_chunk({"counters": {}}, "chunk_keys") is None


def test_labels_from_an_ops_text():
    marks = deepseek_v2_trace.marks_of(published(), 32, 512)
    lab = deepseek_v2_trace.label_of
    kernel = ('%latent_decode_attention.7 = bf16[32,128,512]{2,1,0} '
              'custom-call(s32[1], s32[32], s32[18944], bf16[32,128,640], '
              'bf16[200711,16,640]), custom_call_target="tpu_custom_call"')
    assert lab(kernel, marks) == "latent_decode_attention"
    window = ('%latent_window_attention.46 = (f32[128,1,512], f32[128,1,512],'
              ' f32[128,128,512]) custom-call(s32[1], s32[1,512], '
              'bf16[128,512,128], bf16[128,512,64], bf16[128,1024,128], '
              'bf16[1024,64], bf16[128,128,1024])')
    assert lab(window, marks) in deepseek_v2_trace.WINDOW
    assert lab("%fusion.3 = bf16[128,1024,128] fusion(bf16[1024,512] %x, "
               "bf16[128,512,128] %params__layers___2___mixer____w_uk)",
               marks) == "latent_kvb"
    assert lab("%gather = bf16[64,16,640] gather(bf16[200711,16,640] %p)",
               marks) == "latent_window"
    assert lab("%f = bf16[32,24576] fusion(%params__layers___0___ffn____w_in"
               " bf16[5120,24576])", marks) == "dense_mlp"
    assert lab("%gmm.4 = bf16[256,3072] custom-call(bf16[20,5120,3072] "
               "%params__layers___3___ffn____w_in)", marks) \
        == "routed_experts"
    assert lab("%f = bf16[32,6144] fusion(bf16[5120,6144] %copy-done.3)",
               marks) == "shared_expert"
    assert lab("%f = bf16[32,1536] fusion(bf16[5120,1536] %copy-done.9)",
               marks) == "mixer_latent_proj"
    assert lab("%s = s32[192] sort(s32[192] %x)", marks) == "routed_experts"
    assert lab("%n = f32[32,5120] fusion(f32[32,5120] %x)", marks) == "other"
    obs = {"scoped": {"jit_step": {"runs": 4, "label_seconds": {
        "latent_decode_attention": 0.008, "routed_experts": 0.012,
        "shared_expert": 0.004}}, "jit_chunk_fn": {"runs": 2,
        "label_seconds": {"latent_window": 0.03, "latent_kvb": 0.01}}}}
    assert load_reader("latent_decode_ms_per_step.serve").read(obs) == 2.0
    assert load_reader("group_routed_expert_ms_per_decode.serve").read(
        obs) == 4.0
    assert load_reader("latent_prefill_ms_per_chunk.serve").read(obs) == 20.0
    full = {**obs, "published": published(), "layers": 7, "block_size": 16,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "counters": {"decode_iterations": 4, "kv_blocks_attended": 4000,
                         "chunk_passes": 2, "chunk_keys": 8192,
                         "chunk_query_keys": 2e6, "prefix_hit_tokens": 300,
                         "prefill_tokens": 100}}
    share = load_reader("latent_decode_roofline.serve").read(full)
    assert abs(share - 100 * (31_195_136_000 / 197e12) / 2e-3) < 1e-9
    assert 0 < load_reader("latent_prefill_roofline.serve").read(full) < 100
    assert load_reader("adopted_prefix_share.serve").read(full) == 75.0


def _rehearse(tmp_path, code=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    for name in ("RAY_TPU_TRACING", "RAY_TPU_TRACE_DIR", "XLA_FLAGS"):
        env.pop(name, None)
    run_py = os.path.join(ROOT, "chipbench", "run.py")
    head = [sys.executable, run_py] if code is None else [
        sys.executable, "-c", code.format(run_py=run_py)]
    p = subprocess.run(
        head + ["--workload", CELL, "--seed", "3000000042", "--seconds", "4",
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
    # documents are adopted from the radix index, one pool is walked
    assert c["prefix_hit_tokens"] > 0 and c["prefix_blocks_adopted"] > 0
    assert 0 < c["kv_blocks_attended"] < c["kv_blocks_tabled"]
    assert c["chunk_query_keys"] >= c["chunk_keys"] > 0
    got = line["rehearsal_metrics_not_device_numbers"]
    assert 0 < got["adopted_prefix_share.serve"]["value"] < 100
    assert 0 < got["prefix_hit_rate.serve"]["value"] < 100
    assert 0 <= line["notes"]["cold_request_share"] <= 1
    assert 0 < line["notes"]["chunk_pass_share"] < 1


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
sound = engine._KVAndState.greedy
engine._KVAndState.greedy = staticmethod(
    lambda eng, logits: altered(sound(eng, logits), logits.shape[-1]))
sys.argv = ["run.py"] + sys.argv[1:]
runpy.run_path({run_py!r}, run_name="__main__")
"""


def test_a_token_altered_in_the_greedy_step_comes_out_not_correct(tmp_path):
    line, err = _rehearse(tmp_path, BROKEN)
    assert list(line)[-1] == "checks" and line["failed"] == 0
    assert line["rehearsal_verdict_not_a_result"] is False
    tail = line["checks"]["margin_p99"]
    assert tail["value"] > tail["limit"]
    assert "check margin_p99: value" in err


def test_control_in_float8_comes_out_not_correct_through_the_judge():
    """The control at a size a test can hold: the reference with float8
    e4m3 inputs to every product picks its own greedy tokens; judged as
    a served stream is (``judge``: two quantiles of the margins under
    the float32 reference, the fixture's limits) it is NOT correct,
    while the stated precision (bfloat16) is."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import hybrid
    from chipbench.reference import deepseek_v2 as ref
    from chipbench.traffic.open_loop_http_deepseek_v2 import (judge,
                                                              model_config)
    fixture = load("chipbench", "tests", "rehearse_deepseek_v2.json")
    config = {**load("chipbench", "configs", CONFIG + ".json"),
              **fixture["config"]}
    cfg, pub, held = model_config(config)
    params = jax.jit(lambda k: hybrid.init_params(cfg, k))(
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
