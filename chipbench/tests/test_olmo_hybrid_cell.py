"""The cell ``serve-olmo-hybrid-doc3k-r80`` and what it brought: found by
name with no edit, its configuration's widths (the cut is depth alone),
the window's requests as the mix states them (cold documents, nothing
shared), its label table on ops' texts and on a decode program recorded
on the chip, its bytes functions against the issue's arithmetic, and a
CPU rehearsal at a fixture of its own (``rehearse_olmo_hybrid.json``) —
sound, and with a token altered where it is produced.  ``python -m
pytest chipbench/tests -q``; not part of tier-1; no number here is a
device number.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import olmo_hybrid_bytes, olmo_hybrid_trace    # noqa: E402
from chipbench.readers import load_reader                     # noqa: E402
from chipbench.tests import by_name                           # noqa: E402

CELL = "serve-olmo-hybrid-doc3k-r80"
CONFIG = "olmo-hybrid-7b-16L"
NEW = {"delta_step_ms_per_decode.serve": "itl_p95_ms",
       "delta_step_roofline.serve": "itl_p95_ms",
       "delta_window_ms_per_chunk.serve": "ttft_p90_ms",
       "delta_window_roofline.serve": "ttft_p90_ms",
       "head_window_attention_ms_per_chunk.serve": "ttft_p90_ms",
       "head_window_attention_roofline.serve": "ttft_p90_ms"}
# (until PR 59 the four chunk-side ones were ``delta_prefill_*`` and
# ``window_attention_*``, which read ``jit_chunk_fn`` alone and fell
# silent when PR 58 let the chunks ride the step)
SHAPE_FREE = (
    "device_idle_share.serve", "decode_step_ms.serve",
    "batch_occupancy.serve", "queue_wait_p90_ms.serve",
    "prefill_p90_ms.serve", "front_overhead_p90_ms.serve",
    "decode_pass_ms.serve", "prefill_pass_share.serve",
    "engine_host_ms_per_pass.serve", "decode_program_ms.serve",
    "step_chunk_program_ms.serve", "loop_host_ms_per_pass.serve",
    "device_starved_share.serve", "block_hunt_ms_per_pass.serve",
    "emit_ms_per_pass.serve", "loop_unaccounted_share.serve")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def test_cell_is_found_by_name_with_its_files():
    bench = load("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == (CONFIG, "doc3k-r80")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cfg = load(*entry["file"].split("/"))
    mix = load("chipbench", "traffic", cell["traffic"] + ".json")
    assert mix["kind"] == "open_loop_http_olmo_hybrid"
    assert os.path.exists(os.path.join(ROOT, cfg["reference"]))
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"] == ["num_hidden_layers"] \
        == list(cfg["changed"])
    assert cfg["deployment"]["chips_sharing_a_layer"] == 1
    for key in ("published", "changed", "deployment", "assumed",
                "memory_arithmetic", "engine_note"):
        assert cfg[key], key
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
    catalog = {"vocab_size": 100352, "hidden_size": 3840,
               "intermediate_size": 11008, "num_attention_heads": 30,
               "num_key_value_heads": 30, "max_position_embeddings": 65536,
               "rms_norm_eps": 1e-06, "linear_num_key_heads": 30,
               "linear_num_value_heads": 30, "linear_key_head_dim": 96,
               "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4}
    assert {k: cfg[k] for k in catalog} == catalog
    assert cfg["rope_parameters"] == {"rope_theta": None}
    # the pattern is kept whole as published; its first 16 entries run
    assert cfg["num_hidden_layers"] == 16 and len(cfg["layer_types"]) == 32
    assert cfg["layer_types"] == (["linear_attention"] * 3
                                  + ["full_attention"]) * 8
    assert cfg["published"]["num_hidden_layers"] == 32


def test_the_mix_is_the_issues_traffic_and_the_window_is_built_as_stated():
    from chipbench.traffic_gen import chat_requests
    bench = load("BENCHMARK.json")
    mix = load("chipbench", "traffic", "doc3k-r80.json")
    cfg = load("chipbench", "configs", CONFIG + ".json")
    assert mix["prompt_len"] == {"lo": 1024, "hi": 8192, "median": 3072,
                                 "sigma": 0.5}
    assert mix["output_len"] == {"lo": 64, "hi": 384, "median": 160,
                                 "sigma": 0.6}
    assert mix["max_total"] == 8576 == cfg["engine"]["max_seq"]
    assert mix["shared_heads"]["n"] == 0 and mix["order_seed"] == 0
    assert mix["checked_requests"] >= mix["checked_at_least"] >= 4
    reqs = chat_requests(mix, bench["run_seconds"], 7, cfg["vocab_size"])
    window = [r for r in reqs if not r["lead"]]
    assert len(window) == round(mix["rate_per_s"] * bench["run_seconds"]) \
        >= 60
    # every request a different document: nothing to adopt
    assert all(r["head"] is None for r in reqs)
    assert len({tuple(r["prompt"][:64]) for r in reqs}) == len(reqs)
    for r in window:
        assert 1024 <= len(r["prompt"]) <= 8192
        assert 64 <= r["max_tokens"] <= 384
    # the pool holds a dozen average requests whole (~10 rows are live
    # at the cell's rate): the allocator is measured, not preemption
    mean = sum(len(r["prompt"]) + r["max_tokens"] for r in reqs) / len(reqs)
    assert 12 * mean < cfg["engine"]["n_blocks"] * 16


def test_configuration_holds_the_published_widths():
    from chipbench.traffic.open_loop_http_olmo_hybrid import model_config
    from ray_tpu.models import hybrid
    config = load("chipbench", "configs", CONFIG + ".json")
    cfg, pub = model_config(config)
    assert pub is config
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.dense_width, cfg.vocab_size) == (3840, 30, 30, 128, 11008,
                                                 100352)
    assert (cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim,
            cfg.conv_width) == (30, 96, 192, 4)
    assert (cfg.n_linear, cfg.n_attention, cfg.max_seq) == (12, 4, 8576)
    assert cfg.state_geometry == (12, (3, 11520), (96, 5760))
    assert cfg.kv_geometry == (4, 30, 128) and cfg.value_lanes is None
    assert cfg.qk_norm and cfg.norm_output and not cfg.tied_head
    assert hybrid.LINEAR == "linear_attention"


def test_bytes_functions_against_the_issues_arithmetic():
    pub = load("chipbench", "configs", CONFIG + ".json")
    b = olmo_hybrid_bytes
    matrix, conv = b.state_bytes_a_row(pub)
    assert (matrix, conv) == (2_211_840, 69_120)        # a row and layer
    assert 12 * (matrix + conv) == 27_371_520           # 27.4 MB a row
    assert b.n_layers(pub, "linear_attention") == 12
    assert b.n_layers(pub, "full_attention") == 4
    # the one-token form: 10 rows advance, state read and written once
    flops, nbytes = b.step_work(pub, 10.0)
    assert flops == 0 and nbytes == 12 * 10 * 2 * 2_280_960
    assert b.least_seconds((flops, nbytes), PEAKS) == nbytes / 819e9
    # the window form, a head and block of 64 tokens, by hand
    per_block = (2 * 2 * 64 * 64 * 96 + 64 * 64 * 288
                 + 3 * 2 * 64 * 96 * 192 + 2 * 64 * 64 * 192)
    assert b.window_flops_a_block(pub) == per_block == 11_403_264
    flops, nbytes = b.window_work(pub, 1024.0)
    assert flops == 12 * 16 * 30 * per_block            # 65.7 GFLOP
    assert nbytes == 12 * (1024 * 30 * 576 * 2 + 2 * 2_280_960)
    # at one pass a product the block's own q, k, v, o bind: 0.59 ms of
    # bytes a chunk against 0.33 ms of products
    assert b.least_seconds((flops, nbytes), PEAKS) == nbytes / 819e9 \
        > flops / 197e12
    # the window attention: 1e6 pairs over 30 heads of 128, twice
    flops, nbytes = b.attention_work(pub, 4096.0, 1e6)
    assert flops == 4 * 2 * 2 * 1e6 * 30 * 128
    assert nbytes == 4 * 4096 * 2 * 3840 * 2            # 61.4 KB a token
    assert 4 * 2 * 3840 * 2 == 61_440
    obs = {"counters": {"decode_iterations": 4,
                        "linear_state_rows_advanced": 40,
                        "chunk_passes": 2, "linear_chunk_tokens": 1800}}
    assert b.per_decode(obs, "linear_state_rows_advanced") == 10
    assert b.per_chunk(obs, "linear_chunk_tokens") == 900
    assert b.per_chunk({"counters": {}}, "chunk_keys") is None


def test_labels_from_an_ops_text():
    pub = load("chipbench", "configs", CONFIG + ".json")
    marks = olmo_hybrid_trace.marks_of(pub, 32, 1024, (3, 7, 11, 15))
    lab = olmo_hybrid_trace.label_of
    step = ('%tpu_custom_call.9 = (f32[12,32,96,5760]{3,2,1,0}, '
            'f32[32,1,5760]) custom-call(s32[1], s32[1], s32[32], '
            'f32[12,32,96,5760] %p, bf16[32,192,96], bf16[96,5760], '
            'f32[32,3,5760])')
    assert lab(step, marks) == "delta_step"
    window = ('%tpu_custom_call.27 = (f32[30,1,1024], f32[30,1,1024], '
              'f32[30,128,1024]) custom-call(s32[1], s32[1,1024], '
              'bf16[30,1024,128], bf16[1024,3840], bf16[30,128,1024])')
    assert lab(window, marks) == "window_attention"
    paged = ('%tpu_custom_call.31 = f32[32,1,3840] custom-call(s32[1], '
             's32[32], s32[17152], bf16[32,30,128], bf16[16388,16,3840], '
             'bf16[16388,16,3840])')
    assert lab(paged, marks) == "paged_decode_attention"
    # wqkv is both mixers' name and shape: the layer's index decides
    assert lab("%f = bf16[1,1024,11520] fusion(bf16[1024,3840] %x, "
               "bf16[3840,11520] %params__layers___11___mixer____wqkv__.1)",
               marks) == "mixer_attention"
    assert lab("%f = bf16[1,1024,11520] fusion(bf16[1024,3840] %x, "
               "bf16[3840,11520] %params__layers___12___mixer____wqkv__.1)",
               marks) == "mixer_linear_proj"
    assert lab("%f = bf16[1024,3840] fusion(bf16[5760,3840] "
               "%params__layers___12___mixer____wo__.1)", marks) \
        == "mixer_linear_proj"
    assert lab("%f = bf16[1024,3840] fusion(bf16[3840,3840] "
               "%params__layers___3___mixer____wo__.1)", marks) \
        == "mixer_attention"
    assert lab("%f = f32[32,5760] fusion(bf16[32,3840] %x, "
               "bf16[3840,5760] %copy-done.3)", marks) == "mixer_linear_proj"
    assert lab("%f = bf16[1024,22016] fusion(bf16[3840,22016] "
               "%params__layers___0___ffn____w_in__.1)", marks) \
        == "dense_mlp"
    assert lab("%f = f32[1,30,16,64,64] fusion(f32[1,30,16,64,96] %k)",
               marks) == "mixer_linear_attention"
    assert lab("%s = bf16[1,1024,11520] slice(bf16[1,1027,11520] %pad)",
               marks) == "mixer_linear_attention"
    assert lab("%g = bf16[64,16,3840] gather(bf16[16388,16,3840] %p)",
               marks) == "window_attention"
    # the head's product reads the residual stream [1024, 3840]: no mark
    assert lab("%h = f32[1024,100352] fusion(bf16[3840,100352] "
               "%params__head__.1, bf16[1024,3840] %x)", marks) == "other"
    obs = {"scoped": {"jit_step": {"runs": 4, "label_seconds": {
        "delta_step": 0.002, "mixer_linear_attention": 0.004,
        "dense_mlp": 0.02}}, "jit_chunk_fn": {"runs": 2, "label_seconds": {
            "mixer_linear_attention": 0.03, "window_attention": 0.01}}}}
    assert load_reader("delta_step_ms_per_decode.serve").read(obs) == 1.5
    assert load_reader("delta_window_ms_per_chunk.serve").read(obs) == 15.0
    assert load_reader("head_window_attention_ms_per_chunk.serve").read(
        obs) == 5.0
    full = {**obs, "published": pub, "peaks": PEAKS,
            "counters": {"decode_iterations": 4,
                         "linear_state_rows_advanced": 40,
                         "chunk_passes": 2, "linear_chunk_tokens": 2048,
                         "chunk_keys": 8192, "chunk_query_keys": 2e6}}
    share = load_reader("delta_step_roofline.serve").read(full)
    assert abs(share - 100 * (12 * 10 * 2 * 2_280_960 / 819e9) / 1.5e-3) \
        < 1e-9
    for name in ("delta_window_roofline.serve",
                 "head_window_attention_roofline.serve"):
        assert 0 < load_reader(name).read(full) < 100


def test_the_six_metrics_read_a_decode_program_recorded_on_the_chip():
    """One ``jit_step`` of the cell as traced on the chip, each op
    labelled from its full text there: the reduction gives the recorded
    sums, the one-token kernel is found once a linear layer, and the
    texts the file keeps still get their labels from today's table."""
    from chipbench.scoped_trace import summarize
    rec = load("chipbench", "tests", "recorded_olmo_hybrid_trace.json")
    got = summarize(rec["rows"])["jit_step"]
    assert got["runs"] == 1
    for label, ms in rec["expect"].items():
        assert abs(1e3 * got["label_seconds"][label] - ms) < 1e-6
    assert sum(1 for r in rec["rows"] if r[2] == "delta_step") == 12
    assert sum(1 for r in rec["rows"]
               if r[2] == "paged_decode_attention") == 4
    pub = load("chipbench", "configs", CONFIG + ".json")
    marks = olmo_hybrid_trace.marks_of(pub, 32, rec["prefill_chunk"],
                                       (3, 7, 11, 15))
    for label, text in rec["texts"].items():
        assert olmo_hybrid_trace.label_of(text, marks) == label
    obs = {"scoped": {"jit_step": got}}
    want = rec["expect"]["delta_step"] \
        + rec["expect"]["mixer_linear_attention"]
    assert abs(load_reader("delta_step_ms_per_decode.serve").read(obs)
               - want) < 1e-6
    # no chunk program in the record: its readers find nothing
    assert load_reader("delta_window_ms_per_chunk.serve").read(obs) is None
    assert load_reader("head_window_attention_roofline.serve").read(
        obs) is None


def _rehearse(tmp_path, code=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    for name in ("RAY_TPU_TRACING", "RAY_TPU_TRACE_DIR", "XLA_FLAGS"):
        env.pop(name, None)
    run_py = os.path.join(ROOT, "chipbench", "run.py")
    head = [sys.executable, run_py] if code is None else [
        sys.executable, "-c", code.format(run_py=run_py)]
    p = subprocess.run(
        head + ["--workload", CELL, "--seed", "3000000044", "--seconds", "4",
                "--trace", "1", "--rehearse"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def test_rehearsal_of_the_cell_at_its_own_fixture(tmp_path):
    line, _ = _rehearse(tmp_path)
    assert line["correct"] is False and line["metrics"] == {}
    assert line["rehearsal_verdict_not_a_result"] is True
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    assert line["checks"]["worst_margin"]["value"] \
        <= line["notes"]["tie_tolerance"]
    assert line["checks"]["checked_requests"] == {"value": 4, "at_least": 4}
    assert line["notes"]["compiles_in_window"] == 0
    c = line["notes"]["counters"]
    # nothing is adopted; every prompt token goes through the window
    # form and every decode pass advances its live rows' matrix state
    assert c["prefix_hit_tokens"] == 0
    assert c["linear_chunk_tokens"] == c["prefill_tokens"] > 0
    assert c["linear_state_rows_advanced"] == c["row_steps"] > 0
    assert c["chunk_query_keys"] >= c["chunk_keys"] > 0
    assert line["notes"]["state_bytes"] > 0
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
    worst = line["checks"]["worst_margin"]
    assert worst["value"] > worst["limit"]
    assert "check worst_margin: value" in err


def test_controls_come_out_not_correct_through_the_check():
    """The controls at a size a test can hold: the reference with float8
    e4m3 inputs to every product, and with the matrix state rounded to
    bfloat16, each picks its own greedy tokens; judged as a served
    stream is (the maximum margin under the float32 reference) neither
    is correct at the fixture's tolerance, while bfloat16 products, the
    stated precision, pass a limit set as the cell's is (twice their own
    reading, under half the float8 reading)."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import olmo_hybrid as ref
    from chipbench.traffic.open_loop_http_olmo_hybrid import (make_params,
                                                              model_config)
    fixture = load("chipbench", "tests", "rehearse_olmo_hybrid.json")
    config = {**load("chipbench", "configs", CONFIG + ".json"),
              **fixture["config"]}
    cfg, pub = model_config(config)
    params = make_params(cfg, 3)
    tokens = np.random.default_rng(5).integers(0, cfg.vocab_size, 512)
    full = np.asarray(ref.logits(params, tokens, pub))

    def worst(**kw):
        pick = np.asarray(ref.logits(params, tokens, pub, **kw)).argmax(-1)
        return float((full.max(-1)
                      - full[np.arange(len(pick)), pick]).max())
    tight = fixture["traffic"]["tie_tolerance"]
    f8, stated = worst(round_to=jnp.float8_e4m3fn), \
        worst(round_to=jnp.bfloat16)
    assert f8 > tight and worst(state_round_to=jnp.bfloat16) > tight
    assert 2 * stated < f8 / 2
