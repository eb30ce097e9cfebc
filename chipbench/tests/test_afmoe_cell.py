"""The cell ``serve-trinity-large-mixlen32k-r80`` and what it brought:
found by name with no edit, its configuration's widths (the catalog's
row; the cut is depth, experts held, vocabulary), the window's requests
as the mix states them (short and long prompts in one queue, nothing
shared), its label table on ops' texts and on programs recorded on the
chip, its bytes functions against the issue's arithmetic, and a CPU
rehearsal at a fixture of its own (``rehearse_afmoe.json``) — sound, and
with the control (the reference in a lower precision) judged as a served
stream is.  ``python -m pytest chipbench/tests -q``; not part of tier-1;
no number here is a device number.
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

from chipbench import afmoe_bytes, afmoe_trace                 # noqa: E402
from chipbench.readers import load_reader                     # noqa: E402
from chipbench.tests import by_name                           # noqa: E402

CELL = "serve-trinity-large-mixlen32k-r80"
CONFIG = "trinity-large-preview-5L-e32"
NEW = {"swa_decode_ms_per_step.serve": "itl_p95_ms",
       "swa_decode_roofline.serve": "itl_p95_ms",
       "swa_prefill_ms_per_chunk.serve": "ttft_p90_ms",
       "swa_prefill_roofline.serve": "ttft_p90_ms",
       "full_attention_ms_per_chunk.serve": "ttft_p90_ms",
       "sigmoid_gated_expert_ms_per_decode.serve": "itl_p95_ms",
       "sigmoid_gated_expert_roofline.serve": "itl_p95_ms",
       "window_blocks_resident_share.serve": "ttft_p90_ms"}
SHAPE_FREE = (
    "device_idle_share.serve", "decode_step_ms.serve",
    "batch_occupancy.serve", "queue_wait_p90_ms.serve",
    "prefill_p90_ms.serve", "front_overhead_p90_ms.serve",
    "decode_pass_ms.serve", "prefill_pass_share.serve",
    "engine_host_ms_per_pass.serve", "decode_program_ms.serve",
    "chunk_program_ms.serve", "loop_host_ms_per_pass.serve",
    "device_starved_share.serve", "block_hunt_ms_per_pass.serve",
    "emit_ms_per_pass.serve", "loop_unaccounted_share.serve")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
FULL_POOL, WINDOW_POOL = (4097, 64, 1024), (4 * 2625, 64, 1024)


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def test_cell_is_found_by_name_with_its_files():
    bench = load("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == (CONFIG, "mixlen32k-r80")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cfg = load(*entry["file"].split("/"))
    mix = load("chipbench", "traffic", cell["traffic"] + ".json")
    assert mix["kind"] == "open_loop_http_afmoe"
    assert os.path.exists(os.path.join(ROOT, cfg["reference"]))
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"] == list(cfg["changed"]) == [
        "num_hidden_layers", "num_experts", "vocab_size", "num_dense_layers"]
    assert cfg["deployment"]["chips_sharing_a_layer"] == 8
    for key in ("published", "changed", "deployment", "assumed",
                "memory_arithmetic", "engine_note", "selection_bias_note"):
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
    catalog = {"global_attn_every_n_layers": 4, "head_dim": 128,
               "hidden_size": 3072, "intermediate_size": 12288,
               "load_balance_coeff": 5e-05,
               "max_position_embeddings": 262144,
               "moe_intermediate_size": 3072, "n_group": 1,
               "num_attention_heads": 48, "num_expert_groups": 1,
               "num_experts_per_tok": 4, "num_key_value_heads": 8,
               "num_limited_groups": 1, "num_shared_experts": 1,
               "rms_norm_eps": 1e-05, "rope_theta": 10000,
               "route_scale": 2.448, "sliding_window": 4096,
               "topk_group": 1}
    assert {k: cfg[k] for k in catalog} == catalog
    assert cfg["rope_scaling"] is None and cfg["score_func"] == "sigmoid"
    assert cfg["mup_enabled"] and cfg["route_norm"]
    # the pattern is kept whole as published; layers_held picks this chip's
    assert len(cfg["layer_types"]) == 60
    assert cfg["layer_types"] == (["sliding_attention"] * 3
                                  + ["full_attention"]) * 15
    assert cfg["layers_held"] == [0, 8, 9, 10, 11]
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["num_dense_layers"]) == (5, 32, 25024, 1)
    assert cfg["published"]["num_experts"] == 256
    assert cfg["published"]["vocab_size"] == 200192 == 8 * 25024


def test_the_mix_is_the_issues_traffic_and_the_window_is_built_as_stated():
    from chipbench.traffic_gen import chat_requests
    bench = load("BENCHMARK.json")
    mix = load("chipbench", "traffic", "mixlen32k-r80.json")
    cfg = load("chipbench", "configs", CONFIG + ".json")
    assert mix["prompt_len"] == {"lo": 128, "hi": 32768, "median": 3072,
                                 "sigma": 1.2}
    assert mix["output_len"] == {"lo": 32, "hi": 512, "median": 160,
                                 "sigma": 0.7}
    assert mix["max_total"] == 33280 == cfg["engine"]["max_seq"]
    assert mix["shared_heads"]["n"] == 0 and mix["lead_s"] == 6
    assert mix["checked_requests"] >= mix["checked_at_least"] >= 4
    assert mix["long_checked_at_least"] >= 2
    reqs = chat_requests(mix, bench["run_seconds"], 7, cfg["vocab_size"])
    window = [r for r in reqs if not r["lead"]]
    assert len(window) == round(mix["rate_per_s"] * bench["run_seconds"]) \
        >= 100
    assert all(r["head"] is None for r in reqs)
    lens = sorted(len(r["prompt"]) for r in window)
    assert lens[0] >= 128 and lens[-1] <= 32768
    past = [n for n in lens if n > 4096]
    # ~40 % of the prompts are longer than the window and hold ~83 % of
    # the prompt tokens; ~8 % are past 16 k; the mean is ~5.9 k
    assert 0.37 < len(past) / len(lens) < 0.43
    assert 0.78 < sum(past) / sum(lens) < 0.88
    assert 0.05 < sum(n > 16384 for n in lens) / len(lens) < 0.11
    assert 5200 < sum(lens) / len(lens) < 6600
    # at least two finished contexts past two windows can be checked
    assert sum(n > 2 * 4096 for n in lens) >= 10
    for r in window:
        assert 32 <= r["max_tokens"] <= 512
        assert len(r["prompt"]) + r["max_tokens"] <= 33280


def test_configuration_holds_the_published_widths():
    from chipbench.traffic.open_loop_http_afmoe import model_config
    from ray_tpu.models import hybrid
    config = load("chipbench", "configs", CONFIG + ".json")
    cfg, pub, held = model_config(config)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.dense_width, cfg.expert_width, cfg.shared_width,
            cfg.vocab_size) == (3072, 48, 8, 128, 12288, 3072, 3072, 25024)
    assert (cfg.n_experts, cfg.experts_per_token, held, cfg.routed_scale) \
        == (256, 4, (0, 32), 2.448)
    assert (cfg.n_window, cfg.n_attention, cfg.window, cfg.max_seq) \
        == (4, 1, 4096, 33280)
    assert cfg.kv_geometry == (1, 8, 128)
    assert cfg.window_geometry == (4, 8, 128, 4096)
    assert cfg.state_geometry is None and not cfg.tied_head
    assert pub["num_experts"] == 256 and len(pub["layer_types"]) == 5
    assert hybrid.WINDOW == "window_attention"


def test_bytes_functions_against_the_issues_arithmetic():
    from chipbench.traffic.open_loop_http_afmoe import model_config
    _, pub, _ = model_config(load("chipbench", "configs", CONFIG + ".json"))
    b = afmoe_bytes
    assert b.layers_of(pub, "sliding_attention") == 4
    assert b.layers_of(pub, "full_attention") == 1
    assert b.expert_layers(pub) == 4
    # K/V: 2 pools x 8 heads x 128 lanes x 2 B = 4,096 B a token and layer
    flops, nbytes = b.swa_decode_work(pub, 64, 10 * 65.0)
    assert nbytes == 4 * 650 * 64 * 4096        # 10 rows a window deep
    assert flops == 4 * 4 * 650 * 64 * 48 * 128
    assert b.least_seconds((flops, nbytes), PEAKS) == nbytes / 819e9
    # a chunk deep in a long prompt: 1,024 queries x 4,096 keys in window
    flops, nbytes = b.swa_prefill_work(pub, 5119.0, 1024 * 4096.0)
    assert flops == 4 * 4 * 1024 * 4096 * 48 * 128      # 0.41 TFLOP
    assert nbytes == 4 * 5119 * 4096
    assert b.least_seconds((flops, nbytes), PEAKS) == flops / 197e12
    # a held expert is 3 x 3072 x 3072 (28.3 M) in bfloat16
    one = 3 * 3072 * 3072 * 2
    got = b.gated_expert_bytes_per_decode(pub, 20.0)
    assert got == 20 * one + 4 * (one + 3072 * 256 * 2 + 256 * 4)
    obs = {"counters": {"decode_iterations": 4, "window_blocks_attended": 40,
                        "expert_touched_held_decode": 80, "chunk_passes": 2,
                        "window_chunk_keys": 9000}}
    assert b.per_decode(obs, "window_blocks_attended") == 10
    assert b.per_chunk(obs, "window_chunk_keys") == 4500
    assert b.touched_per_decode(obs) == 20
    assert b.touched_per_decode({"counters": {}}) is None


def test_labels_from_an_ops_text():
    engine = load("chipbench", "configs", CONFIG + ".json")["engine"]
    marks = afmoe_trace.marks_of(engine, FULL_POOL, WINDOW_POOL)
    lab = afmoe_trace.label_of
    swa = ('%tpu_custom_call.25 = f32[32,6,1024] custom-call(s32[1], s32[32],'
           ' s32[16640], f32[32,6,1024], bf16[10500,64,1024] %p, '
           'bf16[10500,64,1024] %q)')
    assert lab(swa, marks) == "swa_decode_attention"
    assert lab(swa.replace("10500", "4097"), marks) \
        == "full_decode_attention"
    window = ('%tpu_custom_call.45 = (f32[48,1,1024], f32[48,1,1024], '
              'f32[48,128,1024]) custom-call(s32[1], s32[2,1024], '
              'bf16[48,1024,128], bf16[1024,1024], bf16[8,128,1024])')
    assert lab(window, marks) == "mixer_swa_attention"
    assert lab(window.replace("s32[2,1024]", "s32[1,1024]"), marks) \
        == "mixer_full_attention"
    assert lab("%g = bf16[16,64,1024] gather(bf16[10500,64,1024] %p)",
               marks) == "swa_pool_ops"
    # the commit sees a pool flattened to [rows x block, width]
    assert lab("%f = bf16[672000,1024] fusion(bf16[672000,1024] %b, "
               "s32[1024] %i, bf16[1024,1024] %new)", marks) \
        == "swa_pool_ops"
    assert lab("%f = bf16[262208,1024] fusion(bf16[262208,1024] %b, "
               "s32[1024] %i, bf16[1024,1024] %new)", marks) \
        == "full_pool_ops"
    assert lab("%gmm.4 = bf16[128,6144] custom-call(bf16[128,3072] %x, "
               "bf16[32,3072,6144] %w)", marks) == "routed_experts"
    assert lab("%f = bf16[32,6144] fusion(bf16[32,3072] %x, "
               "bf16[3072,6144] %copy-done.3)", marks) == "shared_expert"
    assert lab("%f = bf16[1,1024,14336] fusion(bf16[1024,3072] %x, "
               "bf16[3072,14336] %params__layers___2___mixer____wqkv__.1)",
               marks) == "attention_proj"
    assert lab("%f = bf16[1024,24576] fusion(bf16[3072,24576] "
               "%params__layers___0___ffn____w_in__.1)", marks) \
        == "dense_mlp"
    assert lab("%t = f32[48,128,1024] fusion(f32[48,128,1024] %acc, "
               "f32[48,1,1024] %l)", marks) == "attention_walk"
    assert lab("%h = f32[1024,25024] fusion(bf16[3072,25024] "
               "%params__head__.1, bf16[1024,3072] %x)", marks) == "other"
    obs = {"scoped": {"jit_step": {"runs": 4, "label_seconds": {
        "swa_decode_attention": 0.002, "swa_pool_ops": 0.001,
        "routed_experts": 0.004, "shared_expert": 0.002}},
        "jit_chunk_fn": {"runs": 2, "label_seconds": {
            "mixer_swa_attention": 0.008, "swa_pool_ops": 0.001,
            "mixer_full_attention": 0.003, "full_pool_ops": 0.0005,
            "attention_walk": 0.001}}}}
    read = {name: load_reader(name).read for name in NEW}
    assert read["swa_decode_ms_per_step.serve"](obs) == 0.75
    assert read["sigmoid_gated_expert_ms_per_decode.serve"](obs) == 1.5
    # no published keys: the chunk readers cannot share the walk out
    assert read["swa_prefill_ms_per_chunk.serve"](obs) is None
    from chipbench.traffic.open_loop_http_afmoe import model_config
    _, pub, _ = model_config(load("chipbench", "configs", CONFIG + ".json"))
    full = {**obs, "published": pub, "peaks": PEAKS, "block_size": 64,
            "counters": {"decode_iterations": 4, "chunk_passes": 2,
                         "window_blocks_attended": 2600,
                         "expert_touched_held_decode": 40,
                         "window_chunk_keys": 10238,
                         "window_query_keys": 2 * 1024 * 4096,
                         "window_blocks_resident_sum": 300,
                         "window_blocks_one_table_sum": 1200}}
    assert abs(read["swa_prefill_ms_per_chunk.serve"](full)
               - (4.5 + 0.8 * 0.5)) < 1e-9
    assert abs(read["full_attention_ms_per_chunk.serve"](full)
               - (1.75 + 0.2 * 0.5)) < 1e-9
    assert read["window_blocks_resident_share.serve"](full) == 25.0
    share = read["swa_decode_roofline.serve"](full)
    assert abs(share - 100 * (4 * 650 * 64 * 4096 / 819e9) / 0.75e-3) < 1e-9
    for name in ("swa_prefill_roofline.serve",
                 "sigmoid_gated_expert_roofline.serve"):
        assert 0 < read[name](full) < 100


def test_the_metrics_read_programs_recorded_on_the_chip():
    """One ``jit_step`` and one ``jit_chunk_fn`` of the cell as traced
    on the chip, each op labelled from its full text there: the
    reduction gives the recorded sums, each attention layer's kernel is
    found once a layer in the decode step, and the texts the file keeps
    still get their labels from today's table."""
    from chipbench.scoped_trace import summarize
    rec = load("chipbench", "tests", "recorded_afmoe_trace.json")
    got = summarize(rec["rows"])
    for program, expect in rec["expect"].items():
        assert got[program]["runs"] == 1
        for label, ms in expect.items():
            assert abs(1e3 * got[program]["label_seconds"][label] - ms) \
                < 1e-6
    assert sum(1 for r in rec["rows"] if r[2] == "swa_decode_attention") == 4
    assert sum(1 for r in rec["rows"] if r[2] == "full_decode_attention") \
        == 1
    marks = afmoe_trace.marks_of(rec["engine"], tuple(rec["full_pool"]),
                                 tuple(rec["window_pool"]))
    for label, text in rec["texts"].items():
        assert afmoe_trace.label_of(text, marks) == label, label
    obs = {"scoped": got}
    want = rec["expect"]["jit_step"]["swa_decode_attention"] \
        + rec["expect"]["jit_step"]["swa_pool_ops"]
    assert abs(load_reader("swa_decode_ms_per_step.serve").read(obs)
               - want) < 1e-6


def _rehearse(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    for name in ("RAY_TPU_TRACING", "RAY_TPU_TRACE_DIR", "XLA_FLAGS"):
        env.pop(name, None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", CELL, "--seed", "3000000048", "--seconds", "4",
         "--trace", "1", "--rehearse"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def test_rehearsal_of_the_cell_at_its_own_fixture(tmp_path):
    line, _ = _rehearse(tmp_path)
    assert line["correct"] is False and line["metrics"] == {}
    assert line["rehearsal_verdict_not_a_result"] is True
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    for q in ("margin_p90", "margin_p99"):
        assert line["checks"][q]["value"] <= line["checks"][q]["limit"]
    assert line["checks"]["checked_requests"] == {"value": 4, "at_least": 4}
    assert line["checks"]["checked_past_two_windows"]["value"] >= 2
    assert line["notes"]["compiles_in_window"] == 0
    c = line["notes"]["counters"]
    # nothing is adopted; the window pool gives blocks back while rows
    # run, and holds less than one table a row would
    assert c["prefix_hit_tokens"] == 0
    assert c["window_blocks_returned"] > 0
    assert c["window_blocks_allocated"] == c["kv_blocks_allocated"] > 0
    assert c["window_blocks_resident_sum"] < c["window_blocks_one_table_sum"]
    assert c["window_query_keys"] < c["chunk_query_keys"]
    assert line["notes"]["window_blocks_held_at_window_ends"][1] == 0
    share = line["rehearsal_metrics_not_device_numbers"][
        "window_blocks_resident_share.serve"]["value"]
    assert 0 < share < 100


def test_controls_come_out_not_correct_through_the_check():
    """The control at a size a test can hold: the reference with float8
    e4m3 inputs to every product picks its own greedy tokens; judged as
    a served stream is (``judge``: two quantiles of the margins under
    the float32 reference) it is not correct at limits set as the cell's
    are, while bfloat16 products, the stated precision, pass them."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import afmoe as ref
    from chipbench.traffic.open_loop_http_afmoe import (make_params,
                                                        model_config)
    from chipbench.traffic.open_loop_http_nemotron_h import judge
    fixture = load("chipbench", "tests", "rehearse_afmoe.json")
    config = {**load("chipbench", "configs", CONFIG + ".json"),
              **fixture["config"]}
    cfg, pub, held = model_config(config)
    params = make_params(cfg, config, 3)
    tokens = np.random.default_rng(5).integers(0, cfg.vocab_size, 140)
    full = np.asarray(ref.logits(params, tokens, pub, held))

    def margins(**kw):
        pick = np.asarray(ref.logits(params, tokens, pub, held,
                                     **kw)).argmax(-1)
        return full.max(-1) - full[np.arange(len(pick)), pick]
    f8, stated = margins(round_to=jnp.float8_e4m3fn), \
        margins(round_to=jnp.bfloat16)
    # limits between the two readings, with room on both sides
    p90 = (np.quantile(stated, 0.9), np.quantile(f8, 0.9))
    p99 = (np.quantile(stated, 0.99), np.quantile(f8, 0.99))
    assert 2 * p90[0] < p90[1] / 2 and 2 * p99[0] < p99[1] / 2
    mix = {"margin_quantile": 90, "tie_tolerance": (2 * p90[0]
                                                    + p90[1] / 2) / 2,
           "tail_quantile": 99, "tail_tolerance": (2 * p99[0]
                                                   + p99[1] / 2) / 2}

    def correct(m):
        return all(v["value"] <= v["limit"] for v in judge(m, mix).values())
    assert correct(stated) and not correct(f8)


@pytest.mark.parametrize("sequences", [1, 4])
def test_seeded_values_and_the_balance_over_several_sequences(sequences):
    """``make_params``: the embedding at the configuration's standard
    deviation (``init_params`` draws it ``embedding_multiplier`` times
    smaller), every output norm's gain at ``post_norm_gain`` and no
    other norm's touched; the bias evens the load of ALL the balance
    sample's sequences together (the sample through the program's own
    layer function loads every router output alike)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.model import fold_seed
    from chipbench.traffic import open_loop_http_afmoe as kind
    from ray_tpu.models import hybrid
    from ray_tpu.ops.routed_experts import route
    fixture = load("chipbench", "tests", "rehearse_afmoe.json")
    config = {**load("chipbench", "configs", CONFIG + ".json"),
              **fixture["config"]}
    config["selection_bias"] = {"tokens": 256, "rounds": 100,
                                "sequences": sequences}
    cfg, _, held = kind.model_config(config)
    params = kind.make_params(cfg, config, 7)
    want = config["seeded_values"]
    assert abs(float(params["wte"].std()) - want["embedding_std"]) < 1e-3
    gains = [sub[name] for lp in params["layers"] for sub in lp.values()
             for name in ("norm", "post_norm") if name in sub]
    assert len(gains) == 4 * cfg.n_layers
    assert {round(float(g[0]), 6) for g in gains} == {
        1.0, round(want["post_norm_gain"], 6)}

    def loads(tree, key):
        """Every experts sublayer's assignments a router output, over
        ``sequences`` fresh sequences."""
        length = 256 // sequences
        ids = jax.random.randint(key, (sequences, length), 0, cfg.vocab_size)
        n_valid = jnp.full((sequences,), length, jnp.int32)
        tables = hybrid.rotary_tables(cfg, jnp.broadcast_to(
            jnp.arange(length), ids.shape))
        past = {hybrid.ATTENTION: hybrid.causal_attend(cfg),
                hybrid.WINDOW: (hybrid.causal_attend(cfg, cfg.window),
                                tables)}
        x, out = hybrid.embed(cfg, tree, ids), []
        for i, sub in cfg.sublayers:
            lp = tree["layers"][i][hybrid.slot_of(sub)]
            if sub == hybrid.EXPERTS:
                h = hybrid._rms_norm(x, lp["norm"], cfg.rms_eps)
                experts, _ = route(
                    h.reshape(-1, h.shape[-1]), lp["router"],
                    cfg.experts_per_token, lp["router_bias"],
                    cfg.routed_scale, eps=cfg.route_eps)
                out.append(np.bincount(np.asarray(experts).reshape(-1),
                                       minlength=cfg.n_experts))
            x = hybrid.block(cfg, sub, lp, x, past.get(sub), n_valid)[0]
        return np.stack(out)
    sample = loads(params, jax.random.PRNGKey(fold_seed(7, 5)))
    even = 256 * cfg.experts_per_token / cfg.n_experts
    assert np.abs(sample - even).max() <= 0.25 * even
