"""The cell ``serve-nemotron3-nano-reason1k-r80`` and what it brought:
found by name with no edit, its configuration's widths and share, its
label table and readers on an excerpt recorded on the chip, its bytes
functions by hand, and a CPU rehearsal at a fixture of its own
(``rehearse_nemotron_h.json``).  ``python -m pytest chipbench/tests -q``;
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

from chipbench import nemotron_bytes, nemotron_trace      # noqa: E402
from chipbench.readers import load_reader                 # noqa: E402
from chipbench.tests import by_name                       # noqa: E402

CELL = "serve-nemotron3-nano-reason1k-r80"
CONFIG = "nemotron-3-nano-30b-a3b-13L-e64"
NEW = ("relu2_expert_ms_per_decode.serve", "relu2_expert_roofline.serve",
       "grouped_ssm_ms_per_decode.serve",
       "grouped_ssm_update_roofline.serve", "experts_touched_share.serve")
SHAPE_FREE = (
    "device_idle_share.serve", "decode_step_ms.serve",
    "batch_occupancy.serve", "queue_wait_p90_ms.serve",
    "prefill_p90_ms.serve", "front_overhead_p90_ms.serve",
    "decode_pass_ms.serve", "prefill_pass_share.serve",
    "engine_host_ms_per_pass.serve", "decode_program_ms.serve",
    "step_chunk_program_ms.serve")


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
    assert (cell["config"], cell["traffic"]) == (CONFIG, "reason1k-r80")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cfg = load(*entry["file"].split("/"))
    mix = load("chipbench", "traffic", cell["traffic"] + ".json")
    assert mix["kind"] == "open_loop_http_nemotron_h"
    assert os.path.exists(os.path.join(ROOT, cfg["reference"]))
    assert cfg["source"] == entry["source"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"]) == sorted(
        cfg["changed"]) == sorted(k for k in cfg["published"]
                                  if k != "parameters")
    assert cfg["deployment"]["chips_sharing_a_layer"] == 2
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"ttft_p90_ms", "itl_p95_ms", "serve_tokens_per_s",
                   "setup_s"}
    # by name: the cell may be listed under more, a metric may list more
    by_name.check_listed(bench, CELL, NEW, moves="itl_p95_ms")
    by_name.check_listed(bench, CELL, SHAPE_FREE)
    for name in NEW:
        assert callable(load_reader(name).read)
    # no lone chunk program runs in this cell's traced seconds since its
    # chunks ride the step (PR 46): the metric that reads one does not
    # list the cell (PR 59), the fused program's does
    assert CELL not in by_name.metric(bench, "chunk_program_ms.serve")[
        "workloads"]
    # traffic as the issue gives it; enough requests for a tail
    assert mix["prompt_len"] == {"lo": 64, "hi": 1024, "median": 256,
                                 "sigma": 0.8}
    assert mix["output_len"] == {"lo": 256, "hi": 2048, "median": 768,
                                 "sigma": 0.6}
    assert mix["max_total"] == 3072 == cfg["engine"]["max_seq"]
    assert mix["shared_heads"]["n"] == 0 and mix["order_seed"] == 0
    assert (mix["lead_s"], mix["trace_s"], mix["checked_requests"]) == (
        45, 4.0, 4)
    assert mix["drain_s"] >= 70
    assert round(mix["rate_per_s"] * bench["run_seconds"]) >= 80


def test_configuration_holds_the_published_widths_and_the_share():
    from chipbench.traffic.open_loop_http_nemotron_h import model_config
    config = load("chipbench", "configs", CONFIG + ".json")
    cfg, pub, held = model_config(config)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        2688, 32, 2, 128)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.conv_width, cfg.ssm_chunk) == (64, 64, 128, 8, 4, 128)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.expert_width,
            cfg.shared_width, cfg.routed_scale) == (128, 6, 1856, 3712, 2.5)
    assert "".join({"mamba": "M", "experts": "E", "attention": "*"}[k]
                   for k in cfg.layer_types) == "MEMEM*EMEMEM*"
    assert held == cfg.experts_held == (0, 64) and cfg.vocab_size == 65536
    assert pub["n_routed_experts"] == 128
    assert not (cfg.tied_head or cfg.gated_experts
                or cfg.experts_in_every_layer)
    assert config["engine"]["prefill_chunk"] == config["chunk_size"]
    assert cfg.kv_geometry == (2, 2, 128)
    assert cfg.state_geometry == (6, (3, 6144), (4096, 128))
    # every number of the catalog's config under its own key, but the
    # three cuts
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (13, 64, 65536)
    assert len(config["hybrid_override_pattern"]) == 52


def test_bytes_functions_by_hand():
    pub = published()
    # an expert: W_up [2688, 1856] + W_down [1856, 2688] in bf16
    one = 2 * 2688 * 1856 * 2
    assert one == 19_955_712
    common = 5 * ((2 * 2688 * 3712 + 2688 * 128) * 2 + 128 * 4)
    # every held expert of the 5 expert layers touched
    assert nemotron_bytes.relu2_expert_bytes_per_decode(pub, 5, 5 * 64) \
        == 320 * one + common
    # one row: 6 picks a layer, half of them held on average
    assert nemotron_bytes.relu2_expert_bytes_per_decode(pub, 5, 15) \
        == 15 * one + common
    # a row: 6 layers x (4096 x 128 x 4 + 3 x 6144 x 2), read + written
    assert nemotron_bytes.grouped_ssm_state_bytes_per_decode(pub, 1) \
        == 2 * 6 * (2_097_152 + 36_864)
    obs = {"counters": {"decode_iterations": 10, "row_steps": 250,
                        "expert_touched_held_decode": 2_800}}
    assert nemotron_bytes.mean_active_rows(obs) == 25.0
    assert nemotron_bytes.touched_per_decode(obs) == 280.0
    # a program without the counter (a parent commit): nothing to read
    assert nemotron_bytes.touched_per_decode(
        {"counters": {"decode_iterations": 10}}) is None


def test_labels_from_an_ops_text():
    marks = nemotron_trace.marks_of(published(), 64, 128)

    def lab(text):
        return nemotron_trace.label_of(text, marks)
    assert lab("%ragged-dot-none.9 = bf16[384,1920]{1,0} custom-call(%a, "
               "%b)") == "routed_experts"
    # a weight the compiler prefetched: the product names no parameter
    assert lab("%fusion.99 = (f32[64]{0}, bf16[64,2688]{1,0}) fusion("
               "bf16[64,2688]{1,0} %x, bf16[2688,3712]{1,0} %copy-done.13, "
               "bf16[3712,2688]{1,0} %copy-done.12)") == "shared_expert"
    assert lab("%ssd_step.6 = (f32[6,64,4096,128]{3,2,1,0}, f32[64,32,128]"
               "{2,1,0}) custom-call(%c, f32[6,64,4096,128]{3,2,1,0} %ssm.1)"
               ) == "grouped_ssm"
    assert lab("%b = f32[64,8,512]{2,1,0} broadcast(f32[64,8]{1,0} %c)") \
        == "grouped_ssm"
    assert lab("%m = bf16[64,1,6144]{2,0,1} fusion(bf16[64,3,6144] %s)") \
        == "grouped_ssm"
    assert lab("%d = f32[64,64]{1,0} fusion(bf16[64,1,10304]{2,0,1} %p)") \
        == "grouped_ssm"               # dt = softplus(...): [rows, heads]
    # the output projection consumes the mixer's f32 [rows, 1, 4096] and
    # is NOT the mixer's: the weight's shape comes first
    assert lab("%fusion.96 = (f32[64]{0}, bf16[64,2688]{1,0}) fusion("
               "bf16[4096,2688]{1,0} %custom-call.95, f32[64,1,4096]{2,0,1} "
               "%r)") == "projection"
    assert lab("%c = bf16[64,1,10304]{2,0,1} fusion(bf16[2688,10304]{0,1} "
               "%copy-done, bf16[64,2688] %x)") == "mixer_ssm_proj"
    assert lab("%q = bf16[64,1,4608]{2,0,1} fusion(bf16[64,2688] %x, "
               "bf16[672,4608]{1,0} %slice-done.3)") == "mixer_attention"
    assert lab("%sort = (f32[64,128]{1,0}, s32[64,128]{1,0}) sort(%a, %i)") \
        == "routed_experts"            # scores over the router's 128
    assert lab("%r = bf16[64,2688]{1,0} reduce(f32[64,6,2688]{2,1,0} %y)") \
        == "routed_experts"            # the top-6 parts add up
    assert lab("%paged_decode_attention.2 = f32[64,16,256]{2,1,0} "
               "custom-call(%t, bf16[24578,16,256] %k)") == "other"
    assert lab("%h = f32[64,65536]{1,0} fusion(bf16[64,2688] %x, "
               "bf16[2688,65536]{1,0} %params__head__.1)") == "other"
    # 4096 alone is no rule: the attention queries are 32 x 128 wide too
    assert lab("%x = bf16[64,4096]{1,0} fusion(%a)") == "other"


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
    # the numbers judged are two quantiles of the checked tokens' margins
    assert line["checks"]["margin_p90"]["value"] \
        == line["notes"]["margin_p90"] <= line["notes"]["tie_tolerance"]
    assert line["checks"]["margin_p99"]["value"] \
        == line["notes"]["margin_p99"] <= line["notes"]["tail_tolerance"]
    assert "worst_margin" not in line["checks"]
    assert line["notes"]["margin_p90"] <= line["notes"]["margin_p99"] \
        <= line["notes"]["worst_margin"]
    watch = line["notes"]["host_watch"]
    assert set(watch["gc"]) == {"gen0", "gen1", "gen2"}
    assert watch["heartbeat_late_total_s"] >= 0.0
    assert line["notes"]["traced_passes"]["n"] > 0
    assert line["notes"]["compiles_in_window"] == 0
    got = line["rehearsal_metrics_not_device_numbers"]
    assert 0 < got["experts_touched_share.serve"]["value"] <= 100
    c = line["notes"]["counters"]
    assert 0 < c["expert_touched_held_decode"] < c["expert_touched_held"] \
        <= c["expert_assignments_held"] < c["expert_assignments_total"]


def test_control_in_float8_reads_apart_by_the_quantile_the_check_takes():
    """The control of this cell's check at a size a test can hold: the
    reference with float8 e4m3 inputs to every product, judged as a
    served stream is — by the p90 of its tokens' margins under the
    float32 reference — reads several times what the stated precision
    (bfloat16) reads, so a limit between them holds one and fails the
    other.  (At the published widths and depth the MAXIMA of the two lie
    within a factor of three, read on the chip: why this kind does not
    take the maximum.  A model this small is too tame to show that.)"""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import hybrid
    from chipbench.reference import nemotron_h as ref
    from chipbench.traffic.open_loop_http_nemotron_h import model_config

    fixture = load("chipbench", "tests", "rehearse_nemotron_h.json")
    config = {**load("chipbench", "configs", CONFIG + ".json"),
              **fixture["config"]}
    cfg, pub, held = model_config(config)
    params = jax.jit(lambda k: hybrid.init_params(cfg, k))(
        jax.random.PRNGKey(3))
    tokens = np.random.default_rng(5).integers(0, cfg.vocab_size, 512)
    full = np.asarray(ref.logits(params, tokens, pub, held))

    def reading(dtype):
        pick = np.asarray(ref.logits(params, tokens, pub, held,
                                     round_to=dtype)).argmax(-1)
        margin = full.max(-1) - full[np.arange(len(pick)), pick]
        return float(np.quantile(margin, 0.9))
    stated, control = reading(jnp.bfloat16), reading(jnp.float8_e4m3fn)
    assert control > 5 * stated and control > 1e-3


def test_check_judges_the_bulk_and_the_tail():
    """Both quantiles are held to their limits: a stream whose bulk is
    sound and whose tail is not (a fault confined to a few tokens in a
    hundred) fails, which the p90 alone let through."""
    import numpy as np

    from chipbench.traffic.open_loop_http_nemotron_h import judge
    mix = {"margin_quantile": 90, "tie_tolerance": 0.3,
           "tail_quantile": 99, "tail_tolerance": 1.2}
    sound = np.concatenate([np.zeros(900), np.full(95, 0.1),
                            np.full(5, 2.0)])     # 0.5 %: a router's ties
    got = judge(sound, mix)
    assert got["margin_p90"]["limit"] == 0.3 \
        and got["margin_p90"]["value"] == pytest.approx(0.01)
    assert got["margin_p99"]["value"] == pytest.approx(0.1)
    faulty = np.concatenate([np.zeros(900), np.full(70, 0.1),
                             np.full(30, 2.0)])   # 3 % of the tokens wrong
    got = judge(faulty, mix)
    assert got["margin_p90"]["value"] <= 0.3 < 1.2 < got["margin_p99"][
        "value"]
    assert judge(np.zeros(0), mix)["margin_p99"]["value"] == 0.0


@pytest.fixture(scope="module")
def toy():
    import jax

    from ray_tpu.models import hybrid
    from chipbench.traffic.open_loop_http_nemotron_h import model_config
    fixture = load("chipbench", "tests", "rehearse_nemotron_h.json")
    config = {**load("chipbench", "configs", CONFIG + ".json"),
              **fixture["config"]}
    cfg, pub, held = model_config(config)
    params = jax.jit(lambda k: hybrid.init_params(cfg, k))(
        jax.random.PRNGKey(3))
    return config, cfg, pub, held, params


def test_selection_bias_is_balanced_and_nothing_else_changes(toy):
    """``make_params``' second step: over its own tokens every expert of
    every experts layer is chosen equally often (within the last step's
    reach), and only ``router_bias`` differs from the program's init."""
    import jax
    import numpy as np

    from ray_tpu.models import hybrid
    from chipbench.reference import nemotron_h as ref
    from chipbench.traffic.open_loop_http_nemotron_h import \
        balance_selection_bias
    config, cfg, pub, held, params = toy
    key = jax.random.PRNGKey(11)
    balanced = balance_selection_bias(cfg, params, key, tokens=512,
                                      rounds=200)
    same = jax.tree.map(lambda a, b: bool((a == b).all()), params, balanced)
    moved = [path for path, eq in jax.tree_util.tree_leaves_with_path(same)
             if not eq]
    assert moved and all("router_bias" in jax.tree_util.keystr(p)
                         for p in moved)
    ids = np.asarray(jax.random.randint(key, (1, 512), 0, cfg.vocab_size))[0]

    def loads(tree):
        chosen = []
        ref.logits(tree, ids, pub, held, chosen=chosen)
        return [np.bincount(np.asarray(c).reshape(-1),
                            minlength=cfg.n_experts) for c in chosen]
    even = 512 * cfg.experts_per_token / cfg.n_experts
    before, after = loads(params), loads(balanced)
    assert len(after) == cfg.layer_types.count(hybrid.EXPERTS)
    for b, a in zip(before, after):
        assert abs(a - even).max() <= 0.05 * even < abs(b - even).max()


def test_forced_routing_follows_the_choices_it_is_given(toy):
    """``logits(.., chosen=)`` records each experts layer's choices and
    ``forced=`` replays them: its own choices change nothing; another
    run's choices change the logits."""
    import numpy as np

    from chipbench.reference import nemotron_h as ref
    config, cfg, pub, held, params = toy
    tokens = np.random.default_rng(5).integers(0, cfg.vocab_size, 64)
    chosen = []
    full = np.asarray(ref.logits(params, tokens, pub, held, chosen=chosen))
    assert [np.asarray(c).shape for c in chosen] == [(64, 3)] * 2
    again = np.asarray(ref.logits(params, tokens, pub, held, forced=chosen))
    np.testing.assert_array_equal(again, full)
    other = [(np.asarray(c) + 1) % cfg.n_experts for c in chosen]
    assert np.abs(np.asarray(ref.logits(
        params, tokens, pub, held, forced=other)) - full).max() > 1e-4


def test_host_watch_counts_collections_and_reports_without_a_proc():
    import gc
    import time

    from chipbench.host_watch import HostWatch
    watch = HostWatch(tick_s=0.001, late_s=0.05)
    watch.start(time.monotonic())
    gc.collect()
    time.sleep(0.02)
    got = watch.report()
    assert got["gc"]["gen2"]["n"] >= 1
    assert got["gc"]["gen2"]["longest_s"] <= got["gc"]["gen2"]["total_s"]
    assert all(g["late_s"] > 0.05 for g in got["heartbeat_gaps_over_100ms"])
    assert watch._collected not in gc.callbacks
