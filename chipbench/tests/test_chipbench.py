"""The yardstick's own tests: ``python -m pytest chipbench/tests -q``.

Not part of the repo's tier-1 suite (nothing under ``tests/`` imports
this directory).  Everything here runs on the CPU; no number it sees is
a device number.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import stats, trace_reduce, traffic_gen   # noqa: E402
from chipbench.readers import load_reader                 # noqa: E402


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


# ------------------------------------------------------------- traffic

def test_traffic_same_work_for_every_seed():
    mix = load("chipbench", "traffic", "chat-r80-v2.json")
    every_a = traffic_gen.chat_requests(mix, 51, 1, 50304)
    every_b = traffic_gen.chat_requests(mix, 51, 3_000_000_017, 50304)
    assert every_a == traffic_gen.chat_requests(mix, 51, 1, 50304)
    # the lead-in (set-up) is the cycle continued backwards: due before 0
    for reqs in (every_a, every_b):
        lead = [r for r in reqs if r["lead"]]
        assert lead and all(-mix["lead_s"] <= r["due_s"] < 0 for r in lead)
        assert [r["id"] for r in reqs] == list(range(len(reqs)))
    a = [r for r in every_a if not r["lead"]]
    b = [r for r in every_b if not r["lead"]]
    assert len(a) == len(b) == round(mix["rate_per_s"] * 51)

    def shape(reqs):
        return (sorted((len(r["prompt"]), -1 if r["head"] is None
                        else r["head"]) for r in reqs),
                sorted(r["max_tokens"] for r in reqs))
    assert shape(a) == shape(b)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    assert [(r["due_s"], len(r["prompt"]), r["max_tokens"]) for r in a] == \
        [(r["due_s"], len(r["prompt"]), r["max_tokens"]) for r in b]
    for reqs in (a, b):
        due = [r["due_s"] for r in reqs]
        assert due == sorted(due) and 0 <= due[0] and due[-1] < 51
        assert all(len(r["prompt"]) + r["max_tokens"] <= mix["max_total"]
                   for r in reqs)
    # requests of one head really share it, and only it
    sh = mix["shared_heads"]
    by_head = {}
    for r in a:
        if r["head"] is not None:
            by_head.setdefault(r["head"], []).append(r["prompt"])
    assert len(by_head) == sh["n"]
    for prompts in by_head.values():
        assert len({tuple(p[:sh["len"]]) for p in prompts}) == 1
        assert len({tuple(p[sh["len"]:sh["len"] + 8])
                    for p in prompts}) == len(prompts)


def test_zipf_counts_and_grid():
    assert traffic_gen.zipf_counts(51, 4, 1.0) == [25, 12, 8, 6]
    grid = traffic_gen.lognormal_grid(101, 32, 768, 200, 0.8)
    assert grid == sorted(grid) and grid[50] == 200
    assert grid[0] == 32 and grid[-1] == 768


# ---------------------------------------------------------- arithmetic

def test_percentile_and_gaps_on_hand_made_stamps():
    xs = list(range(1, 101))                      # 1..100
    assert stats.percentile(xs, 50) == 51         # nearest rank of 0..99
    assert stats.percentile(xs, 90) == 90
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile([7], 95) == 7
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    assert stats.gaps([1.0, 1.5, 1.75, 3.0]) == [0.5, 0.25, 1.25]
    assert stats.gaps([2.0]) == []


def test_client_metrics_window_rules_on_hand_made_stamps():
    from chipbench.traffic.open_loop_http import client_metrics
    reqs = [{"id": 0, "due_s": -2.0, "max_tokens": 3, "lead": True},
            {"id": 1, "due_s": 1.0, "max_tokens": 3, "lead": False},
            {"id": 2, "due_s": 9.0, "max_tokens": 2, "lead": False},
            {"id": 3, "due_s": 9.5, "max_tokens": 2, "lead": False}]

    def rec(i, sent, stamps, ended="done"):
        return {"id": i, "due_s": reqs[i]["due_s"], "sent_s": sent,
                "token_s": stamps, "tokens": [7] * len(stamps),
                "ended": ended, "detail": ""}
    by_id = {0: rec(0, -1.99, [-0.5, 0.5, 1.5]),
             1: rec(1, 1.01, [2.0, 2.25, 2.75]),
             2: rec(2, 9.0, [9.8, 10.4]),          # second token in the drain
             3: rec(3, 9.5, [11.0], ended="unfinished")}
    m = client_metrics(reqs, by_id, 10.0)
    # due in the window only; from the DUE time, not the send time
    assert m["ttft"] == pytest.approx([1.0, 0.8, 1.5])
    # gaps that END inside the window, lead-in request included
    assert sorted(m["gaps"]) == pytest.approx([0.25, 0.5, 1.0, 1.0])
    assert m["tokens_in_window"] == 2 + 3 + 1
    assert [f[0] for f in m["failures"]] == [3]
    assert m["late"] == pytest.approx([0.01, 0.01, 0.0, 0.0])


# --------------------------------------------------------------- trace

def test_self_times_and_union_on_nested_events():
    ops = "XLA Ops"
    rows = [["/device:TPU:0", ops, "while", 0, 100],
            ["/device:TPU:0", ops, "a", 10, 30],
            ["/device:TPU:0", ops, "pallas:k", 50, 40],
            ["/device:TPU:0", ops, "b", 150, 50],
            ["/device:TPU:0", "XLA Modules", "jit_step", 0, 200],
            ["/device:TPU:0", "XLA Modules", "jit_step", 200, 200]]
    s = trace_reduce.summarize(rows, window_s=400e-9)
    assert s["busy_s"] == pytest.approx(150e-9)
    assert s["op_seconds"]["while"] == pytest.approx(30e-9)
    assert s["op_seconds"]["pallas:k"] == pytest.approx(40e-9)
    assert s["module_counts"]["jit_step"] == 2
    assert s["idle_gaps"][0][1] == pytest.approx(50e-9)
    assert "after while before b" in s["idle_gaps"][0][0]
    idle = load_reader("device_idle_share.train").read({"trace": s})
    assert idle == pytest.approx(100 * (1 - 150 / 400))
    assert trace_reduce.summarize([])["busy_s"] == 0.0
    assert load_reader("device_idle_share.train").read(
        {"trace": trace_reduce.summarize([])}) is None


def test_reducer_on_the_recorded_trace():
    rows = load("chipbench", "tests", "recorded_trace.json")["rows"]
    s = trace_reduce.summarize(rows)
    # two whole steps of jit__step, the device never idle between them
    assert s["n_devices"] == 1 and s["module_counts"] == {"jit__step": 2}
    assert s["busy_s"] == pytest.approx(0.3242, abs=1e-3)
    assert s["busy_s"] <= s["window_s"]
    # self times partition the busy time (ops on one core do not overlap)
    assert sum(s["op_seconds"].values()) == pytest.approx(s["busy_s"],
                                                          rel=1e-3)
    obs = {"trace": s, "trace_steps": 2, "step_wall_ms": 165.0}
    step = load_reader("step_device_ms.train").read(obs)
    flash = load_reader("flash_ms_per_step.train").read(obs)
    gap = load_reader("trainer_gap_ms.train").read(obs)
    assert step == pytest.approx(162.1, abs=0.5)
    assert flash == pytest.approx(50.4, abs=1.0)
    assert gap == pytest.approx(165.0 - step)
    bd = trace_reduce.breakdown(s)
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][0].startswith("pallas:")


def test_short_name():
    text = ('%tpu_custom_call.36 = (bf16[192,1024,64]{2,1,0:T(8,128)}, '
            'bf16[192,1024,64]{2,1,0}) custom-call(bf16[192] %b), '
            'custom_call_target="tpu_custom_call", '
            'frontend_attributes={kernel_metadata={}}')
    assert trace_reduce.short_name(text) == \
        "pallas:tpu_custom_call.36 (bf16[192,1024,64]"
    assert trace_reduce.short_name("jit__step(1178419516)") == "jit__step"
    assert trace_reduce.short_name(
        "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)") == "fusion.2 f32[8]"


def test_counter_readers():
    obs = {"window_s": 10.0,
           "counters": {"decode_iterations": 100, "occupancy_sum": 40.0,
                        "prefix_hit_tokens": 30, "prefix_lookup_tokens": 120}}
    assert load_reader("decode_step_ms.serve").read(obs) == 100.0
    assert load_reader("batch_occupancy.serve").read(obs) == 40.0
    assert load_reader("prefix_hit_rate.serve").read(obs) == 25.0
    for name in ("decode_step_ms.serve", "batch_occupancy.serve",
                 "prefix_hit_rate.serve", "flash_ms_per_step.train"):
        assert load_reader(name).read({"window_s": 1.0}) is None


def test_every_declared_metric_has_a_reader_and_every_cell_its_files():
    bench = load("BENCHMARK.json")
    for m in bench["per_layer"]:
        assert callable(load_reader(m["name"]).read)
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    for w in bench["workloads"]:
        cfg = load("chipbench", "configs", w["config"] + ".json")
        mix = load("chipbench", "traffic", w["traffic"] + ".json")
        # honestly reduced configurations list the keys they changed
        assert isinstance(cfg["reduced"], list) and "source" in cfg
        assert all(key in cfg for key in cfg["reduced"])
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "traffic", mix["kind"] + ".py"))


# ----------------------------------------------------------- reference

def test_reference_against_the_program_forward_at_a_tiny_size():
    import jax
    import numpy as np

    from chipbench.reference import gpt2 as ref
    from ray_tpu.models import gpt

    cfg = gpt.GPTConfig.tiny(dtype=jax.numpy.float32)
    params = gpt.init_params(cfg, jax.random.PRNGKey(3))
    # every bias and scale away from its init, so a dropped one shows
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree.unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape) for x, k in
        zip(leaves, keys)])
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 33), dtype=np.int32)
    want = np.asarray(ref.forward(params, tokens[:, :-1], cfg.n_heads))
    got = np.asarray(gpt.forward(params, tokens[:, :-1], cfg))
    # both float32 on the CPU: only summation order differs
    assert np.abs(want - got).max() < 2e-4
    assert float(ref.loss(params, tokens, cfg.n_heads)) == pytest.approx(
        float(gpt.loss_fn(params, {"tokens": tokens}, cfg)), abs=1e-4)
    # the teacher-forced margin of the argmax continuation is 0
    prompt = tokens[0, :20].tolist()
    nxt = int(want[0, 19].argmax())
    m = ref.margins(params, prompt, [nxt], cfg.n_heads, 32)
    assert m.shape == (1,) and float(m[0]) == 0.0


# ------------------------------------------------------ the entry point

def run_py(cwd, *args, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "chipbench", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


def test_off_chip_without_rehearse_exits_nonzero_and_prints_nothing():
    p = run_py(ROOT, "--workload", "train-124m-b16s1024", "--seed", "1",
               "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout == ""


def test_bare_checkout_exits_nonzero_and_prints_nothing(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_py(str(tmp_path), "--workload", "train-124m-b16s1024",
               "--seed", "1", "--seconds", "1", "--trace", "0",
               "--rehearse")
    assert p.returncode != 0 and p.stdout == ""


def test_a_cell_added_as_files_is_found_with_no_edit(tmp_path):
    """A new configuration, traffic mix, cell and per-layer metric: four
    new files and entries in BENCHMARK.json, no existing file edited."""
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "ray_tpu"), tmp_path / "ray_tpu")
    before = {p: open(p, "rb").read() for p in
              (str(x) for x in (tmp_path / "chipbench").rglob("*")
               if x.is_file())}
    cb = tmp_path / "chipbench"
    cfg = load("chipbench", "configs", "gpt2-124m.json")
    cfg["source"] = "https://example.org/another"
    (cb / "configs" / "another.json").write_text(json.dumps(cfg))
    mix = load("chipbench", "traffic", "b16s1024.json")
    (cb / "traffic" / "b8.json").write_text(json.dumps(mix))
    (cb / "layer_metrics" / "steps_counted.new.py").write_text(
        "def read(obs):\n    return float(obs['steps'])\n")
    bench = load("BENCHMARK.json")
    bench["configs"].append({"name": "another", "source": cfg["source"],
                             "file": "chipbench/configs/another.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "train-another-b8",
                               "config": "another", "traffic": "b8",
                               "chips": 1, "why": "test"})
    bench["end_to_end"][0]["workloads"].append("train-another-b8")
    bench["per_layer"][0]["workloads"].append("train-another-b8")
    assert bench["per_layer"][0]["name"] == "device_idle_share.train"
    bench["per_layer"].append({
        "name": "steps_counted.new", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "trainer",
        "moves": "train_tokens_per_s", "workloads": ["train-another-b8"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    p = run_py(str(tmp_path), "--workload", "train-another-b8", "--seed",
               "3000000019", "--seconds", "1", "--trace", "1", "--rehearse",
               env_extra={"JAX_COMPILATION_CACHE_DIR":
                          str(tmp_path / "cache")})
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    # a rehearsal is never a result: no metric, never correct
    assert line["correct"] is False and line["metrics"] == {}
    assert line["device"]["platform"] == "cpu"
    seen = line["rehearsal_metrics_not_device_numbers"]
    assert seen["steps_counted.new"]["value"] >= 2
    assert "device_idle_share.train" not in seen    # no device in the trace
    for path, content in before.items():
        assert open(path, "rb").read() == content
