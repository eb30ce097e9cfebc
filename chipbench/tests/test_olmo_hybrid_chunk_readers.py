"""The ``olmo_hybrid`` cell's readers that follow a chunk into the fused
step+chunk program: each sums its label over ``jit_chunk_fn`` AND
``jit_step_chunk`` runs, reads a parent's trace (no fused program)
through the chunk program alone, and gives None with nothing to read;
``step_chunk_program_ms.serve`` times the fused program by its module.
``python -m pytest chipbench/tests -q``; not part of tier-1; no number
here is a device number.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench.readers import load_reader                     # noqa: E402
from chipbench.tests import by_name                           # noqa: E402

CELL = "serve-olmo-hybrid-doc3k-r80"
NEW = {"delta_window_ms_per_chunk.serve": "ttft_p90_ms",
       "delta_window_roofline.serve": "ttft_p90_ms",
       "head_window_attention_ms_per_chunk.serve": "ttft_p90_ms",
       "head_window_attention_roofline.serve": "ttft_p90_ms"}
# what PR 58 added beside them as ``linear_*`` twins of accepted metrics,
# folded into those by PR 59: the cell is listed under the originals
FOLDED = {"step_chunk_program_ms.serve": "itl_p95_ms",
          "step_chunk_pass_ms.serve": "itl_p95_ms",
          "step_chunk_pass_host_ms.serve": "itl_p95_ms",
          "step_chunk_pass_wait_ms.serve": "itl_p95_ms",
          "chunk_in_step_share.serve": "itl_p95_ms"}
RETIRED = ("delta_prefill_ms_per_chunk.serve", "delta_prefill_roofline.serve",
           "window_attention_ms_per_chunk.serve",
           "window_attention_roofline.serve",
           "linear_step_chunk_program_ms.serve",
           "linear_step_chunk_pass_ms.serve",
           "linear_step_chunk_pass_host_ms.serve",
           "linear_step_chunk_pass_wait_ms.serve",
           "linear_chunk_in_step_share.serve")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CHUNK = {"runs": 2, "label_seconds": {"mixer_linear_attention": 0.03,
                                      "window_attention": 0.01}}
FUSED = {"runs": 6, "label_seconds": {"mixer_linear_attention": 0.074,
                                      "window_attention": 0.014,
                                      "delta_step": 0.006}}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def test_benchmark_json_gives_them_to_the_cell_and_nothing_reads_nothing():
    bench = by_name.bench()
    by_name.check_listed(bench, CELL, NEW)
    by_name.check_listed(bench, CELL, FOLDED)
    for name in NEW:
        assert load_reader(name).read({}) is None
    # one pass a window runs a chunk and then a step here since PR 58:
    # the metrics of that kind of pass no longer list the cell
    for name in ("chunk_then_step_pass_ms.serve",
                 "chunk_then_step_pass_host_ms.serve",
                 "chunk_then_step_pass_wait_ms.serve",
                 "chunk_program_ms.serve"):
        assert CELL not in by_name.metric(bench, name)["workloads"]
    names = {m["name"] for m in bench["per_layer"]}
    for name in RETIRED:
        assert name not in names and not by_name.has_reader(name)


def test_a_label_is_summed_over_both_programs_that_hold_a_chunk():
    pub = load("chipbench", "configs", "olmo-hybrid-7b-16L.json")
    both = {"scoped": {"jit_chunk_fn": CHUNK, "jit_step_chunk": FUSED,
                       "jit_step": {"runs": 90, "label_seconds": {
                           "mixer_linear_attention": 0.1}}}}
    read = lambda name, obs: load_reader(name).read(obs)    # noqa: E731
    assert read("delta_window_ms_per_chunk.serve", both) \
        == pytest.approx(1e3 * 0.104 / 8)
    assert read("head_window_attention_ms_per_chunk.serve", both) \
        == pytest.approx(1e3 * 0.024 / 8)
    # a parent's trace (no fused program): the chunk program alone
    parent = {"scoped": {"jit_chunk_fn": CHUNK}}
    assert read("delta_window_ms_per_chunk.serve", parent) \
        == pytest.approx(15.0)
    assert read("head_window_attention_ms_per_chunk.serve", parent) \
        == pytest.approx(5.0)
    # every chunk rode: the fused program's runs alone
    rode = {"scoped": {"jit_step_chunk": FUSED}}
    assert read("delta_window_ms_per_chunk.serve", rode) \
        == pytest.approx(1e3 * 0.074 / 6)
    work = {"published": pub, "peaks": PEAKS,
            "counters": {"chunk_passes": 8, "linear_chunk_tokens": 8192,
                         "chunk_keys": 32768, "chunk_query_keys": 8e6}}
    for new in ("delta_window_roofline.serve",
                "head_window_attention_roofline.serve"):
        assert 0 < read(new, {**parent, **work}) < 100
        assert 0 < read(new, {**both, **work}) < 100
        assert read(new, both) is None          # no counters, no peaks


def test_the_fused_program_is_timed_by_its_module():
    read = load_reader("step_chunk_program_ms.serve").read
    assert read({"trace": {"module_counts": {"jit_step_chunk": 4,
                                             "jit_step": 50},
                           "module_seconds": {"jit_step_chunk": 0.24,
                                              "jit_step": 0.6}}}) \
        == pytest.approx(60.0)
    assert read({"trace": {"module_counts": {"jit_step": 50},
                           "module_seconds": {"jit_step": 0.6}}}) is None
