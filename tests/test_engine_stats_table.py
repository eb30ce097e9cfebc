"""What the serving engine reports of itself (ISSUE 47): the keys and
types of ``stats()``, the ``/metrics`` series in order, and what a
replica and a fleet return of several engines.  The literals below were
recorded at the commit before the table (``ray_tpu/serve/engine_stats.py``)
existed: they hold the table to what every reader already reads (the
window pool's rows, marked, are ISSUE 48's; the state snapshots',
ISSUE 52's).

Everything runs on CPU with the ``tiny`` configurations of both families.
"""

import time

import jax
import jax.numpy as jnp
import pytest

from ray_tpu import serve
from ray_tpu.inference import (EngineConfig, InferenceEngine,
                               build_gpt_deployment, metrics_snapshot)
from ray_tpu.inference import serving
from ray_tpu.metrics import render_prometheus
from ray_tpu.models import gpt, hybrid
from ray_tpu.serve import engine_stats, fleet
from ray_tpu.serve.fleet import FleetConfig, ingress

GPT = gpt.GPTConfig.tiny(dtype=jnp.float32, max_seq=64)
HYBRID = hybrid.HybridConfig.tiny()

# ------------------------------------------- (a) recorded at the parent

STATS_TYPES = {
    "max_slots": int, "waiting_requests": int, "waiting_interactive": int,
    "stopped": bool, "draining": bool, "batch_occupancy": float,
    "generated_tokens": int, "requests_completed": int,
    "decode_iterations": int, "admissions": int, "chunk_passes": int,
    "chunks_in_step": int, "prefill_tokens": int,
    "kv_blocks_attended": int, "kv_blocks_tabled": int, "chunk_keys": int,
    "chunk_query_keys": int, "linear_state_rows_advanced": int,
    "linear_chunk_tokens": int, "tokens_greedy_on_device": int,
    "tokens_sampled": int, "fetch_bytes": int, "loop_account": dict,
    "tokens_per_step": float, "row_steps": int, "row_tokens": int,
    "speculate": type(None), "spec_drafted_tokens": int,
    "spec_accepted_tokens": int, "spec_accept_rate": float,
    "spec_passes": int, "mesh_devices": int, "mesh_axes": dict,
    "tp_shards": int, "weight_bytes": int,
    "weight_bytes_cast_per_pass": int, "active_slots": int,
    "free_slots": int, "cache_bytes": int, "cache_bytes_per_device": int,
    "block_size": int, "blocks_total": int, "blocks_per_device": int,
    "blocks_free": int, "block_utilization": float,
    "prefix_cached_blocks": int, "prefix_hit_tokens": int,
    "prefix_blocks_adopted": int, "prefix_lookup_tokens": int,
    "prefix_hit_rate": float, "preemptions": int,
    "peak_active_requests": int, "state_bytes": int,
    "state_rows_in_use": int, "expert_assignments_held": int,
    "expert_assignments_total": int, "expert_load_max": int,
    "expert_touched_held": int, "expert_touched_held_decode": int,
    "pool_generation": int,
    # the second group of K/V layers (ISSUE 48): rows added to the table
    "window_blocks_total": int, "window_blocks_held": int,
    "window_blocks_allocated": int, "window_blocks_returned": int,
    "kv_blocks_allocated": int, "window_blocks_resident_sum": int,
    "window_blocks_one_table_sum": int, "window_blocks_attended": int,
    "window_chunk_keys": int, "window_query_keys": int,
    # what the latent window kernel walked, tile by tile (ISSUE 49)
    "chunk_pairs_walked": int, "chunk_tiles_plain": int,
    "chunk_tiles_diagonal": int,
    # the key blocks the head-by-head window form walked (ISSUE 53)
    "chunk_key_blocks_walked": int,
    # the recurrent state kept at block ends (ISSUE 52)
    "state_snapshot_bytes": int, "state_snapshots_written": int,
    "state_snapshots_restored": int,
    # the loop one pass ahead of what it has read (ISSUE 55)
    "passes_launched_ahead": int, "passes_drained": int,
}
LOOP_ACCOUNT_TYPES = {
    "ns": dict, "starved_ns": dict, "count": dict, "unaccounted_ns": int,
    "unaccounted_starved_ns": int, "passes": int, "t_made_ns": int,
    "t_ns": int,
    # ISSUE 54: the account by kind of pass
    "by_kind": dict, "gaps": dict,
    # ISSUE 55: why a pass in flight was landed early, by reason
    "drained_by": dict, "unit_t_ns": int,
}

SERIES = [
    ("ray_tpu_inference_active_slots", "gauge",
     "Cache slots currently decoding, per engine"),
    ("ray_tpu_inference_waiting_requests", "gauge",
     "Requests queued for a free slot, per engine"),
    ("ray_tpu_inference_batch_occupancy_ratio", "gauge",
     "Mean active/max_slots per decode iteration"),
    ("ray_tpu_inference_generated_tokens_total", "counter",
     "Tokens generated since engine start"),
    ("ray_tpu_inference_requests_completed_total", "counter",
     "Generation requests completed since engine start"),
    ("ray_tpu_inference_block_utilization_ratio", "gauge",
     "Paged KV pool blocks in use / usable blocks"),
    ("ray_tpu_inference_prefix_hit_rate", "gauge",
     "Prompt tokens adopted from the radix prefix cache / prompt "
     "tokens seen"),
    ("ray_tpu_inference_prefix_cached_blocks", "gauge",
     "Blocks held by the radix prefix index"),
    ("ray_tpu_inference_prefix_hit_tokens_total", "counter",
     "Prompt tokens served from blocks adopted from the radix prefix "
     "index (no prefill program ran them)"),
    ("ray_tpu_inference_prefix_blocks_adopted_total", "counter",
     "Blocks taken over from the radix prefix index by admissions and "
     "re-matches"),
    ("ray_tpu_inference_preemptions_total", "counter",
     "Requests requeued by block-pressure preemption"),
    ("ray_tpu_inference_admissions_total", "counter",
     "Requests given a cache row (a preempted request counts again)"),
    ("ray_tpu_inference_chunk_passes_total", "counter",
     "Prefill chunks run, by the chunk program or inside a decode "
     "step"),
    ("ray_tpu_inference_chunks_in_step_total", "counter",
     "Prefill chunks that ran inside a decode step's program (one "
     "read of the weights for both)"),
    ("ray_tpu_inference_prefill_tokens_total", "counter",
     "Prompt tokens run through a prefill program (prefix-cache "
     "hits excluded)"),
    ("ray_tpu_inference_kv_blocks_attended_total", "counter",
     "KV blocks holding a key of a live row, summed over one-token "
     "decode passes (read once a pool and layer)"),
    ("ray_tpu_inference_kv_blocks_tabled_total", "counter",
     "Block-table entries of all rows, summed over one-token decode "
     "passes (what a whole-table gather reads)"),
    ("ray_tpu_inference_chunk_keys_total", "counter",
     "Keys in reach of prefill chunks' windows (position + tokens, "
     "summed over chunk passes)"),
    ("ray_tpu_inference_chunk_query_keys_total", "counter",
     "(query, key) pairs under the causal mask, summed over prefill "
     "chunk passes"),
    ("ray_tpu_inference_chunk_key_blocks_walked_total", "counter",
     "Key blocks the head-by-head window form walked, a layer each (up "
     "to the block of the chunk's last real key), summed over prefill "
     "chunk passes"),
    ("ray_tpu_inference_chunk_pairs_walked_total", "counter",
     "(query, key) pairs of the score tiles the latent window kernel did "
     "not skip, summed over prefill chunk passes"),
    ("ray_tpu_inference_chunk_tiles_plain_total", "counter",
     "Score tiles the latent window kernel ran with no mask (every key "
     "before every query), a head and layer, summed over prefill chunk "
     "passes"),
    ("ray_tpu_inference_chunk_tiles_diagonal_total", "counter",
     "Score tiles the latent window kernel ran under the causal mask (the "
     "edge crosses them), a head and layer, summed over prefill chunk "
     "passes"),
    ("ray_tpu_inference_linear_state_rows_advanced_total", "counter",
     "Rows whose linear-attention matrix state a one-token decode "
     "pass wrote, summed over passes"),
    ("ray_tpu_inference_linear_chunk_tokens_total", "counter",
     "Real prompt tokens through the window form of the delta rule, "
     "summed over prefill chunk passes"),
    ("ray_tpu_inference_tokens_greedy_on_device_total", "counter",
     "Decode and first tokens chosen by a serving program's own "
     "argmax (a pass fetches the integers, not the logits)"),
    ("ray_tpu_inference_tokens_sampled_total", "counter",
     "Decode and first tokens chosen by a dispatch of their own on "
     "the logits (temperature > 0, a full-width prefill's first "
     "token, a speculative pass)"),
    ("ray_tpu_inference_fetch_bytes_total", "counter",
     "Bytes the engine's loop fetched from the device"),
    ("ray_tpu_inference_tokens_per_step", "gauge",
     "Tokens emitted per compiled decode/verify call (speculative "
     "decoding pushes this above 1)"),
    ("ray_tpu_inference_spec_accept_rate", "gauge",
     "Drafted tokens accepted by the verify pass / drafted tokens "
     "offered"),
    ("ray_tpu_inference_spec_accepted_tokens_total", "counter",
     "Drafted tokens accepted since engine start"),
    ("ray_tpu_inference_mesh_devices", "gauge",
     "Devices in the engine's mesh (1 = unmeshed single device)"),
    ("ray_tpu_inference_tp_shards", "gauge",
     "Tensor-parallel shards of the paged KV pool's heads dim "
     "(block counts are per-device AND global — heads are what's "
     "split)"),
    ("ray_tpu_inference_state_bytes", "gauge",
     "Bytes of the per-row recurrent-state pool (0 = the model "
     "keeps K/V only)"),
    ("ray_tpu_inference_state_rows_in_use", "gauge",
     "Decode rows holding a recurrent state"),
    ("ray_tpu_inference_state_snapshot_bytes", "gauge",
     "Bytes of the recurrent-state snapshots kept with the paged KV "
     "pool's blocks (0 = the state has no snapshot form)"),
    ("ray_tpu_inference_state_snapshots_written_total", "counter",
     "Blocks closed with the recurrent state at their end kept"),
    ("ray_tpu_inference_state_snapshots_restored_total", "counter",
     "Requests whose row took its recurrent state from an adopted "
     "block's snapshot"),
    ("ray_tpu_inference_window_blocks_held", "gauge",
     "Blocks of the window-attention layers' pool held by rows"),
    ("ray_tpu_inference_window_blocks_allocated_total", "counter",
     "Blocks of the window-attention layers' pool handed to rows"),
    ("ray_tpu_inference_window_blocks_returned_total", "counter",
     "Blocks of the window-attention layers' pool given back behind "
     "the window by rows still running"),
    ("ray_tpu_inference_kv_blocks_allocated_total", "counter",
     "Blocks of the (full-attention layers') paged KV pool handed to "
     "rows, adopted ones not counted"),
    ("ray_tpu_inference_window_blocks_attended_total", "counter",
     "Blocks holding a key inside a live row's window, summed over "
     "one-token decode passes (read once a pool and window layer)"),
    ("ray_tpu_inference_expert_assignments_held_total", "counter",
     "(token, expert) assignments routed to experts held here"),
    ("ray_tpu_inference_expert_assignments_total", "counter",
     "(token, expert) assignments routed to any expert"),
    ("ray_tpu_inference_expert_load_max_total", "counter",
     "Assignments of the busiest held expert, summed over layers "
     "and passes"),
    ("ray_tpu_inference_expert_touched_held_total", "counter",
     "Held experts with at least one assignment, summed over "
     "expert layers and passes"),
    ("ray_tpu_inference_expert_touched_held_decode_total", "counter",
     "Held experts with at least one assignment, summed over "
     "expert layers and decode steps (no prefill chunk)"),
    ("ray_tpu_inference_weight_bytes", "gauge",
     "Bytes of the parameter tree the programs are handed"),
    ("ray_tpu_inference_weight_bytes_cast_per_pass", "gauge",
     "Bytes of weights a program casts to its compute dtype every "
     "pass (0 = each is stored in it)"),
    ("ray_tpu_inference_passes_launched_ahead_total", "counter",
     "Passes dispatched while the pass before them was still unread "
     "(queued behind it on the device)"),
    ("ray_tpu_inference_passes_drained_total", "counter",
     "Passes in flight that were read before the next could be launched "
     "(a sampled row, a preemption, a cancelled row, a cross-thread op, "
     "a full-width prefill, shutdown)"),
    ("ray_tpu_inference_loop_seconds_total", "counter",
     "The engine loop thread's wall time by phase (self time; "
     "`wait` is the wait for the device, `parked` an engine with no "
     "work, `unaccounted` what no span site covers)"),
    ("ray_tpu_inference_loop_starved_seconds_total", "counter",
     "The part of each phase's time during which the loop had no "
     "program in flight on the device"),
]

FLEET_STATS_TYPES = {
    "max_slots": int, "active_slots": int, "waiting_requests": int,
    "waiting_interactive": int, "blocks_total": int, "blocks_free": int,
    "block_utilization": float, "mesh_devices": int, "tp_shards": int,
    "prefix_hit_tokens": int, "prefix_lookup_tokens": int,
    "prefix_hit_rate": float, "spec_drafted_tokens": int,
    "spec_accepted_tokens": int, "spec_accept_rate": float,
    "tokens_per_step": float, "models": list, "stopped": bool,
    "draining": bool,
}
FLEET_SNAPSHOT_TYPES = {
    "replicas": int, "total_slots": int, "active_slots": int,
    "engine_waiting": int, "ingress_queued": int, "occupancy": float,
    "total_blocks": int, "block_utilization": float, "mesh_devices": int,
    "tp_shards": int, "prefix_hit_rate": float,
    "spec_drafted_tokens": int, "spec_accepted_tokens": int,
    "spec_accept_rate": float, "admitted": int, "shed": int,
    "rejected": int, "completed": int, "errored": int, "cancelled": int,
    "resumed_failure": int, "resumed_scale_down": int, "drained": int,
    "drain_timeout": int, "replayed_tokens": int, "resumed": int,
}


def _types(d):
    return {k: type(v) for k, v in d.items()}


def _gpt_engine(name=None, **kw):
    ec = dict(max_slots=4, kv_block_size=8, prefill_chunk=8)
    return InferenceEngine(gpt.init_params(GPT, jax.random.PRNGKey(0)), GPT,
                           EngineConfig(**{**ec, **kw}), name=name)


def _hybrid_engine(name=None):
    return InferenceEngine(
        hybrid.init_params(HYBRID, jax.random.PRNGKey(0)), HYBRID,
        EngineConfig(max_slots=3, max_seq=96, n_blocks=14, kv_block_size=8,
                     prefill_chunk=8), name=name)


def _stats_of(make):
    eng = make()
    try:
        eng.generate([1, 2, 3], max_new=4, timeout=170)
        return eng.stats()
    finally:
        eng.shutdown()


def _series_with_two_engines():
    a, b = _gpt_engine(name="table-a"), _hybrid_engine(name="table-b")
    try:
        a.generate([1, 2, 3], max_new=2, timeout=170)
        snap = metrics_snapshot()
    finally:
        a.shutdown()
        b.shutdown()
    for name, _, _, series in snap:
        labels = [key for key in series
                  if dict(key)["engine"] in ("table-a", "table-b")]
        # the engine's name first, then (for the per-phase two) the phase
        assert {key[0] for key in labels} == {("engine", "table-a"),
                                              ("engine", "table-b")}, name
        assert all(isinstance(series[key], float) for key in labels)
    return [row[:3] for row in snap]


def _fleet_types(which):
    dep = build_gpt_deployment(cfg=GPT, engine_cfg=EngineConfig(max_slots=4),
                               seed=0, num_replicas=2)
    try:
        handle = serve.run(dep, use_actors=False)
        f = fleet.enable("v1", FleetConfig(rate=500, burst=64))
        handle.remote({"prompt": [1, 2], "max_tokens": 2}).result(timeout=170)
        if which == "fleet_stats":
            user = serve.get_handle("v1")._state.replicas[0].impl._user
            return _types(user.fleet_stats())
        return _types(f.fleet_snapshot())
    finally:
        serve.shutdown()


@pytest.mark.parametrize("read, recorded", [
    (lambda: _types(_stats_of(_gpt_engine)), STATS_TYPES),
    (lambda: _types(_stats_of(_hybrid_engine)), STATS_TYPES),
    (lambda: _types(_stats_of(_gpt_engine)["loop_account"]),
     LOOP_ACCOUNT_TYPES),
    (_series_with_two_engines, SERIES),
    (lambda: _fleet_types("fleet_stats"), FLEET_STATS_TYPES),
    (lambda: _fleet_types("fleet_snapshot"), FLEET_SNAPSHOT_TYPES),
], ids=["stats_gpt", "stats_hybrid", "loop_account", "metrics_series",
        "fleet_stats", "fleet_snapshot"])
def test_reported_as_recorded_before_the_table(read, recorded):
    assert read() == recorded


@pytest.mark.parametrize("make", [_gpt_engine, _hybrid_engine],
                         ids=["gpt", "hybrid"])
def test_latent_window_counters_read_zero_without_latent_layers(make):
    """The window kernel's three counters (ISSUE 49) are a latent
    layout's: a model that keeps K/V heads ran chunks and counted
    none."""
    st = _stats_of(make)
    assert st["chunk_passes"] > 0 and st["chunk_query_keys"] > 0
    assert (st["chunk_pairs_walked"], st["chunk_tiles_plain"],
            st["chunk_tiles_diagonal"]) == (0, 0, 0)
    # ... and their tables are one key block, attended packed: no key
    # block was walked either (ISSUE 53)
    assert st["chunk_key_blocks_walked"] == 0


def test_no_engine_renders_one_zero_row_a_series():
    """With no live engine every series keeps one ``engine="none"`` row
    (the exposition's names do not come and go with the engines)."""
    from ray_tpu.inference import engine as engine_mod
    with engine_mod._registry_lock:
        held = dict(engine_mod._ENGINES)
        engine_mod._ENGINES.clear()
    try:
        snap = metrics_snapshot()
    finally:
        with engine_mod._registry_lock:
            engine_mod._ENGINES.update(held)
    assert [row[:3] for row in snap] == SERIES
    assert all(row[3] == {(("engine", "none"),): 0.0} for row in snap)
    text = render_prometheus(snap)
    assert text.count('{engine="none"} 0') == len(SERIES)


# --------------------------------------------------- (b) a case a table row

@pytest.fixture(scope="module")
def reported():
    """One engine's ``stats()`` and the exposition's text with it live."""
    eng = _gpt_engine(name="table-rows")
    try:
        eng.generate([1, 2, 3], max_new=4, timeout=170)
        return eng.stats(), render_prometheus(metrics_snapshot())
    finally:
        eng.shutdown()


@pytest.mark.parametrize("row", engine_stats.ROWS, ids=lambda row: row.key)
def test_row(row, reported):
    st, text = reported
    assert (row.key in st) == row.reported
    assert row.kind in (engine_stats.COUNTER, engine_stats.GAUGE,
                        engine_stats.RATIO)
    assert row.over in (None, engine_stats.SUM, engine_stats.MAX)
    if row.kind == engine_stats.RATIO:
        # its operands are rows, and reduce: a ratio over several
        # engines is the ratio of their sums
        for operand in filter(None, (row.num, row.less, row.den)):
            assert engine_stats.ROW[operand].over == engine_stats.SUM
        assert isinstance(st[row.key], float)
    if row.kind == engine_stats.COUNTER:
        assert row.key in engine_stats.Counters.__slots__
    if row.metric is None:
        assert not row.help
        return
    assert row.help
    assert [r.metric for r in engine_stats.ROWS].count(row.metric) == 1
    assert row.metric.startswith("ray_tpu_inference_")
    assert row.metric.endswith("_total") == (
        row.kind == engine_stats.COUNTER)
    assert f"# HELP {row.metric} {row.help}\n" in text
    assert f"# TYPE {row.metric} {row.metric_kind}\n" in text
    assert f'{row.metric}{{engine="table-rows"}} ' in text


def test_table_is_what_stats_reports(reported):
    st, _ = reported
    assert set(st) == {r.key for r in engine_stats.ROWS if r.reported}
    assert set(st) == set(STATS_TYPES)


# ------------------------------------- (c) several engines: the reduction

RATIOS = [r for r in engine_stats.ROWS if r.kind == engine_stats.RATIO]


@pytest.fixture(scope="module")
def two_engines():
    """A replica that holds two engines with different counts (one has
    served a prompt twice: prefix hits, cached blocks; the other one
    short prompt once) -> (its ``fleet_stats()``, the engines'
    ``stats()``)."""
    server = serving.GPTServer(
        cfg=GPT, engine_cfg=EngineConfig(max_slots=4, kv_block_size=8,
                                         prefill_chunk=8, speculate="ngram"),
        variants={"a": 0, "b": 1}, multiplex_capacity=2)
    try:
        long_ = [5, 6, 7, 8] * 6
        for _ in range(2):
            server({"prompt": long_, "max_tokens": 12, "model": "a"})
        server({"prompt": [9, 2], "max_tokens": 3, "model": "b"})
        return server.fleet_stats(), [e.stats() for e in server._engines()]
    finally:
        server.teardown()


@pytest.mark.parametrize("key", serving._FLEET_STATS)
def test_fleet_stats_is_the_tables_reduction(key, two_engines):
    fleet_st, stats = two_engines
    assert len(stats) == 2
    row = engine_stats.ROW[key]
    if row.kind == engine_stats.RATIO:
        num = sum(s[row.num] - (s[row.less] if row.less else 0)
                  for s in stats)
        den = sum(s[row.den] for s in stats)
        want = num / den if den else 0.0
    elif row.over == engine_stats.MAX:
        want = max(s[key] for s in stats)
    else:
        want = sum(s[key] for s in stats)
    assert fleet_st[key] == want
    assert type(fleet_st[key]) is type(want)
    assert engine_stats.reduce(stats, [key]) == {key: want}


def test_the_two_engines_differ(two_engines):
    """What makes the cases above tell a sum from a mean."""
    _, (a, b) = two_engines
    assert a["prefix_hit_rate"] != b["prefix_hit_rate"]
    assert a["block_utilization"] != b["block_utilization"]
    assert a["generated_tokens"] != b["generated_tokens"]


@pytest.mark.parametrize("row", RATIOS, ids=lambda row: row.key)
def test_ratio_of_the_sums_not_mean_of_the_ratios(row):
    one = {row.num: 1, row.den: 2}
    other = {row.num: 9, row.den: 10}
    if row.less:                        # num and den are one key then
        one, other = {row.num: 2, row.less: 1}, {row.num: 10, row.less: 1}
    ratios = [engine_stats.ratios({**dict.fromkeys(
        engine_stats.ROW, 0), **s})[row.key] for s in (one, other)]
    assert ratios == [0.5, 0.9]
    over = engine_stats.reduce([one, other], [row.key])[row.key]
    assert over == pytest.approx(10 / 12) and over != sum(ratios) / 2
    assert engine_stats.reduce([], [row.key]) == {row.key: 0.0}


def test_what_does_not_reduce_is_refused():
    with pytest.raises(ValueError, match="does not reduce"):
        engine_stats.reduce([{"block_size": 16}], ["block_size"])
    with pytest.raises(KeyError):
        engine_stats.reduce([{}], ["prefix_hit_ratee"])


def test_fleet_snapshot_is_the_reduction_of_the_live_probes():
    """Two replicas behind the ingress: each engine row of the snapshot
    is the table's reduction of the replicas' ``fleet_stats()``, under
    the snapshot's name for it."""
    dep = build_gpt_deployment(cfg=GPT, engine_cfg=EngineConfig(max_slots=4),
                               seed=0, num_replicas=2)
    try:
        handle = serve.run(dep, use_actors=False)
        f = fleet.enable("v1", FleetConfig(rate=500, burst=64))
        for n in (2, 5, 9):
            handle.remote({"prompt": list(range(1, n + 1)),
                           "max_tokens": 2}).result(timeout=170)
        # the engines are idle now; let the router's cached probes age
        time.sleep(2 * f.router.INPROC_TTL_S)
        snap = f.fleet_snapshot()
        probes = [r.impl._user.fleet_stats()
                  for r in serve.get_handle("v1")._state.replicas]
        want = engine_stats.reduce(probes, ingress._SNAPSHOT_ROWS)
        for key, value in want.items():
            assert snap[ingress._SNAPSHOT_NAMES.get(key, key)] == value, key
        assert snap["total_slots"] == 8
        assert snap["occupancy"] == snap["active_slots"] / 8
    finally:
        serve.shutdown()
