"""What a serving engine counts, stated once.

One row a number that ``InferenceEngine.stats()`` reports: its key, its
kind, its ``/metrics`` series where it has one, and how it reduces over
several engines.  The engine keeps its cumulative rows in ONE
``Counters`` object built from this table and reports by walking it
(``inference/engine.py``: ``stats()``, ``metrics_snapshot()``); a
replica's and a fleet's sums (``inference/serving.py``: ``fleet_stats``;
``serve/fleet/ingress.py``: ``fleet_snapshot``) reduce by its rule.  A
new counter is one row here and its increment where it is counted.

Lives at the serve layer (jax-free, as ``qos.py``): the fleet's code
reads it without importing the inference stack.
"""

from __future__ import annotations

from operator import attrgetter
from typing import Callable, Iterable, Mapping, NamedTuple, Optional

# kinds.  A counter is cumulative since the engine was made and lives in
# the engine's ``Counters``; a gauge is what the engine reads off itself
# when ``stats()`` is called (a level, a size, a flag, the loop's
# account); a ratio is ``(num - less) / den`` of other rows, 0.0 over 0
COUNTER, GAUGE, RATIO = "counter", "gauge", "ratio"
# over several engines
SUM, MAX = "sum", "max"


class Row(NamedTuple):
    key: str                        # in ``stats()``
    kind: str
    metric: Optional[str] = None    # the ``/metrics`` series' name
    help: str = ""
    over: Optional[str] = None      # SUM, MAX, or None: it does not reduce
    num: Optional[str] = None       # a ratio's operands, by key
    den: Optional[str] = None
    less: Optional[str] = None
    reported: bool = True           # False: an operand only

    @property
    def metric_kind(self) -> str:
        return COUNTER if self.kind == COUNTER else GAUGE


def _counter(key, metric=None, help="", over=SUM, **kw) -> Row:
    return Row(key, COUNTER, metric, help, over, **kw)


def _gauge(key, metric=None, help="", over=None) -> Row:
    return Row(key, GAUGE, metric, help, over)


def _ratio(key, num, den, metric=None, help="", less=None) -> Row:
    return Row(key, RATIO, metric, help, None, num, den, less)


# The rows with a series come in the order ``/metrics`` renders them; a
# comment says what the help text beside it does not.  The engine's loop
# thread alone writes a counter, without a lock, unless its row says
# "under _mlock": those another thread may write, or are read as a pair.
ROWS: tuple[Row, ...] = (
    # ---- rows and queue
    _gauge("max_slots", over=SUM),
    # occupied rows: decoding + prefilling
    _gauge("active_slots", "ray_tpu_inference_active_slots",
           "Cache slots currently decoding, per engine", over=SUM),
    _gauge("free_slots"),
    _gauge("waiting_requests", "ray_tpu_inference_waiting_requests",
           "Requests queued for a free slot, per engine", over=SUM),
    _gauge("waiting_interactive", over=SUM),
    _gauge("stopped"),
    _gauge("draining"),
    # Σ active/max_slots, with ``decode_iterations`` under _mlock
    _counter("occupancy_sum", reported=False),
    _ratio("batch_occupancy", "occupancy_sum", "decode_iterations",
           "ray_tpu_inference_batch_occupancy_ratio",
           "Mean active/max_slots per decode iteration"),
    # under _mlock, both
    _counter("generated_tokens", "ray_tpu_inference_generated_tokens_total",
             "Tokens generated since engine start"),
    _counter("requests_completed",
             "ray_tpu_inference_requests_completed_total",
             "Generation requests completed since engine start"),
    # one-token decode steps and speculative verify passes
    _counter("decode_iterations"),
    # the high-water mark of occupied rows (under _mlock); a peak of
    # several engines is no sum of peaks
    _counter("peak_active_requests", over=None),
    # ---- the paged cache: the router and autoscaler read BLOCK
    # pressure through ``fleet_stats``.  Block counts are replicated
    # across tp shards (heads are what's split): ``blocks_total`` is the
    # global admission budget AND the per-device count, both keys
    # reported so neither meaning is guessed, and summing engines needs
    # no per-shard correction
    _gauge("cache_bytes"),
    _gauge("cache_bytes_per_device"),
    _gauge("block_size"),
    _gauge("blocks_total", over=SUM),
    _gauge("blocks_per_device"),
    _gauge("blocks_free", over=SUM),
    _ratio("block_utilization", "blocks_total", "blocks_total",
           "ray_tpu_inference_block_utilization_ratio",
           "Paged KV pool blocks in use / usable blocks",
           less="blocks_free"),
    _ratio("prefix_hit_rate", "prefix_hit_tokens", "prefix_lookup_tokens",
           "ray_tpu_inference_prefix_hit_rate",
           "Prompt tokens adopted from the radix prefix cache / prompt "
           "tokens seen"),
    _gauge("prefix_cached_blocks", "ray_tpu_inference_prefix_cached_blocks",
           "Blocks held by the radix prefix index"),
    # the three under _mlock: a prompt is looked up once, at admission;
    # a re-match before a chunk adds only what it won
    _counter("prefix_hit_tokens",
             "ray_tpu_inference_prefix_hit_tokens_total",
             "Prompt tokens served from blocks adopted from the radix "
             "prefix index (no prefill program ran them)"),
    _counter("prefix_blocks_adopted",
             "ray_tpu_inference_prefix_blocks_adopted_total",
             "Blocks taken over from the radix prefix index by admissions "
             "and re-matches"),
    _counter("prefix_lookup_tokens"),
    # under _mlock
    _counter("preemptions", "ray_tpu_inference_preemptions_total",
             "Requests requeued by block-pressure preemption"),
    # fences remotely-advertised block ids across donated-pool
    # recoveries (cluster prefix plane)
    _gauge("pool_generation"),
    # ---- the prefill side of the load: prefill against generated
    # tokens says which of the two a replica's passes go to, chunk
    # passes over admissions how many prefill programs a prompt costs
    _counter("admissions", "ray_tpu_inference_admissions_total",
             "Requests given a cache row (a preempted request counts "
             "again)"),
    _counter("chunk_passes", "ray_tpu_inference_chunk_passes_total",
             "Prefill chunks run, by the chunk program or inside a decode "
             "step"),
    _counter("chunks_in_step", "ray_tpu_inference_chunks_in_step_total",
             "Prefill chunks that ran inside a decode step's program (one "
             "read of the weights for both)"),
    _counter("prefill_tokens", "ray_tpu_inference_prefill_tokens_total",
             "Prompt tokens run through a prefill program (prefix-cache "
             "hits excluded)"),
    # what the attention kernel reads, beside what a gather would
    _counter("kv_blocks_attended",
             "ray_tpu_inference_kv_blocks_attended_total",
             "KV blocks holding a key of a live row, summed over one-token "
             "decode passes (read once a pool and layer)"),
    _counter("kv_blocks_tabled", "ray_tpu_inference_kv_blocks_tabled_total",
             "Block-table entries of all rows, summed over one-token "
             "decode passes (what a whole-table gather reads)"),
    # what a window form must read of a row's past, and its work
    _counter("chunk_keys", "ray_tpu_inference_chunk_keys_total",
             "Keys in reach of prefill chunks' windows (position + tokens, "
             "summed over chunk passes)"),
    _counter("chunk_query_keys", "ray_tpu_inference_chunk_query_keys_total",
             "(query, key) pairs under the causal mask, summed over "
             "prefill chunk passes"),
    # 0 where the table is one key block and the window is attended
    # packed over it (``decode.window_by_head``), and for a latent layout
    _counter("chunk_key_blocks_walked",
             "ray_tpu_inference_chunk_key_blocks_walked_total",
             "Key blocks the head-by-head window form walked, a layer "
             "each (up to the block of the chunk's last real key), summed "
             "over prefill chunk passes"),
    # 0 for a model with no latent-attention layer: what its window
    # kernel walked of those pairs, tile by tile
    # (``ops/attention.window_tiles``: the kernel's own classification)
    _counter("chunk_pairs_walked",
             "ray_tpu_inference_chunk_pairs_walked_total",
             "(query, key) pairs of the score tiles the latent window "
             "kernel did not skip, summed over prefill chunk passes"),
    _counter("chunk_tiles_plain",
             "ray_tpu_inference_chunk_tiles_plain_total",
             "Score tiles the latent window kernel ran with no mask (every "
             "key before every query), a head and layer, summed over "
             "prefill chunk passes"),
    _counter("chunk_tiles_diagonal",
             "ray_tpu_inference_chunk_tiles_diagonal_total",
             "Score tiles the latent window kernel ran under the causal "
             "mask (the edge crosses them), a head and layer, summed over "
             "prefill chunk passes"),
    # 0 for a model with no linear-attention layer
    _counter("linear_state_rows_advanced",
             "ray_tpu_inference_linear_state_rows_advanced_total",
             "Rows whose linear-attention matrix state a one-token decode "
             "pass wrote, summed over passes"),
    _counter("linear_chunk_tokens",
             "ray_tpu_inference_linear_chunk_tokens_total",
             "Real prompt tokens through the window form of the delta "
             "rule, summed over prefill chunk passes"),
    # ---- where a decode or first token was chosen, and what every
    # ``engine.fetch`` brought to the host
    _counter("tokens_greedy_on_device",
             "ray_tpu_inference_tokens_greedy_on_device_total",
             "Decode and first tokens chosen by a serving program's own "
             "argmax (a pass fetches the integers, not the logits)"),
    _counter("tokens_sampled", "ray_tpu_inference_tokens_sampled_total",
             "Decode and first tokens chosen by a dispatch of their own on "
             "the logits (temperature > 0, a full-width prefill's first "
             "token, a speculative pass)"),
    _counter("fetch_bytes", "ray_tpu_inference_fetch_bytes_total",
             "Bytes the engine's loop fetched from the device"),
    # the loop thread's wall time by phase (``engine._LOOP_PHASES``):
    # self ``ns``, the part of it with no program in flight
    # ``starved_ns``, entries ``count``; what no phase covers; from
    # ``t_made_ns`` to ``t_ns``, the loop's newest stamp.  Its two
    # per-phase series are ``metrics_snapshot``'s own
    _gauge("loop_account"),
    # ---- per-ROW step accounting: exactly 1.0 for plain decode by
    # construction, 1 + accepted-per-row-pass under speculation.  The
    # batch width cancels out, so the ratio isolates speculation's win;
    # its operands (the pair under _mlock) are reported so that several
    # engines reduce exactly
    _ratio("tokens_per_step", "row_tokens", "row_steps",
           "ray_tpu_inference_tokens_per_step",
           "Tokens emitted per compiled decode/verify call (speculative "
           "decoding pushes this above 1)"),
    _counter("row_steps"),              # (row, compiled call) pairs
    _counter("row_tokens"),             # tokens those pairs emitted
    # ---- speculative decoding (zeros when ``speculate`` is None): the
    # accept rate is the drafter's quality, tokens per step the latency
    # it buys.  The counters under _mlock
    _gauge("speculate"),
    _ratio("spec_accept_rate", "spec_accepted_tokens", "spec_drafted_tokens",
           "ray_tpu_inference_spec_accept_rate",
           "Drafted tokens accepted by the verify pass / drafted tokens "
           "offered"),
    _counter("spec_accepted_tokens",
             "ray_tpu_inference_spec_accepted_tokens_total",
             "Drafted tokens accepted since engine start"),
    _counter("spec_drafted_tokens"),    # offered to verify
    _counter("spec_passes"),            # verify passes run
    # ---- serving geometry: 1/1 for an unmeshed engine, so the series
    # always exists and a sharded rollout shows as a step.  Over engines
    # a max, not a sum: multiplexed engines share the one mesh, and a
    # mixed rollout shows its widest
    _gauge("mesh_devices", "ray_tpu_inference_mesh_devices",
           "Devices in the engine's mesh (1 = unmeshed single device)",
           over=MAX),
    _gauge("mesh_axes"),
    _gauge("tp_shards", "ray_tpu_inference_tp_shards",
           "Tensor-parallel shards of the paged KV pool's heads dim "
           "(block counts are per-device AND global — heads are what's "
           "split)", over=MAX),
    # ---- the second kind of state
    _gauge("state_bytes", "ray_tpu_inference_state_bytes",
           "Bytes of the per-row recurrent-state pool (0 = the model "
           "keeps K/V only)"),
    _gauge("state_rows_in_use", "ray_tpu_inference_state_rows_in_use",
           "Decode rows holding a recurrent state"),
    # ... kept at block ends where it has a snapshot form (zeros for
    # every other model): a block closed by a chunk or a decode step is
    # a snapshot written; an admission (or a re-match before a chunk)
    # that adopted a chain is the LAST block's snapshot restored, once
    # a request; restored / ``admissions`` is the share of rows that
    # went on from a cached state
    _gauge("state_snapshot_bytes", "ray_tpu_inference_state_snapshot_bytes",
           "Bytes of the recurrent-state snapshots kept with the paged KV "
           "pool's blocks (0 = the state has no snapshot form)"),
    _counter("state_snapshots_written",
             "ray_tpu_inference_state_snapshots_written_total",
             "Blocks closed with the recurrent state at their end kept"),
    _counter("state_snapshots_restored",
             "ray_tpu_inference_state_snapshots_restored_total",
             "Requests whose row took its recurrent state from an adopted "
             "block's snapshot"),
    # ---- the second kind of K/V state: the pool of a model's
    # window-attention layers (zeros for a model without).  A row takes
    # its blocks chunk by chunk and gives them back behind the window;
    # the two sums, a decode pass each, set what the rows hold of it
    # beside what they hold of the full layers' pool, which is what ONE
    # table a row would keep of the window layers too
    _gauge("window_blocks_total", over=SUM),
    _gauge("window_blocks_held", "ray_tpu_inference_window_blocks_held",
           "Blocks of the window-attention layers' pool held by rows",
           over=SUM),
    _counter("window_blocks_allocated",
             "ray_tpu_inference_window_blocks_allocated_total",
             "Blocks of the window-attention layers' pool handed to rows"),
    _counter("window_blocks_returned",
             "ray_tpu_inference_window_blocks_returned_total",
             "Blocks of the window-attention layers' pool given back "
             "behind the window by rows still running"),
    _counter("kv_blocks_allocated",
             "ray_tpu_inference_kv_blocks_allocated_total",
             "Blocks of the (full-attention layers') paged KV pool handed "
             "to rows, adopted ones not counted"),
    _counter("window_blocks_resident_sum"),
    _counter("window_blocks_one_table_sum"),
    # what a window layer's two programs must read and multiply
    _counter("window_blocks_attended",
             "ray_tpu_inference_window_blocks_attended_total",
             "Blocks holding a key inside a live row's window, summed over "
             "one-token decode passes (read once a pool and window layer)"),
    _counter("window_chunk_keys"),      # keys in reach of chunks' windows
    _counter("window_query_keys"),      # (query, key) pairs inside them
    # ---- routed-expert load, of the programs that report one (zeros
    # for the others).  max / (held / experts held) = the imbalance
    _counter("expert_assignments_held",
             "ray_tpu_inference_expert_assignments_held_total",
             "(token, expert) assignments routed to experts held here"),
    _counter("expert_assignments_total",
             "ray_tpu_inference_expert_assignments_total",
             "(token, expert) assignments routed to any expert"),
    _counter("expert_load_max", "ray_tpu_inference_expert_load_max_total",
             "Assignments of the busiest held expert, summed over layers "
             "and passes"),
    _counter("expert_touched_held",
             "ray_tpu_inference_expert_touched_held_total",
             "Held experts with at least one assignment, summed over "
             "expert layers and passes"),
    _counter("expert_touched_held_decode",
             "ray_tpu_inference_expert_touched_held_decode_total",
             "Held experts with at least one assignment, summed over "
             "expert layers and decode steps (no prefill chunk)"),
    # ---- the weights, by shapes and dtypes at construction
    _gauge("weight_bytes", "ray_tpu_inference_weight_bytes",
           "Bytes of the parameter tree the programs are handed"),
    _gauge("weight_bytes_cast_per_pass",
           "ray_tpu_inference_weight_bytes_cast_per_pass",
           "Bytes of weights a program casts to its compute dtype every "
           "pass (0 = each is stored in it)"),
    # ---- the loop one pass ahead of what it has read: over the loop
    # account's ``passes``, the first is the mechanism's hit share; the
    # account's ``drained_by`` says why a pass in flight was landed early
    _counter("passes_launched_ahead",
             "ray_tpu_inference_passes_launched_ahead_total",
             "Passes dispatched while the pass before them was still "
             "unread (queued behind it on the device)"),
    _counter("passes_drained", "ray_tpu_inference_passes_drained_total",
             "Passes in flight that were read before the next could be "
             "launched (a sampled row, a preemption, a cancelled row, a "
             "cross-thread op, a full-width prefill, shutdown)"),
)

ROW = {row.key: row for row in ROWS}
COUNTED = tuple(row.key for row in ROWS if row.kind == COUNTER)
OPERANDS_ONLY = tuple(row.key for row in ROWS if not row.reported)
_read_counted = attrgetter(*COUNTED)


class Counters:
    """One engine's cumulative rows, an attribute each under the row's
    key: an increment is a plain attribute add where the thing is
    counted."""

    __slots__ = COUNTED

    def __init__(self):
        for key in COUNTED:
            setattr(self, key, 0)

    def snapshot(self, lock) -> dict:
        """Every counter at one moment of ``lock`` (the one their
        cross-thread writers hold), which is held for the read alone."""
        with lock:
            values = _read_counted(self)
        return dict(zip(COUNTED, values))


def _ratio_of(row: Row, value: Callable[[str], float]) -> float:
    num = value(row.num) - (value(row.less) if row.less else 0)
    den = value(row.den)
    return num / den if den else 0.0


def ratios(values: Mapping) -> dict:
    """Every ratio row, from a mapping that holds their operands."""
    return {row.key: _ratio_of(row, values.__getitem__)
            for row in ROWS if row.kind == RATIO}


def reduce(stats: Iterable[Mapping], keys: Iterable[str]) -> dict:
    """``keys`` over several engines' (or replicas') ``stats()``, each
    by its row's rule.  A ratio is the ratio of its reduced operands,
    never a mean of ratios.  A probed replica may report less than an
    engine does: what it leaves out counts as nothing (one device,
    where the rule is a max)."""
    stats = list(stats)

    def over(key: str):
        row = ROW[key]
        if row.kind == RATIO:
            return _ratio_of(row, over)
        if row.over == SUM:
            return sum(s.get(key, 0) for s in stats)
        if row.over == MAX:
            return max((s.get(key, 1) for s in stats), default=1)
        raise ValueError(f"{key!r} does not reduce over engines")
    return {key: over(key) for key in keys}
