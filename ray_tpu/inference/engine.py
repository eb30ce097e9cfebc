"""Continuous-batching inference engine (Orca-style iteration-level
scheduling over a fixed decode-batch width) with a PAGED KV cache.

One background loop owns the model state and runs one compiled decode
step per iteration over ALL rows at once, ONE PASS AHEAD of what it has
read: pass N+1 is dispatched, queued on the device behind pass N, before
pass N's tokens are fetched and emitted (``_loop_pass``, ``_pass_done``,
``_land``), so the host's turn-around runs beside the device and not
between two of its programs.  Between steps — the prefill
boundary — it admits waiting requests, advances prefills, and evicts
finished requests (EOS / max-tokens).  Requests therefore join and
leave MID-DECODE of their neighbors: a long generation never blocks a
short one behind it, and the decode batch stays as full as the offered
load allows — the throughput lever a sequential server lacks.

The cache is the paged BlockPool (inference/cache.py):

  * admission is BLOCK-BUDGET accounting, not slot counting — a request
    is admitted when a decode row is free AND the pool can cover its
    prompt after prefix-hit credit (LRU-evicting unreferenced cached
    prefixes under pressure), so short and long sequences share one
    pool with near-zero waste and peak concurrency is bounded by real
    token usage, not worst-case stripes.
  * a radix prefix index (cache.RadixIndex) lets a request whose prompt
    head matches a cached prefix ADOPT those blocks by refcount instead
    of re-running prefill; finished/preempted requests donate their
    clean KV chains back to the index.
  * prefill runs in fixed-width CHUNKS interleaved with decode
    iterations, occupancy-aware (one chunk per pass at healthy decode
    occupancy — bounded stall; batch-fill below it) and shortest-
    remaining-first — a long prompt no longer stalls neighbors' token
    cadence for its whole prefill, and cold duplicates of a shared
    head serialize so one representative publishes for the rest.
  * decode-time block growth that finds the pool dry first evicts
    cached prefixes, then PREEMPTS the youngest lowest-priority request
    (its blocks are donated to the prefix index and it re-queues; on
    re-admission its prompt includes every token already emitted, so
    the stream continues exactly — deterministic for greedy, and
    temperature sampling's rng state lives host-side in the request).

  * SPECULATIVE DECODING (``EngineConfig.speculate``): a drafter
    proposes up to ``speculate_k`` tokens per greedy row per pass — the
    host-side n-gram/prompt-lookup drafter ("ngram") or the
    truncated-layer self-drafter ("self") — and ONE widened verify step
    scores every row's window at once (decode.make_spec_verify_step).
    Greedy accept/reject against the verify argmaxes is token-EXACT, so
    the full-recompute oracle gates it like plain decode; the block
    budget is charged up front for drafted positions (alloc/prefix-
    evict only — hoped-for tokens never preempt a neighbor) and the
    rejected tail's charge rolls back after the pass.  A preempted row
    refunds any speculative charge automatically: granted blocks live
    in the row chain, and preemption releases the chain.

Tokens stream out per request as they are sampled: GenerationRequest is
a tiny condition-variable mailbox whose ``stream()`` generator the serve
layer turns into chunked transfer-encoding.  All waits are bounded
condition waits (no bare ``Event.wait()`` / ``time.sleep`` polling — the
control-plane lint's blocking rules are the house style even off the
node event loop).

What a pass exchanges with the device is small integers, for either
model family: ONE packed int32 array up a program (decode.pack_step /
pack_chunk), the greedy tokens down — the argmax each program takes
itself over the float32 logits models.gpt.sample_token would read (ties
to the lowest index, so greedy decode stays token-identical to the
full-recompute oracle; asserted in tests).  The logits [n_slots, vocab]
stay on the device (6.4 MB a pass at GPT-2 XL's 32 rows, which went to
the host and back for an argmax program of its own until ISSUE 39); a
row with temperature > 0 keeps its per-request rng and samples from its
row of them there, one dispatch of its own.  A pass that holds both a
chunk and decoding rows runs them as ONE program where the model has
one (``_step_chunk``: the weights stream once a pass; the GPT family's
decode.make_paged_step_chunk without speculation or routed experts,
the hybrid family's recurrent.make_recurrent_step_chunk for the layouts
whose every sublayer kind takes a window in two parts — Mamba-2, the
short convolution, the delta rule, attention over K/V blocks, routed
experts, the dense MLP; not latent attention or window layers — which
each seam's ``build`` derives from the configuration): the pass's last chunk
is prepared and packed, and launched by the decode step.  A prompt's
greedy first token is its last
chunk's own argmax, read inside the pass's fetch: the fused
program's last integer, or the chunk program's, which is not waited for
before the step goes out behind it (_finish_prefill).  The full-width
prefill and the speculation programs have no greedy output: they sample
on the logits as before.

What lets the loop run ahead: the token each row feeds its next step
stays ON the device.  Every program of a pass carries a small int32
array, a token a row (``_feed``; donated like the pools, replicated
under a mesh): the decode step and the fused program write every stepped
row's greedy token into it, a chunk program the greedy token of its last
real position at its row, and a step reads a row's input token there
wherever the packed array says ``decode.FEED`` in the token column — a
row whose token the host has not read yet.  A row whose token the host
knows (a sampled one, a full-width prefill's, every row of a
speculating engine) is handed it in the packed array, as before.  What
the host needs to pack pass N+1 it knows without pass N's tokens:
positions advance by one, a row that reaches ``max_new`` at N sits out
of N+1 (occupied until its last token lands and evicts it), a prompt
whose last chunk rode N turns active in N+1.  What it cannot know: a row
that emits EOS at N was stepped in N+1 already — that token is dropped
where N+1 lands (the request has left the row), and what the stray step
wrote lies in blocks and a state row that any later owner's programs
run behind.  A row with temperature > 0 takes its token on the host: a
pass with such a row is read before the next is packed, as is the pass
in flight before anything off the hot path (a preemption, a cancelled
row, a cross-thread op, a full-width prefill, shutdown: ``_drain``);
an engine that speculates reads every pass at once.  Nothing in flight
is the degenerate case of the one algorithm; ``passes_launched_ahead``
over the account's ``passes`` is its hit share, ``passes_drained`` (by
reason: the account's ``drained_by``) what fell back.
"""

from __future__ import annotations

import itertools
import sys
import threading
import time
import weakref
from dataclasses import dataclass
from typing import Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.core import fault_injection as _fi
from ray_tpu.core import flight_recorder as _fr
from ray_tpu.inference.cache import BlockPool, RadixIndex
from ray_tpu.inference.decode import (FEED, SpeculationUnsupported,
                                      make_chunk_prefill_fn,
                                      make_paged_decode_step,
                                      make_paged_draft_step,
                                      make_paged_step_chunk,
                                      make_prefill_fn,
                                      make_spec_verify_step,
                                      ngram_propose, pack_chunk,
                                      pack_step, pack_step_chunk,
                                      window_by_head, window_key_block)
from ray_tpu.inference.recurrent import (has_step_chunk,
                                         make_recurrent_chunk_fn,
                                         make_recurrent_decode_step,
                                         make_recurrent_step_chunk)
from ray_tpu.models import gpt, hybrid
from ray_tpu.ops.attention import window_tiles
from ray_tpu.parallel.sharding import (DEFAULT_LLM_RULES, Rules,
                                       tree_shardings)
from ray_tpu.serve import engine_stats
from ray_tpu.util import tracing


@dataclass
class EngineConfig:
    """Engine knobs.  ``max_slots`` is the decode-batch width (the
    concurrency cap); memory is ``n_blocks`` × ``kv_block_size`` tokens,
    decoupled from the row count (short and long sequences share one
    pool)."""
    max_slots: int = 8
    max_seq: Optional[int] = None        # cache width; None = model max_seq
    eos_token: Optional[int] = None      # None = never stop early
    default_max_new: int = 64
    max_waiting: int = 1024              # admission-queue bound (backpressure)
    idle_wait_s: float = 0.05            # loop park interval when empty
    # ---- paged cache
    kv_block_size: int = 16              # tokens per block
    n_blocks: Optional[int] = None       # usable blocks; None = max_slots
    #                                      * ceil(max_seq/block)
    # a model with window-attention layers: usable blocks of THEIR pool
    # (cache.BlockPool.window); None = max_slots * what one row may hold
    # (a window, a chunk and a block's rounding)
    n_window_blocks: Optional[int] = None
    prefill_chunk: int = 32              # chunked-prefill window width
    prefix_cache: bool = True            # radix prefix reuse on/off
    # ---- speculative decoding (draft-then-verify).
    # None = off; "ngram" = host-side
    # prompt-lookup drafting against the request's own prompt+history;
    # "self" = truncated-layer self-draft (the first ``draft_layers``
    # layers straight into the head).  Greedy requests emit the EXACT
    # non-speculative token stream (accept/reject is argmax-checked per
    # drafted position); temperature > 0 requests transparently fall
    # back to one token per step — never a silent parity break.
    speculate: Optional[str] = None      # None | "ngram" | "self"
    speculate_k: int = 4                 # drafted tokens per verify pass
    draft_layers: int = 1                # self-drafter depth ("self" mode)


# priority classes + the replica-death/draining errors live in the
# jax-free serve.qos module (the fleet's generic machinery imports them
# from there); re-exported here for the engine's own API surface.
from ray_tpu.serve.qos import (PRIORITY_BATCH,           # noqa: F401
                               PRIORITY_INTERACTIVE, EngineDrainingError,
                               PrefixInstallPressure, PrefixUnavailable,
                               ReplicaDeadError, StalePrefixGeneration,
                               parse_priority)


class EngineStoppedError(ReplicaDeadError):
    """The engine was shut down (replica teardown / chaos kill) with
    this request queued or mid-decode.  A typed subclass so the fleet
    layer can tell a dead replica (retry elsewhere — the generation is
    deterministic from the request) from a request-specific failure
    (do not retry)."""


class GenerationRequest:
    """One in-flight generation: a mailbox the engine appends tokens to
    and consumers drain via ``stream()`` / ``result()``."""

    def __init__(self, req_id: int, prompt: np.ndarray, max_new: int,
                 temperature: float, rng: Optional[jax.Array],
                 priority: int = PRIORITY_BATCH):
        self.id = req_id
        self.prompt = prompt
        self.max_new = max_new
        self.temperature = temperature
        self.priority = priority
        self._rng = rng
        # emitted tokens already folded into ``prompt`` by a preemption
        # (block-pressure requeue): on re-admission the prefill covers
        # prompt+emitted and the stream continues exactly where it was
        self._consumed = 0
        self.tokens: list[int] = []
        self.done = False
        self.cancelled = False
        self.error: Optional[BaseException] = None
        self._cond = threading.Condition()
        # stamps are time.monotonic() seconds, the clock of the spans
        # (util/tracing.py) and of the benchmark's own stamps
        self.created_s = time.monotonic()
        # the request got a row (after a preemption BEFORE its first
        # token: the last admission; a later preemption is decode time)
        self.admitted_s: Optional[float] = None
        self.first_token_s: Optional[float] = None
        self.finished_s: Optional[float] = None
        # ``stream()`` has read the first token: the consumer is awake
        self.first_yield_s: Optional[float] = None
        # what the lifecycle spans report (_record_spans); the caller's
        # span (the serve front's) is their parent, where there is one
        self._trace_ctx = tracing.inject_context()
        self.prompt_tokens = int(prompt.size)
        self.prefix_hit_tokens = 0
        self.preemptions = 0
        self.chunk_passes = 0
        self.full_width_prefill = False
        # per-request speculation accounting (accept-rate per stream)
        self.spec_drafted = 0
        self.spec_accepted = 0

    # ---- engine side -----------------------------------------------------

    def _emit(self, token: int) -> None:
        with self._cond:
            if self.first_token_s is None:
                self.first_token_s = time.monotonic()
            self.tokens.append(int(token))
            self._cond.notify_all()

    def _admitted(self) -> None:
        if self.first_token_s is None:
            self.admitted_s = time.monotonic()

    def _finish(self, error: Optional[BaseException] = None) -> None:
        with self._cond:
            first = not self.done
            self.error = error
            self.done = True
            self.finished_s = time.monotonic()
            self._cond.notify_all()
        if first:
            self._record_spans()

    def _record_spans(self) -> None:
        """The request's lifecycle as spans, built once from its stamps
        (always on): ``request.queue`` (submit -> a row),
        ``request.prefill`` (-> first token), ``request.decode``
        (-> finish) partition submit -> finish exactly; a request that
        never reached a stage ends in the stage it died in.
        ``request.decode`` says when a ``stream()`` read the first token
        (``first_yield_ns``), if one had by now."""
        def ns(t):
            return int(t * 1e9)
        end = ns(self.finished_s)
        admitted = ns(self.admitted_s) if self.admitted_s is not None \
            else end
        first = ns(self.first_token_s) if self.first_token_s is not None \
            else end
        status = {} if self.error is None else {
            "error": type(self.error).__name__}
        queue = tracing.record_span(
            "request.queue", ns(self.created_s), admitted,
            parent=self._trace_ctx, req=self.id,
            prompt_tokens=self.prompt_tokens,
            prefix_hit_tokens=self.prefix_hit_tokens,
            preemptions=self.preemptions, **status)
        # submitted outside any span: the three are roots of one trace
        ctx = self._trace_ctx or {"trace_id": queue.trace_id,
                                  "span_id": None}
        if self.admitted_s is not None:
            tracing.record_span(
                "request.prefill", admitted, first, parent=ctx,
                req=self.id, chunk_passes=self.chunk_passes,
                full_width=self.full_width_prefill)
        if self.first_token_s is not None:
            woke = {} if self.first_yield_s is None else {
                "first_yield_ns": ns(self.first_yield_s)}
            tracing.record_span(
                "request.decode", first, end, parent=ctx,
                req=self.id, output_tokens=len(self.tokens), **woke)

    def _next_rng(self) -> Optional[jax.Array]:
        if self._rng is None:
            return None
        self._rng, sub = jax.random.split(self._rng)
        return sub

    # ---- consumer side ---------------------------------------------------

    def cancel(self) -> None:
        """Abandon the request: the engine drops it from the waiting
        queue, or evicts it at the next decode iteration, freeing its
        slot for live work.  Idempotent; a no-op once done."""
        with self._cond:
            self.cancelled = True
            self._cond.notify_all()

    def stream(self, timeout: Optional[float] = None) -> Iterator[int]:
        """Yield generated tokens as they arrive; returns at completion,
        raises the engine-side error if the request failed."""
        i = 0
        deadline = (time.monotonic() + timeout
                    if timeout is not None else None)
        while True:
            with self._cond:
                while len(self.tokens) <= i and not self.done:
                    remain = 0.5
                    if deadline is not None:
                        remain = min(remain, deadline - time.monotonic())
                        if remain <= 0:
                            raise TimeoutError(
                                f"request {self.id}: no token within "
                                f"{timeout}s")
                    self._cond.wait(timeout=remain)
                if len(self.tokens) > i:
                    tok = self.tokens[i]
                    if i == 0 and self.first_yield_s is None:
                        self.first_yield_s = time.monotonic()
                else:                      # done, mailbox drained
                    if self.error is not None:
                        raise self.error
                    return
            yield tok
            i += 1

    def result(self, timeout: Optional[float] = None) -> list[int]:
        """Block until completion; returns the full generated-token list."""
        deadline = (time.monotonic() + timeout
                    if timeout is not None else None)
        with self._cond:
            while not self.done:
                remain = 0.5
                if deadline is not None:
                    remain = min(remain, deadline - time.monotonic())
                    if remain <= 0:
                        raise TimeoutError(
                            f"request {self.id} not done within {timeout}s")
                self._cond.wait(timeout=remain)
            if self.error is not None:
                raise self.error
            return list(self.tokens)


# engine registry for /metrics export (weak: an engine dies with its
# replica, the gauge series just disappears — the loop thread also only
# holds its engine weakly, see _engine_loop)
def init_params_for(cfg, key):
    """Seeded parameters of ``cfg``'s model family."""
    family = hybrid if isinstance(cfg, hybrid.HybridConfig) else gpt
    return family.init_params(cfg, key)


_ENGINES: "weakref.WeakValueDictionary[str, InferenceEngine]" = \
    weakref.WeakValueDictionary()
_engine_seq = itertools.count()
_registry_lock = threading.Lock()


# The loop thread's time account (``tracing.Account``): a phase is a
# span site of the pass, here beside the span it yields while tracing is
# on.  The containers ``engine.pass`` / ``.decode`` / ``.prefill_chunk``
# own no phase: what of them no site covers reads as ``unaccounted``.
_LOOP_PHASES = {
    "parked": None,                 # ``_cond.wait``: no work to do
    "admit": "engine.schedule",     # under ``_cond``: ops, reaping, admission
    "grow": "engine.schedule",      # ``_grow_row``: the block hunt
    "prefill_host": None,           # a chunk's host part: re-match, publish
    "pack": "engine.upload",
    "dispatch": "engine.dispatch",  # a program launched where it ends
    "wait": "engine.fetch",         # ... every launch landed where it ends
    "emit": "engine.sample",        # the row loop, ``req._emit``, eviction
}
# the loop's time between two ``engine.account`` spans, checked where a
# pass ends
ACCOUNT_EVERY_NS = 1_000_000_000

# What a pass launched, noted at its ``dispatch`` sites, and the KIND of
# pass that makes it (``tracing.Account.pass_done``): the decode step
# alone, the fused program alone, chunk programs and no step (no row
# decoding), lone chunks and then either of the two, a full-width
# prefill or a draft-and-verify iteration whatever else ran, and
# ``host``: a pass that launched nothing (cross-thread ops, a cancelled
# or preempted prefill).  The loop's time outside every pass is of the
# kind ``idle``: a park (its ``wait_ns``) and what led up to it.
_CHUNK, _STEP, _STEP_CHUNK, _PREFILL, _SPEC = 1, 2, 4, 8, 16


def _kind_of(launched: int) -> str:
    if launched & _PREFILL:
        return "prefill"
    if launched & _SPEC:
        return "spec"
    step = ("step_chunk" if launched & _STEP_CHUNK
            else "step" if launched & _STEP else "")
    if launched & _CHUNK:
        return "chunk+" + step if step else "chunk"
    return step or "host"


_PASS_KIND = tuple(_kind_of(launched) for launched in range(32))


class _Pass:
    """What ONE pass launched and the host has not read yet.  The loop
    builds one (``InferenceEngine._pass``) as it dispatches the pass's
    programs, and keeps at most ONE more, whole, in flight behind it
    (``_flight``): its tokens are read (``_land``) after the next pass
    has been dispatched, so the device always has a program queued.

    ``launched``: the programs, as ``_PASS_KIND`` indexes them.
    ``stepped``: the (row, request) pairs its decode step stepped, in
    the rows' order (None: no step); ``loads``: the int32 vectors the
    step's fetch brings, still on the device, the step's own last;
    ``step_seq``: the step's place among the loop's launches
    (``tracing.Account.launched``);
    ``logits``: the step's, where a stepped row samples on them (else
    their shape alone: two passes must not both hold a pass's logits).
    ``first``: the first tokens
    its chunks owe, [row, request, the program's int32 vector (the
    token is its last), the program's place among the dispatches];
    ``joining``: the (row, request) pairs of them that turn active
    where the pass ends.  ``chunks``: the int32 vector of its last
    chunk PROGRAM and that program's place among the dispatches (None:
    it launched none, or they are known to have ended).  ``ahead``: it
    was dispatched while the pass before it was unread; ``drained``: it
    was read before the next pass could be launched."""

    __slots__ = ("launched", "stepped", "loads", "step_seq", "logits",
                 "first", "joining", "chunks", "ahead", "drained")

    def __init__(self):
        self.launched = 0
        self.stepped = None
        self.loads = []
        self.step_seq = 0
        self.logits = None
        self.first = []
        self.joining = []
        self.chunks = None
        self.ahead = self.drained = False

    @property
    def owes(self) -> bool:
        return self.stepped is not None or bool(self.first)


def _engine_loop(ref: "weakref.ref[InferenceEngine]") -> None:
    """Loop-thread driver.  A strong reference exists only DURING a
    pass; between passes the engine is collectable, and a collected
    engine simply ends the thread (its requests are unreachable too,
    short of a consumer-held mailbox, which shutdown()/teardown covers
    for the supported lifecycles)."""
    while True:
        eng = ref()
        if eng is None:
            return
        try:
            alive = eng._loop_pass()
        except BaseException:
            eng._drain_pending()
            raise
        if not alive:
            eng._drain_pending()
            return
        del eng


class _Seam:
    """What the engine's pass is handed a model family through: the
    programs a pass runs and what they carry.  A namespace of functions
    of the engine, never instantiated (a bound method kept on the engine
    would be a reference cycle, and an abandoned engine must die by
    reference count alone).

    Both families' decode step and chunk program take ONE packed int32
    array and return the logits, which stay on the device, and a small
    int32 vector: ``N_LOAD`` counts of the routed experts' load, then
    the greedy tokens (every row's from the step, the last real
    position's from a chunk).  The pass's host algorithm over them is
    the engine's own, stated once (``_pass_done``, ``_land``); a seam
    states what differs: the tree served, the programs, the pools
    ``run`` hands them (the token array ``eng._feed`` behind them, for
    both), the load."""

    N_LOAD = 0

    @staticmethod
    def count(eng, loads, rode: bool) -> None:
        """``loads``: the int32 vectors a decode pass fetched, the
        step's last; ``rode``: that one is the fused program's, a chunk
        ran inside the step."""

    @staticmethod
    def greedy(eng, logits):
        """The decode step's greedy tokens, one a row, as fetched
        (``logits``: the step's, or their shape alone where no stepped
        row samples)."""
        return eng._greedy

    @staticmethod
    def row_admitted(eng, row, block: int = 0) -> None:
        pass

    @staticmethod
    def row_released(eng, row) -> None:
        pass


class _KVOnly(_Seam):
    """The seam for a model whose whole past is K/V blocks
    (models/gpt.py, the programs of decode.py)."""

    @staticmethod
    def serve(params, cfg):
        """-> (the tree the programs are handed every pass, its logical
        axes, the leaves of it that a program casts to ``cfg.dtype`` at
        use).  Float32 masters are cast ONCE, here, not once a pass."""
        served = gpt.serving_params(params, cfg)
        return (served, gpt.param_logical_axes(cfg, served=True),
                gpt.cast_at_use(served))

    @staticmethod
    def build(eng, bs: int) -> None:
        cfg, ec, mesh, rules = (eng.cfg, eng.engine_cfg, eng._mesh,
                                eng._rules)
        # the full-width prefill stays: a COLD prompt on an idle
        # engine seeds all its blocks from one training-forward call
        # (chunking pays a full-table gather per chunk — it earns
        # its keep on prefix hits and under load, not cold+idle)
        eng._prefill = make_prefill_fn(cfg, mesh=mesh, rules=rules)
        eng._full_width_over = eng.max_seq // 2
        eng._step = make_paged_decode_step(
            cfg, block_size=bs, n_table=eng.pool.blocks_per_seq,
            mesh=mesh, rules=rules)
        eng._chunk = make_chunk_prefill_fn(
            cfg, chunk=ec.prefill_chunk, block_size=bs,
            n_table=eng.pool.blocks_per_seq, mesh=mesh, rules=rules)
        # the two as ONE program, for a pass that holds both.  Not
        # where a pass may take the speculative iteration in the step's
        # place, nor for THIS family's routed experts: their capacity
        # is per window, and a decode row's one-token window can never
        # drop (the hybrid family's are dropless, and fuse)
        eng._step_chunk = (
            None if ec.speculate is not None or cfg.n_experts
            else make_paged_step_chunk(
                cfg, chunk=ec.prefill_chunk, block_size=bs,
                n_table=eng.pool.blocks_per_seq, mesh=mesh, rules=rules))

    @staticmethod
    def operands(eng) -> tuple:
        """What every program of a pass takes before its packed array."""
        return eng.params, eng.pool.k, eng.pool.v, eng._feed

    @staticmethod
    def run(eng, program, packed):
        logits, greedy, k, v, eng._feed = program(
            *_KVOnly.operands(eng), packed)
        eng.pool.swap(k, v)
        # no load to count: only the newest program's tokens are owed
        eng._load = [greedy]
        return logits


class _KVAndState(_Seam):
    """The seam for the hybrid family (models/hybrid.py, the programs of
    recurrent.py): its programs carry the block pools the model's
    attention layers keep (K and V, or the one latent pool) and, where
    the model has recurrent layers, the state pool beside them, and
    report the routed experts' load.  What follows from a recurrent
    state — no prefix index, re-prefill after preemption — the engine
    derives from ``cfg.state_geometry``, not from the family: a model
    of this family without recurrent layers keeps its whole past in
    blocks, and is served with both; so is one whose state has a
    snapshot form (``cache.snapshot_geometry``: the short convolution's,
    kept at every block's end with the block), for which a prefix is
    its blocks AND the state after its last token — an adopting row's
    state is restored from the LAST adopted block's snapshot inside its
    admission (``row_admitted``), a match ends on a block boundary, and
    a preempted row resumes from its last full block.  An SSM or matrix
    state has no such form: those models adopt nothing and re-prefill
    from zero.  Whether a pass that holds a chunk
    and decoding rows runs ONE program follows from the sublayer kinds
    (``build``; ``recurrent.has_step_chunk``), a recurrent state or
    not; that program's int32 vector is the step's and then the
    chunk's, each with its own load counts (``count``)."""

    N_LOAD = hybrid.N_LOAD

    @staticmethod
    def refuse(cfg, ec: "EngineConfig", mesh) -> None:
        """What the family's programs have no form for today, refused
        at construction: speculation (no verify program; and a rejected
        draft cannot be rolled back out of a recurrent state: snapshots
        are kept at block ends, not at a draft's start), a mesh."""
        recurrent = cfg.state_geometry is not None
        if ec.speculate is not None:
            raise SpeculationUnsupported(
                "speculative decoding needs a cache that can roll "
                "rejected tokens back; a recurrent state cannot (no "
                "snapshot at the draft's start: where there are "
                "snapshots they are a block end's)" if recurrent else
                "the hybrid family has no verify program yet")
        if mesh is not None:
            raise ValueError(
                ("a model with recurrent layers" if recurrent else
                 "the hybrid family") + " is served on one device (no "
                "sharding rules yet)")

    @staticmethod
    def serve(params, cfg):
        """The family's weights are published and held in ``cfg.dtype``
        and fill the chip: the tree is served as given, every leaf the
        array it was (no mesh, so no axes)."""
        return params, None, hybrid.cast_at_use(params)

    @staticmethod
    def build(eng, bs: int) -> None:
        """Prompts take the chunk path only (a state is built window by
        window; there is no one-pass scatter of it), so no prompt is
        ever over the full-width threshold."""
        cfg, ec = eng.cfg, eng.engine_cfg
        eng._prefill = None
        eng._full_width_over = eng.max_seq
        eng._step = make_recurrent_decode_step(
            cfg, block_size=bs, n_table=eng.pool.blocks_per_seq)
        eng._chunk = make_recurrent_chunk_fn(
            cfg, chunk=ec.prefill_chunk, block_size=bs,
            n_table=eng.pool.blocks_per_seq)
        # the two as ONE program where every sublayer kind of the model
        # takes a window in two parts (Mamba-2, the short convolution,
        # the delta rule, attention over K/V blocks, routed experts, the
        # dense MLP); a latent or window-attention sublayer: the two
        # back to back
        eng._step_chunk = (
            make_recurrent_step_chunk(
                cfg, chunk=ec.prefill_chunk, block_size=bs,
                n_table=eng.pool.blocks_per_seq)
            if has_step_chunk(cfg) else None)

    @staticmethod
    def operands(eng) -> tuple:
        st = eng.pool.state
        return (eng.params, eng.pool.pools,
                () if st is None else st.arrays, eng._feed)

    @staticmethod
    def run(eng, program, packed):
        logits, load, pools, state, eng._feed = program(
            *_KVAndState.operands(eng), packed)
        eng.pool.swap(*pools)
        if eng.pool.state is not None:
            eng.pool.state.swap(*state)
        # every program's load stays on the device until the next
        # decode pass fetches them all
        eng._load.append(load)
        return logits

    @staticmethod
    def count(eng, loads, rode: bool) -> None:
        # the fused program's vector is the step's and then the chunk's
        # own ``N_LOAD`` + 1 (``make_recurrent_step_chunk``), the load
        # of each counted apart: the decode rows' touches stay the
        # step's alone
        *chunks, step = loads
        if rode:
            chunks.append(step[-(hybrid.N_LOAD + 1):])
        counts = eng._counts
        for load in (*chunks, step):
            counts.expert_assignments_held += int(load[0])
            counts.expert_assignments_total += int(load[1])
            counts.expert_load_max += int(load[2])
            counts.expert_touched_held += int(load[3])
        counts.expert_touched_held_decode += int(step[3])

    @staticmethod
    def row_admitted(eng, row, block: int = 0) -> None:
        """``block``: the last block of the chain the row adopted (0:
        none) — its snapshot is the state the row goes on from."""
        st = eng.pool.state
        if st is not None:
            st.admit(row, block)

    @staticmethod
    def row_released(eng, row) -> None:
        if eng.pool.state is not None:
            eng.pool.state.release(row)


class InferenceEngine:
    """Continuous-batching engine over one parameter set.

    >>> eng = InferenceEngine(params, cfg, EngineConfig(max_slots=8))
    >>> req = eng.submit([1, 2, 3], max_new=16)
    >>> for tok in req.stream(): ...
    """

    def __init__(self, params, cfg,
                 engine_cfg: Optional[EngineConfig] = None, *,
                 mesh=None, rules: Rules = DEFAULT_LLM_RULES,
                 name: Optional[str] = None,
                 labels: Optional[dict] = None):
        self.cfg = cfg
        # extra label pairs on this engine's /metrics series (the serve
        # layer sets deployment/replica/model so multi-replica fleets
        # don't collapse into one ambiguous series)
        self.labels = dict(labels) if labels else {}
        self.engine_cfg = engine_cfg or EngineConfig()
        ec = self.engine_cfg
        self._mesh = mesh
        self._rules = rules
        # THE seam between the scheduler and a model family: what
        # programs a pass runs and what pools they carry follows from
        # the family (models/gpt.py: K/V blocks alone; models/hybrid.py:
        # blocks for its attention layers, a recurrent state for its
        # state-space layers, either or both).  Chosen once, here; a
        # pass calls ``self._seam``'s functions and branches on nothing.
        # What a row's past IS follows from the model, not the family:
        # with a recurrent state (``cfg.state_geometry``) a prefix is
        # more than its blocks.
        recurrent = cfg.state_geometry is not None
        hybrid_family = isinstance(cfg, hybrid.HybridConfig)
        self._seam = _KVAndState if hybrid_family else _KVOnly
        if hybrid_family:
            _KVAndState.refuse(cfg, ec, mesh)
        # what the programs are handed every pass; the engine keeps no
        # reference to a leaf that ``serve`` replaced
        self.params, axes, cast = self._seam.serve(params, cfg)
        self._weight_bytes = sum(
            p.nbytes for p in jax.tree.leaves(self.params))
        self._weight_bytes_cast = sum(
            p.nbytes for p in cast if p.dtype != jnp.dtype(cfg.dtype))
        if mesh is not None:
            # shard the weights to match the annotated step bodies
            # (heads/mlp/qkv/vocab over tp per the rules) so the first
            # compiled call doesn't start from fully-replicated params
            self.params = jax.device_put(
                self.params, tree_shardings(axes, rules, mesh))
        n = ec.max_slots
        self._spec = ec.speculate
        if self._spec is not None:
            if self._spec not in ("ngram", "self"):
                raise ValueError(
                    f"speculate must be None, 'ngram' or 'self', got "
                    f"{self._spec!r}")
            if ec.speculate_k < 1:
                raise ValueError(
                    f"speculate_k must be >= 1, got {ec.speculate_k}")
        bs = ec.kv_block_size
        per_seq = -(-int(ec.max_seq or cfg.max_seq) // bs)
        n_blocks = ec.n_blocks if ec.n_blocks is not None else n * per_seq
        # window layers (``cfg.window_geometry``): a row holds, of their
        # pool, the keys its next query can still see and the chunk it
        # is writing
        wg = getattr(cfg, "window_geometry", None)
        self._window = wg[3] if wg is not None else 0
        span = self._window + ec.prefill_chunk + bs
        self.pool = BlockPool(
            cfg, n_blocks, bs, max_seq=ec.max_seq, mesh=mesh, rules=rules,
            state_rows=n, window_span=span,
            n_window_blocks=None if wg is None else (
                ec.n_window_blocks if ec.n_window_blocks is not None
                else n * min(per_seq, -(-span // bs) + 1)))
        self.max_seq = self.pool.max_seq
        # a cached prefix is its K/V blocks: with recurrent layers
        # that is no longer the whole of a prefix, so nothing is
        # adopted (no index) — by derivation, not by an option — unless
        # the state has a snapshot form and every full block carries
        # the state at its end (``pool.snapshots``).  Nor with window
        # layers: the window pool's blocks of a prefix are given back
        # as the row moves on, so a chain in the index would name
        # full-layer blocks whose window-layer half is gone
        self.trie = (RadixIndex(self.pool)
                     if ec.prefix_cache and wg is None
                     and (not recurrent or self.pool.snapshots)
                     else None)
        self._seam.build(self, bs)
        if self._spec is not None:
            self._verify = make_spec_verify_step(
                cfg, width=ec.speculate_k + 1, block_size=bs,
                n_table=self.pool.blocks_per_seq, mesh=mesh,
                rules=rules)
            # "self" additionally compiles the truncated-layer
            # drafter (raises SpeculationUnsupported on a bad
            # draft_layers — still construction time)
            self._draft = (make_paged_draft_step(
                cfg, draft_layers=ec.draft_layers,
                k=ec.speculate_k, block_size=bs,
                n_table=self.pool.blocks_per_seq, mesh=mesh,
                rules=rules) if self._spec == "self" else None)
        # the token each row feeds its next decode step, ON the device:
        # the programs read and write it (``decode.FEED``), carried and
        # donated like the pools (replicated under a mesh), so a step
        # can be launched before the last one's tokens have been read
        self._feed = self._zero_feed()
        # the programs' int32 vectors (load counts, then greedy tokens)
        # that no pass has taken yet (a pass's step takes them all), and
        # the last decode step's greedy tokens as fetched
        self._load = []
        self._greedy = None
        # the pass being dispatched, and the one before it while its
        # tokens are unread: at most ONE pass is in flight ahead of what
        # the host has read (``_pass_done``, ``_land``)
        self._pass = _Pass()
        self._flight: Optional[_Pass] = None
        # a row's block table(s) as the programs take them: the full
        # layers' [n, T], then (a model with window layers) the window
        # layers' beside it, ONE array [n, 2 T] of which both are views.
        # A window table is indexed by position like the other; its
        # entries behind the window are 0 again (the scratch block)
        T = self.pool.blocks_per_seq
        self._tables_all = np.zeros((n, T * (2 if self._window else 1)),
                                    np.int32)
        self._tables = self._tables_all[:, :T]
        self._wtables = self._tables_all[:, T:]
        self._row_blocks: dict[int, list[int]] = {}
        # row -> [index of its first window block held, the ids from there]
        self._row_wblocks: dict[int, list] = {}
        self._free_rows = list(range(n - 1, -1, -1))
        self._prefilling: dict[int, int] = {}   # row -> next prefill pos

        self._slot_req: dict[int, GenerationRequest] = {}
        # a row's next input token where the host knows it, ``FEED``
        # where it is still on the device (launched, not read yet)
        self._tokens = np.zeros(n, np.int32)
        self._positions = np.zeros(n, np.int32)   # where it will be written
        # rows the next decode step steps.  A row whose LAST token is on
        # its way (``max_new`` reached by what is launched) sits out,
        # occupied, until that token lands and evicts it
        self._active = np.zeros(n, bool)
        # tokens launched for a row and not read yet (0 .. 2)
        self._owed = np.zeros(n, np.int32)
        # occupied rows whose request samples (temperature > 0)
        self._sampling = 0
        self._waiting: list[GenerationRequest] = []
        self._req_seq = itertools.count()
        self._cond = threading.Condition()
        self._stopped = False
        self._draining = False
        # cross-thread op queue: the pool arrays and the radix trie are
        # loop-thread-only, so the cluster prefix plane's extract/
        # install calls enqueue closures here and the loop runs them
        # between passes (_run_op / _run_ops_locked) — same serialization
        # as every other trie/pool touch, no new locking
        self._ops: list = []
        # prefixes published to the LOCAL trie since the last
        # prefix_export() drain — what the fleet forwards to the
        # cluster directory (bounded; oldest dropped first)
        self._prefix_outbox: list = []

        # what the engine counts: the cumulative rows of
        # ``serve/engine_stats.py``, an attribute each.  The loop thread
        # writes most of them alone, without a lock; ``_mlock`` guards
        # the ones another thread may write or that are read as a pair
        # (the table says which), and the prefix outbox
        self._mlock = threading.Lock()
        self._counts = engine_stats.Counters()
        # a model with linear-attention layers (a matrix state a row)
        self._linear = getattr(cfg, "n_linear", 0) > 0
        # a model with latent-attention layers (ONE latent pool)
        self._latent = getattr(cfg, "n_latent", 0) > 0
        # the layers whose chunk program walks a row's table a key block
        # at a time (what the hybrid family's ``paged_attend`` derives)
        self._walk_layers = (
            cfg.n_attention if self._seam is _KVAndState
            and not self._latent and window_by_head(
                self.pool.layout, self.pool.blocks_per_seq) else 0)
        # a model whose full blocks carry a state snapshot
        self._snapshots = self.pool.snapshots
        # the loop thread's time by phase, always on; ``engine.account``
        # spans carry it to the ring (``_pass_ended``)
        self._passes = 0               # units of the account: passes
        #                                whose tokens were read, and
        #                                passes that owed none
        self._acct = tracing.Account(_LOOP_PHASES, launch=("dispatch",),
                                     land=("wait",),
                                     waits=("wait", "parked"))
        self._account_t1_ns = self._acct.t_made_ns
        # what the pass being dispatched has launched (``_PASS_KIND``);
        # the first tokens emitted since the account's last unit and the
        # tokens emitted as it ended; whether this turn of the loop has
        # landed a pass (its unit is booked where it lands); why passes
        # in flight were landed early
        self._launched = 0
        self._first_tokens = 0
        self._emitted = 0
        self._landed_now = False
        self._drained_by: dict[str, int] = {}

        with _registry_lock:
            self.name = name or f"engine-{next(_engine_seq)}"
            _ENGINES[self.name] = self

        # the thread holds the engine only WEAKLY between passes: an
        # engine abandoned without shutdown() becomes collectable (the
        # loop then exits on its own), instead of a bound-method target
        # pinning the KV pool + a 50 ms-tick thread alive forever
        self._thread = threading.Thread(
            target=_engine_loop, args=(weakref.ref(self),), daemon=True,
            name=f"raytpu-inference-{self.name}")
        self._thread.start()

    # ------------------------------------------------------------ submit

    def submit(self, prompt: Sequence[int], *,
               max_new: Optional[int] = None,
               temperature: float = 0.0,
               seed: int = 0,
               priority: int = PRIORITY_BATCH) -> GenerationRequest:
        """Queue a generation; returns immediately with the request
        mailbox.  Admission happens at the next prefill boundary, in
        (priority, arrival) order — an interactive waiter takes a freed
        slot ahead of batch waiters that arrived earlier.

        Speculation interplay (``EngineConfig.speculate``): greedy
        requests (``temperature == 0``) ride the draft-then-verify path
        and emit the EXACT token stream non-speculative decode would.
        ``temperature > 0`` requests are accepted and transparently
        decode one token per step — never drafted, never a silent
        parity break (the decided alternative to a typed rejection:
        mixed batches are the serving norm, and a sampled request on a
        speculating engine is valid work, not an error).  The typed
        ``SpeculationUnsupported`` is reserved for configurations with
        no speculation path at all (a recurrent state, bad draft depth)
        and raised at engine construction."""
        ec = self.engine_cfg
        prompt = np.asarray(list(prompt), np.int32)
        max_new = int(max_new if max_new is not None else ec.default_max_new)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError("prompt must be a non-empty 1-D token list")
        if prompt.min() < 0 or prompt.max() >= self.cfg.vocab_size:
            raise ValueError(
                f"prompt tokens out of range [0, {self.cfg.vocab_size})")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        total = int(prompt.size) + max_new
        if total > self.max_seq:
            # this also bounds the paged block budget: BlockPool
            # guarantees n_blocks >= ceil(max_seq / block_size), so any
            # request within the cache width can eventually fit
            raise ValueError(
                f"prompt ({prompt.size}) + max_new ({max_new}) = {total} "
                f"exceeds the cache width {self.max_seq}")
        rng = (jax.random.PRNGKey(seed) if temperature > 0.0 else None)
        req = GenerationRequest(next(self._req_seq), prompt, max_new,
                                float(temperature), rng,
                                priority=int(priority))
        with self._cond:
            if self._stopped:
                raise EngineStoppedError("engine is shut down")
            if self._draining:
                raise EngineDrainingError(
                    "engine is draining (planned scale-down)")
            if len(self._waiting) >= ec.max_waiting:
                raise RuntimeError(
                    f"engine admission queue full ({ec.max_waiting})")
            self._waiting.append(req)
            self._cond.notify_all()
        return req

    def generate(self, prompt: Sequence[int], *,
                 max_new: Optional[int] = None, temperature: float = 0.0,
                 seed: int = 0, timeout: Optional[float] = None) -> list[int]:
        """Synchronous convenience wrapper around submit()+result()."""
        return self.submit(prompt, max_new=max_new, temperature=temperature,
                           seed=seed).result(timeout=timeout)

    def warm_up(self, timeout: float = 300.0) -> None:
        """Bring every program a pass can run to the device before the
        first request.  First all of them side by side, a thread each:
        compiled, or loaded from the persistent compile cache, from the
        operands a pass hands them (``lower(..).compile()``, which the
        first real call then finds done).  A program's bring-up from a
        warm cache is ~5 s at the hybrid cells' sizes, most of it
        outside the interpreter, and a model with the fused program has
        three of them.  Then one short generation (the chunk program
        and the decode step), and the program that runs both once on
        nothing — every decode row inactive and a chunk of no real
        token, all of whose writes go to the scratch block.  Requests
        that arrive one at a time never give a pass both a chunk and a
        decoding row, so the first overlap would otherwise compile on
        the request path."""
        n, T = self._tables_all.shape
        zeros = np.zeros(n, np.int32)
        step = pack_step(np.zeros_like(self._tables_all), zeros, zeros,
                         zeros)
        chunk = pack_chunk(np.zeros(T, np.int32),
                           np.zeros(self.engine_cfg.prefill_chunk, np.int32),
                           0, 0, 0)
        programs = [(self._chunk, chunk), (self._step, step)]
        if self._step_chunk is not None:
            programs.append((self._step_chunk, pack_step_chunk(step, chunk)))

        def load_all():
            operands, errors = self._seam.operands(self), []

            def load(program, packed):
                try:
                    program.lower(*operands, packed).compile()
                except BaseException as e:      # raised by ``load_all``
                    errors.append(e)
            threads = [threading.Thread(target=load, args=p, daemon=True)
                       for p in programs]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if errors:
                raise errors[0]

        def run_once():
            with self._acct.phase("dispatch"):
                self._launched |= _STEP_CHUNK
                self._seam.run(self, *programs[-1])
            with self._acct.phase("wait"):
                jax.block_until_ready(self._load.pop())

        self._run_op(load_all, timeout=timeout)
        self.generate([1], max_new=2, timeout=timeout)
        if self._step_chunk is not None:
            self._run_op(run_once, timeout=timeout)

    # ------------------------------------------------------------- loop

    def _loop_pass(self) -> bool:
        """One turn of the loop: reap → admit → dispatch a pass → read
        the pass BEFORE it (``_pass_done``); False when stopped.  Runs
        on the loop thread, which holds the engine only WEAKLY between
        turns (_engine_loop) so an engine abandoned without shutdown()
        is still collectable.

        The loop is ONE pass ahead of what it has read: the pass it
        dispatches is queued behind the one in flight, whose tokens it
        then reads and emits.  What the next pass needs of the last it
        knows without its tokens (positions advance by one; a row that
        reaches ``max_new`` sits out; a stepped row's input token is on
        the device); what it cannot know first has the pass in flight
        read (``_drain``): a row that samples, a preemption, a cancelled
        row, a cross-thread op, a full-width prefill, shutdown, and
        every pass of an engine that speculates."""
        # engine.pass opens once the park check finds work, under that
        # check's lock, and closes with the pass, the lock long released
        sp = tracing.NOOP
        worked = False
        try:
            if self._flight is not None and (self._stopped or self._ops):
                # off the hot path: the pass in flight lands first (not
                # under the lock: submitters do not wait for the device)
                try:
                    self._drain("shutdown" if self._stopped else "op")
                except Exception as e:
                    self._fail_all(e)
            with self._cond:
                # park unless there is work a pass can make progress
                # on: an active row to decode, a prefill to advance, or
                # a waiting request AND a free row to admit it into
                # (waiting alone must not spin when the pool is handed
                # out; admission retries at the idle tick because
                # block availability also depends on evictable cached
                # prefixes)
                if (not self._stopped and not self._ops
                        and self._flight is None
                        and not self._active.any()
                        and not self._prefilling
                        and not (self._waiting
                                 and self._admission_possible())):
                    # ONE bounded wait, then back out to _engine_loop:
                    # an idle engine must drop the loop thread's strong
                    # reference every tick, or it is never collectable
                    with self._acct.phase("parked"):
                        self._cond.wait(self.engine_cfg.idle_wait_s)
                    self._acct.pass_done("idle")
                    return not self._stopped
                if self._stopped:
                    return False
                worked = True
                sp = tracing.span("engine.pass").__enter__()
                if sp:
                    sp.set(active=int(self._active.sum()),
                           prefilling=len(self._prefilling),
                           waiting=len(self._waiting))
                self._schedule_locked()
            try:
                if self._sampling and self._samples():
                    # a row that samples takes its token on the host,
                    # from the logits of the pass in flight
                    self._drain("sampled")
                # prefill progress is interleaved with decode, ONE
                # chunk a pass at healthy occupancy, so a long prompt
                # cannot stall its neighbors' token cadence; the pass's
                # last chunk rides the decode step where ONE program
                # runs both
                ride = (self._prefill_chunk_pass() if self._prefilling
                        else None)
                if not (self._active.any()
                        and self._paged_decode_iteration(ride)):
                    self._pass_done()
            except Exception as e:            # step failure: fail the
                self._fail_all(e)             # in-flight requests, keep serving
            return True
        finally:
            sp.__exit__(*sys.exc_info())
            if worked:
                self._pass_ended()

    def _unit_done(self, launched: int, ahead: bool = False,
                   drained: bool = False) -> None:
        """A unit of the account ends at the newest stamp: the loop's
        time since the last one goes to the row of the KIND of pass
        ``launched`` makes, beside the tokens emitted since (the
        counters' growth) and whether the pass was launched ``ahead``
        of an unread one and ``drained`` early.  Where a pass's tokens
        were read and emitted (``_land``) it is that pass's kind,
        whatever was dispatched meanwhile: a unit runs from the last
        emit to this one, which is the gap its tokens waited."""
        counts = self._counts
        emitted = counts.tokens_greedy_on_device + counts.tokens_sampled
        tokens, self._emitted = emitted - self._emitted, emitted
        self._passes += 1
        self._acct.pass_done(_PASS_KIND[launched],
                             tokens - self._first_tokens, tokens=tokens,
                             ahead=int(ahead), drained=int(drained))
        self._first_tokens = 0

    def _pass_ended(self) -> None:
        """Where a turn of the loop that found work ends, a failed one
        too.  A turn that read no pass and left none in flight (its
        pass owed no token: chunks of prompts that go on, cross-thread
        ops; or it failed) is a unit of its own kind; a turn that only
        launched ahead is none, its time is part of the unit that reads
        its pass.  Once the loop has spent ``ACCOUNT_EVERY_NS`` since
        the last one, the account so far goes to the ring as an
        ``engine.account`` span, all of it cumulative: the loop's
        account and every counter of the engine's table (three of them
        also under their own keys, where ``chunk_in_step_share.serve``
        reads them)."""
        counts, acct = self._counts, self._acct
        if not self._landed_now and self._flight is None:
            self._unit_done(self._launched)
        self._launched = 0
        self._landed_now = False
        if acct.t_ns - self._account_t1_ns < ACCOUNT_EVERY_NS:
            return
        counters = counts.snapshot(self._mlock)
        self._account_t1_ns = tracing.record_account(
            "engine.account", self._account_t1_ns, acct.t_ns,
            acct.interval_profiled(), engine=self.name,
            passes=self._passes,
            decode_iterations=counters["decode_iterations"],
            chunk_passes=counters["chunk_passes"],
            chunks_in_step=counters["chunks_in_step"], counters=counters,
            drained_by=dict(self._drained_by), **acct.snapshot())

    def _schedule_locked(self) -> None:
        """The pass's scheduling under ``_cond``: cross-thread ops,
        reaping, admission."""
        with self._acct.phase("admit") as sp:
            # only this thread writes the two counters
            counts = self._counts
            admitted0, preempted0 = counts.admissions, counts.preemptions
            if self._ops and self._flight is None:
                # (ops queued since this turn's drain wait a turn)
                self._run_ops_locked()
            # reap cancelled waiters even when the pool is full:
            # zombies must not consume max_waiting backpressure
            # (a burst of timed-out clients would otherwise make
            # submit() reject live work as "queue full")
            live = []
            for r in self._waiting:
                if r.cancelled:
                    r._finish()
                else:
                    live.append(r)
            self._waiting = live
            self._paged_admit_locked()
            if sp:
                sp.set(admitted=counts.admissions - admitted0,
                       preempted=counts.preemptions - preempted0)

    def _zero_feed(self):
        """The token array, zeroed: int32 a row, replicated under a
        mesh."""
        feed = jnp.zeros(self.engine_cfg.max_slots, jnp.int32)
        if self._mesh is None:
            return feed
        return jax.device_put(feed, jax.sharding.NamedSharding(
            self._mesh, jax.sharding.PartitionSpec()))

    def _admission_possible(self) -> bool:
        """Cheap park-predicate check; the real budget decision happens
        in the admission pass."""
        return bool(self._free_rows) and (
            self.pool.n_free > 0
            or (self.trie is not None and self.trie.cached_blocks > 0)) \
            and (not self._window or self.pool.window.n_free > 0)

    def _drain_pending(self) -> None:
        """Terminal cleanup: fail everything still queued or in-flight."""
        self._flight = None
        with self._cond:
            self._stopped = True
            pending = list(self._slot_req.values()) + self._waiting
            self._slot_req.clear()
            self._waiting.clear()
            ops, self._ops = self._ops, []
            for _fn, box in ops:
                # a queued prefix op on a dying engine resolves as a
                # dead-replica error — the prefix plane maps it to its
                # local-recompute fallback like every other failure
                box["error"] = EngineStoppedError("engine shut down")
                box["done"] = True
            self._cond.notify_all()
        err = EngineStoppedError("engine shut down")
        for r in pending:
            if not r.done:
                r._finish(err)

    # ----------------------------------------------------------- paged path

    def _chaos(self, point: str, **ctx) -> Optional[dict]:
        """Fault-plane hook (infer_admit / infer_block_alloc /
        infer_speculate / infer_shard_commit — the last fires after a
        meshed decode iteration installs the sharded pool arrays, the
        spot a multi-host commit could straggle or die):
        zero-overhead gate when no plan is installed.
        Returns the ctx dict when a plan ran — a scripted fn may have
        mutated it (e.g. ``ctx["reject_all"] = True`` forces the
        speculative pass to discard every draft), and the caller reads
        the verdict from it."""
        fi = _fi._active
        if fi is None:
            return None
        ctx["engine"] = self.name
        fi.on_infer(point, ctx)
        return ctx

    def _fr_note(self, req: GenerationRequest) -> None:
        """Flight-recorder copy of a finished request (armed only):
        an ``engine_request`` event the merged ``ray_tpu timeline``
        renders as one engine slice per request, accept/reject counts
        in its args."""
        rec = _fr._active
        if rec is None:
            return
        ev = {
            "t": time.time(), "kind": "engine_request",
            "engine": self.name, "req": req.id,
            "start_t": tracing.wall_time(int(req.created_s * 1e9)),
            "tokens": len(req.tokens),
            "spec_accepted": req.spec_accepted,
            "spec_rejected": req.spec_drafted - req.spec_accepted,
        }
        if self._mesh is not None:
            # timeline slices carry the serving geometry so a trace of
            # a sharded fleet says WHICH mesh served each request
            ev["mesh_devices"] = int(np.prod(
                list(self._mesh.devices.shape)))
            ev["tp_shards"] = self.pool.heads_shards
        rec.note_ingress(ev)

    def _paged_admit_locked(self) -> None:
        """Block-budget admission (called under ``_cond``): admit while
        a decode row is free AND the pool covers the prompt after
        prefix-hit credit.  Head-of-line within (priority, arrival)
        order — a large request that does not fit yet is not overtaken
        (no starvation), and a freed row goes to the most urgent class
        first."""
        if not (self._waiting and self._free_rows):
            return
        self._waiting.sort(key=lambda r: (r.priority, r.id))
        while self._waiting and self._free_rows:
            req = self._waiting[0]
            try:
                if not self._try_admit_paged(req):
                    break
            except Exception as e:
                self._waiting.pop(0)
                req._finish(e)
                continue
            self._waiting.pop(0)

    def _try_admit_paged(self, req: GenerationRequest) -> bool:
        bs = self.pool.block_size
        prompt = req.prompt
        n_prompt = int(prompt.size)
        p_blocks = -(-n_prompt // bs)
        ids, hit = (self.trie.match(prompt) if self.trie is not None
                    else ([], 0))
        need = p_blocks - len(ids)
        if self.pool.n_free < need and self.trie is not None:
            # pressure: evict unreferenced cached prefixes, LRU-first
            # (the just-matched chain is protected by its new refcount)
            self.trie.evict(need - self.pool.n_free)
        # the window layers' pool must hold what the prompt's first
        # chunks will ask of it (taken chunk by chunk: ``_window_cover``)
        w_need = (min(p_blocks, self.pool.window.blocks_per_row)
                  if self._window else 0)
        if self.pool.n_free < need or (
                w_need and self.pool.window.n_free < w_need):
            for bid in ids:
                self.pool.decref(bid)
            return False
        try:
            self._chaos("infer_admit", req=req.id, need=need,
                        hit_tokens=hit)
        except BaseException:
            for bid in ids:
                self.pool.decref(bid)
            raise
        row = self._free_rows.pop()
        blocks = list(ids)
        for _ in range(need):
            blocks.append(self.pool.alloc())
        self._counts.kv_blocks_allocated += need
        self._tables_all[row, :] = 0
        if self._window:
            self._row_wblocks[row] = [0, []]
        self._tables[row, :len(blocks)] = blocks
        self._row_blocks[row] = blocks
        self._slot_req[row] = req
        self._sampling += req.temperature != 0.0
        self._prefilling[row] = hit          # prefill resumes past the hit
        # (a state with a snapshot form goes on from the last adopted
        # block's: matches over such a pool end on a block boundary)
        self._seam.row_admitted(self, row, ids[-1] if ids else 0)
        if hit and self._snapshots:
            self._counts.state_snapshots_restored += 1
        req._admitted()
        req.prefix_hit_tokens = hit
        occupied = self.engine_cfg.max_slots - len(self._free_rows)
        counts = self._counts
        counts.admissions += 1
        with self._mlock:
            counts.prefix_hit_tokens += hit
            counts.prefix_blocks_adopted += len(ids)
            counts.prefix_lookup_tokens += n_prompt
            counts.peak_active_requests = max(counts.peak_active_requests,
                                              occupied)
        return True

    def _take_block(self, row: int, window: bool = False) -> Optional[int]:
        """A fresh block for ``row``: free list, else LRU prefix
        eviction, else preempt the youngest lowest-priority occupied
        row (``row`` itself last).  Returns None when ``row`` was the
        preemption victim — the caller must stop touching it.
        ``window``: of the window layers' pool (a preempted row gives
        back its blocks of both)."""
        pool = self.pool.window if window else self.pool
        req = self._slot_req.get(row)
        while True:
            self._chaos("infer_block_alloc", row=row)
            bid = pool.alloc()
            if bid is not None:
                if window:
                    self._counts.window_blocks_allocated += 1
                else:
                    self._counts.kv_blocks_allocated += 1
                return bid
            if self.trie is not None and self.trie.evict(1):
                continue
            if self._flight is not None:
                # before a row is preempted the pass in flight lands: a
                # row that finished in it gives its blocks back, and a
                # victim's tokens are read before it is requeued
                self._drain("preempt")
                if self._slot_req.get(row) is not req:
                    return None           # ``row`` itself finished in it
                continue
            victim = self._pick_victim()
            if victim is None:
                return None
            self._preempt_row(victim)
            if victim == row:
                return None

    def _pick_victim(self) -> Optional[int]:
        """Preemption victim: the youngest request of the least urgent
        class among occupied rows (prefilling or decoding)."""
        occupied = list(self._slot_req)
        if not occupied:
            return None
        return max(occupied,
                   key=lambda r: (self._slot_req[r].priority,
                                  self._slot_req[r].id))

    def _preempt_row(self, row: int) -> None:
        """Block-pressure preemption: donate the row's clean KV chain to
        the prefix index (re-admission will adopt it back if it survives
        eviction), release the blocks, and requeue the request with its
        emitted tokens folded into the prompt — the stream continues
        exactly where it left off."""
        req = self._slot_req[row]
        seq = self._sequence(req)
        self._insert_prefix(row, seq[:self._cached(row, req)])
        self._release_row(row)
        req.prompt = seq
        req._consumed = len(req.tokens)
        req.preemptions += 1
        with self._mlock:
            self._counts.preemptions += 1
        with self._cond:
            stopped = self._stopped
            if not stopped:
                self._waiting.append(req)
            self._cond.notify_all()
        if stopped:       # raced with shutdown: never leave it hanging
            req._finish(EngineStoppedError("engine shut down"))

    @staticmethod
    def _sequence(req: GenerationRequest) -> np.ndarray:
        """The request's tokens so far: its prompt (what it was
        re-admitted with) and what it has emitted since."""
        return np.concatenate(
            [req.prompt, np.asarray(req.tokens[req._consumed:], np.int32)])

    def _cached(self, row: int, req: GenerationRequest) -> int:
        """The tokens of ``_sequence(req)`` whose K/V the row's blocks
        hold for certain: a prefilling row's position; else every token
        but the last EMITTED one (where the loop is a pass ahead the
        row's position is too, and what that pass writes is nobody's
        yet)."""
        if row in self._prefilling:
            return self._prefilling[row]
        return max(int(req.prompt.size) + len(req.tokens) - req._consumed
                   - 1, 0)

    def _insert_prefix(self, row: int, seq: np.ndarray) -> None:
        if self.trie is None or len(seq) == 0:
            return
        self.trie.insert(seq, self._row_blocks[row])

    def _release_row(self, row: int) -> None:
        """Drop the row's references (blocks survive only if the prefix
        index kept them) and return the row to the free list."""
        req = self._slot_req.pop(row, None)
        if req is not None:
            self._sampling -= req.temperature != 0.0
        self._active[row] = False
        self._owed[row] = 0
        self._prefilling.pop(row, None)
        # a first token still owed to the row is nobody's now (a row
        # preempted between its prompt's last chunk and the token's
        # read prefills again, and gets it then)
        for p in (self._pass, self._flight):
            if p is not None and p.first:
                p.first[:] = [owed for owed in p.first if owed[0] != row]
        self._seam.row_released(self, row)
        for bid in self._row_blocks.pop(row, []):
            self.pool.decref(bid)
        for bid in self._row_wblocks.pop(row, (0, ()))[1]:
            self.pool.window.decref(bid)
        self._tables_all[row, :] = 0
        with self._cond:
            self._free_rows.append(row)
            self._cond.notify_all()

    def _window_cover(self, row: int, start: int, end: int) -> bool:
        """Before ``row`` writes positions ``start .. end`` (a chunk, or
        one token): give back the window layers' blocks behind the
        window of the query at ``start`` — the earliest query still to
        come sees keys ``start - window + 1`` on, and no later one sees
        further back — then take the blocks the write needs.  A block
        goes back exactly once: it leaves the row's list as it is
        released.  False = ``row`` was preempted hunting for a block."""
        bs = self.pool.block_size
        held = self._row_wblocks[row]
        live = max(0, start - self._window + 1) // bs
        while held[1] and held[0] < live:
            self.pool.window.decref(held[1].pop(0))
            self._wtables[row, held[0]] = 0
            held[0] += 1
            self._counts.window_blocks_returned += 1
        if not held[1]:
            held[0] = max(held[0], live)
        for bidx in range(held[0] + len(held[1]), (end - 1) // bs + 1):
            nb = self._take_block(row, window=True)
            if nb is None:
                return False
            held[1].append(nb)
            self._wtables[row, bidx] = nb
        return True

    def _cow_block(self, row: int, bidx: int) -> bool:
        """Copy-on-write: make table entry ``bidx`` exclusively owned
        before a write touches it (the shared case is an adopted
        partially-filled tail).  False = ``row`` was preempted while
        hunting for the copy's block."""
        bid = self._row_blocks[row][bidx]
        if self.pool.refcount(bid) == 1:
            return True
        nb = self._take_block(row)
        if nb is None:
            return False
        self.pool.copy_block(bid, nb)
        self.pool.decref(bid)
        self._row_blocks[row][bidx] = nb
        self._tables[row, bidx] = nb
        return True

    def _prefill_chunk_pass(self):
        """Advance prefills, occupancy-aware.  At healthy decode
        occupancy (>= half the rows active), ONE chunk per pass — that
        bounds the active streams' per-iteration stall (the point of
        chunking).  Below it, a decode iteration costs nearly the same
        almost-empty as full, so filling rows dominates: run as many
        chunks as there are prefilling rows before the next iteration
        (each picked shortest-remaining-first, so the cheapest prefill
        usually FINISHES within the pass rather than every row
        advancing one step).

        -> the pass's LAST chunk, prepared and packed but not launched
        (``_advance_prefill``), when the decode step is to run it;
        else None."""
        n = self.engine_cfg.max_slots
        todo = (1 if 2 * int(self._active.sum()) >= n
                else len(self._prefilling))
        ride = None
        for i in range(todo):
            if not self._prefilling or (
                    i and 2 * int(self._active.sum()) >= n):
                break
            ride = self._prefill_one_chunk(last=i == todo - 1)
        return ride

    def _prefill_one_chunk(self, last: bool = False):
        """Advance ONE prefilling request, shortest-remaining-first
        (ties by arrival).  SRF activates the cheapest prefill soonest
        (occupancy), and — critically for shared prefixes — SERIALIZES
        cold duplicates of the same head: one representative finishes
        and publishes the chain, the rest re-match and jump instead of
        each paying the whole train.  (Round-robin interleaves the
        duplicates so none publishes until nearly everyone has paid.)
        On prompt completion the request gets its first token and the
        row turns active (``_finish_prefill``).

        The pass's ``last`` chunk rides the decode step when rows are
        decoding and ONE program runs both.  The step's block hunt then
        comes first: it may preempt the very row whose chunk would
        ride, and a preempted row's chunk is never launched."""
        may_ride = (last and self._step_chunk is not None
                    and bool(self._active.any()))
        if may_ride:
            self._grow_rows()
            if not self._prefilling:
                return None
        with tracing.span("engine.prefill_chunk") as sp, \
                self._acct.phase("prefill_host"):
            return self._advance_prefill(sp, may_ride)

    def _advance_prefill(self, sp, may_ride: bool = False):
        """-> None once the chunk is launched, or, of a chunk that
        ``may_ride`` while a row still decodes, (row, tokens, the packed
        chunk) for ``_paged_decode_iteration`` to launch."""
        row = min(self._prefilling,
                  key=lambda r: (int(self._slot_req[r].prompt.size)
                                 - self._prefilling[r],
                                 self._slot_req[r].id))
        req = self._slot_req[row]
        if req.cancelled:                  # abandoned mid-prefill
            self._release_row(row)
            req._finish()
            self._note_done(req)
            return None
        pos = self._prefilling[row]
        bs = self.pool.block_size
        C = self.engine_cfg.prefill_chunk
        prompt = req.prompt
        n = int(prompt.size)
        if self.trie is not None:
            # re-match EVERY advance: a sibling admitted in the same
            # burst publishes the shared head at its own prefill
            # completion, and a colder copy of that head may be
            # mid-chunk-train right here — adopting the published chain
            # jumps its position forward and hands the replaced fresh
            # blocks back (concurrent shared-prefix requests would
            # otherwise each pay the full prefill).  A host-side token
            # walk per chunk is noise next to the chunk itself.
            ids2, hit2 = self.trie.match(prompt)
            if hit2 > pos:
                blocks = self._row_blocks[row]
                for i, nb in enumerate(ids2):
                    self.pool.decref(blocks[i])
                    blocks[i] = nb
                    self._tables[row, i] = nb
                with self._mlock:
                    # the prompt was counted at admission; fold in only
                    # the INCREMENTAL tokens the re-match won
                    self._counts.prefix_hit_tokens += hit2 - pos
                    self._counts.prefix_blocks_adopted += (len(ids2)
                                                           - pos // bs)
                pos = self._prefilling[row] = hit2
                if self._snapshots:
                    self._seam.row_admitted(self, row, ids2[-1])
                    if not req.prefix_hit_tokens:   # once a request
                        self._counts.state_snapshots_restored += 1
                    req.prefix_hit_tokens = hit2
            else:
                for bid in ids2:
                    self.pool.decref(bid)
        if (pos == 0 and n > self._full_width_over
                and 2 * int(self._active.sum())
                < self.engine_cfg.max_slots):
            # cold LONG prompt at low decode occupancy: ONE full-width
            # forward (the r10 prefill — gpt.forward with return_kv)
            # seeds every block at once through the table scatter — a
            # long chunk train pays a full-table gather per chunk, and
            # there is little decode cadence to protect.  Under real
            # load (occupancy >= half) long prompts take the chunked
            # path — bounded stall wins; short prompts always chunk
            # (one cheap window beats an S-wide forward).  (pos == 0
            # also means no adopted blocks — the table is exclusive.)
            sp.set(row=row, tokens=n, full_width=True)
            # its first token is sampled on its logits and waited for:
            # the pass in flight lands first
            self._drain("prefill")
            req.full_width_prefill = True
            self._counts.prefill_tokens += n
            padded = np.zeros((1, self.max_seq), np.int32)
            padded[0, :n] = prompt
            with self._acct.phase("dispatch"):
                self._launched |= _PREFILL
                logits, k_new, v_new = self._prefill(self.params, padded)
                self.pool.write_prefill(self._tables[row], k_new[:, 0],
                                        v_new[:, 0])
            self._finish_prefill(row, req, logits, (0, n - 1))
            return None
        # the write window [pos, pos+C) must only touch exclusively
        # owned blocks — only the FIRST can be shared (an adopted
        # partial tail), but the scan is cheap
        first = pos // bs
        last = min(-(-(pos + C) // bs), len(self._row_blocks[row]))
        for bidx in range(first, last):
            if not self._cow_block(row, bidx):
                return None                # row preempted under pressure
        n_q = min(C, n - pos)
        if self._window and not self._window_cover(row, pos, pos + n_q):
            return None
        if sp:
            sp.set(row=row, tokens=n_q, full_width=False,
                   state_rows=self.pool.state_rows_in_use)
        req.chunk_passes += 1
        counts = self._counts
        counts.chunk_passes += 1
        counts.prefill_tokens += n_q
        counts.chunk_keys += pos + n_q
        counts.chunk_query_keys += n_q * pos + n_q * (n_q + 1) // 2
        if self._linear:
            counts.linear_chunk_tokens += n_q
        if self._snapshots:
            counts.state_snapshots_written += (pos + n_q) // bs - pos // bs
        key_block = window_key_block(bs)
        walked = -(-(pos + n_q) // key_block)
        counts.chunk_key_blocks_walked += self._walk_layers * walked
        if self._latent:
            tiles = window_tiles(pos, n_q, C, key_block)
            counts.chunk_pairs_walked += tiles["pairs"]
            counts.chunk_tiles_plain += tiles["plain"]
            counts.chunk_tiles_diagonal += tiles["diagonal"]
        if self._window:
            # the same two of a window layer: keys in its queries' reach
            # and (query, key) pairs inside the window
            counts.window_chunk_keys += pos + n_q - max(
                0, pos - self._window + 1)
            counts.window_query_keys += int(np.minimum(
                np.arange(pos, pos + n_q) + 1, self._window).sum())
            # a window layer's walk starts at its first query's window
            counts.chunk_key_blocks_walked += self.cfg.n_window * (
                walked - max(0, pos - self._window + 1) // key_block)
        chunk_toks = np.zeros(C, np.int32)
        chunk_toks[:n_q] = prompt[pos:pos + n_q]
        with self._acct.phase("pack") as up:
            packed = pack_chunk(self._tables_all[row], chunk_toks, pos, row,
                                n_q)
            up.set(bytes=packed.nbytes)
        # (the copy-on-write above may have preempted the last active row)
        if may_ride and self._active.any():
            return row, n_q, packed
        self._chunks_ended()
        with self._acct.phase("dispatch"):
            self._launched |= _CHUNK
            logits = self._seam.run(self, self._chunk, packed)
        self._pass.chunks = (self._load[-1],
                             self._acct.launched)
        self._chunk_launched(row, n_q, logits, n_q - 1, self._load[-1])
        return None

    def _chunk_launched(self, row: int, n_q: int, logits, idx, owed,
                        in_step: bool = False) -> None:
        """A chunk of ``n_q`` tokens of ``row``'s prompt is on its way,
        by the chunk program or ``in_step``; ``logits[idx]`` are its
        last real position's, ``owed`` its program's int32 vector."""
        req = self._slot_req[row]
        new_pos = self._prefilling[row] + n_q
        if new_pos < int(req.prompt.size):
            self._prefilling[row] = new_pos
            return
        self._finish_prefill(row, req, logits, idx, owed, in_step)

    def _finish_prefill(self, row: int, req: GenerationRequest,
                        logits, idx, owed=None,
                        in_step: bool = False) -> None:
        """Prompt fully in cache (its last chunk dispatched): its first
        token is the last prompt position's (``logits[idx]``).

        A greedy first token is its program's own argmax, the last
        entry of ``owed`` (the program's int32 vector, still on the
        device), and the program has also written it to the row's entry
        of the token array: no slice and no sampling dispatched, and
        nothing waited for here.  The token is owed by the pass
        (``_Pass.first``), read and emitted where the pass lands
        (``_land``); the row joins the decode batch at the end of this
        pass without it (``_pass_done``), its input token on the device.
        So does the sampled first token of a chunk that ran
        ``in_step``: sampled here on the step's logits, it is owed by
        that dispatch (the host supplies it to the row's first step).
        Any other sampled request, and a full-width prefill (no
        ``owed``), take one sampling dispatch on the logits and wait
        for it, the pass in flight read first."""
        del self._prefilling[row]
        if self.trie is not None:
            # publish the prompt's full blocks NOW (not at finish):
            # concurrent requests sharing this head re-match at their
            # first chunk and skip the whole head prefill.  Full blocks
            # only — decode writes the partial tail, and sharing it here
            # would force copy-on-write against ourselves.
            full = (int(req.prompt.size) // self.pool.block_size) \
                * self.pool.block_size
            if full > 0:
                self._insert_prefix(row, req.prompt[:full])
                self._note_prefix_published(
                    req.prompt[:full],
                    self._row_blocks[row][:full // self.pool.block_size])
        if req.temperature != 0.0 and in_step:
            with self._acct.phase("emit") as sample:
                sample.set(rows=1)
                owed = gpt.sample_token(
                    logits[idx], temperature=req.temperature,
                    rng=req._next_rng())[None]
        elif owed is None or req.temperature != 0.0:
            self._drain("sampled")
            tok = self._first_token(req, logits[idx])
            self._emit_to(req, tok)
            self._start_decoding(row, req, tok)
            return
        elif self._spec is not None and not self._active.any():
            # a speculating engine drafts from the tokens on the host:
            # the row's own iteration follows in this pass, so its first
            # token is read here
            with self._acct.phase("wait") as fetch:
                tok = int(jax.device_get(owed)[-1])
                self._fetched(fetch, owed.nbytes)
            self._emit_to(req, tok)
            self._counts.tokens_greedy_on_device += 1
            self._start_decoding(row, req, tok)
            return
        self._owed[row] += 1
        self._pass.first.append(
            [row, req, owed, self._acct.launched])
        if self._active.any():
            self._pass.joining.append((row, req))
        else:                               # no decode to run behind:
            self._turn_active(row, req)     # its own follows the chunk

    def _emit_to(self, req: GenerationRequest, tok: int) -> None:
        """A prompt's first token goes out: the request's first, unless
        a preemption made it prefill again (then it is a gap to its
        stream, as a decode step's token is)."""
        if not req.tokens:
            self._first_tokens += 1
        req._emit(tok)

    def _turn_active(self, row: int, req: GenerationRequest) -> None:
        """``row``'s prompt is in the cache and its first token on its
        way: the row joins the decode batch, whose next step finds the
        token in the token array (or is handed it by the host, which
        will have read a sampled one by then: a row that samples is
        never stepped ahead of what was read).  A request of ONE new
        token stays out: the token that is owed evicts it."""
        self._positions[row] = int(req.prompt.size)
        self._tokens[row] = FEED
        self._active[row] = (len(req.tokens) + int(self._owed[row])
                             < req.max_new)

    def _pass_done(self) -> None:
        """The pass's programs are all dispatched.  Rows whose prompt
        ended in it turn active; the pass BEFORE it, still unread, is
        read now (``_land``): the device has this one queued behind it.
        This pass then stays in flight for the next to be dispatched
        behind — unless one of its rows samples or the engine
        speculates: then the next pass needs its tokens, and it is read
        at once (nothing in flight is the degenerate case of the same
        algorithm)."""
        this, before = self._pass, self._flight
        for row, req in this.joining:
            # (a row preempted since, by this pass's block hunt,
            # prefills again)
            if self._slot_req.get(row) is req:
                self._turn_active(row, req)
        self._pass, self._flight = _Pass(), None
        if before is not None:
            if self._launched:
                this.ahead = True
                self._counts.passes_launched_ahead += 1
            self._land(before)
        if not this.owes:
            return
        this.launched, self._launched = self._launched, 0
        if self._spec is not None or isinstance(this.logits, jax.Array):
            self._land(this)
        else:
            self._flight = this

    def _drain(self, reason: str) -> None:
        """Read the pass in flight NOW, before what the loop does next
        (no-op with none in flight): ``reason`` says what could not go
        ahead of it."""
        flight, self._flight = self._flight, None
        if flight is None:
            return
        flight.drained = True
        self._counts.passes_drained += 1
        self._drained_by[reason] = self._drained_by.get(reason, 0) + 1
        self._land(flight)

    def _chunks_ended(self) -> None:
        """Before a chunk PROGRAM is launched: those of the pass in
        flight have ended.  A chunk program returns its whole window's
        logits (``[chunk, vocab]`` float32: 411 MB at 1,024 x 100,352),
        which the runtime holds from the launch to the program's end
        whoever refers to them; a pass launched ahead would otherwise
        hold its chunks' beside the unread pass's, where the synchronous
        loop held one pass's.  The wait costs the device nothing: the
        unread pass's decode step is queued behind its chunks, and this
        chunk is dispatched while it runs."""
        flight = self._flight
        if flight is None or flight.chunks is None:
            return
        (vector, seq), flight.chunks = flight.chunks, None
        with self._acct.phase("wait") as fetch:
            jax.block_until_ready(vector)
            self._fetched(fetch, 0)
            self._acct.landed(seq)

    def _fetched(self, fetch, n_bytes: int, **attributes) -> None:
        """An ``engine.fetch`` span brought ``n_bytes`` to the host."""
        fetch.set(bytes=n_bytes, **attributes)
        self._counts.fetch_bytes += n_bytes

    def _land(self, flight: _Pass) -> None:
        """Read and emit what ``flight`` owes, and end its unit of the
        account.  ONE wait: the first tokens its chunks owe, each as
        soon as its program has ended (a chunk program's ends a decode
        step before the step's own vector; the token of a chunk that
        ran inside the step is the last of the step's integers), each
        emitted as it is read; then the rows' greedy tokens of its
        decode step — and, of a model that reports one, the expert load
        of the pass and of the chunks before it — in ONE small transfer.
        The logits stay on the device (a sampled row indexes them
        there).  Then the row loop: a token to its request's mailbox,
        and what the token causes (EOS or ``max_new``: the eviction).

        A stepped row whose request has left the row since (it emitted
        EOS a pass ago, when this pass was already dispatched) emits
        nothing: that step's token is nobody's, and what it wrote lies
        in blocks and a state row that every later owner's programs run
        behind."""
        counts, acct = self._counts, self._acct
        firsts, upto = [], 0
        rode = bool(flight.launched & _STEP_CHUNK)  # a chunk in the step
        with acct.phase("wait") as fetch:
            n_first = n_bytes = 0
            for row, req, owed, seq in flight.first:
                tok = int(jax.device_get(owed)[-1])
                upto = max(upto, seq)
                # the step's own vector (its chunk ended the prompt)
                # comes once, and is counted with the loads
                if not (flight.loads and owed is flight.loads[-1]):
                    n_first, n_bytes = n_first + 1, n_bytes + owed.nbytes
                self._emit_to(req, tok)
                if req.temperature == 0.0:
                    counts.tokens_greedy_on_device += 1
                else:
                    counts.tokens_sampled += 1
                firsts.append((row, req, tok))
            if flight.stepped is not None:
                loads = jax.device_get(flight.loads)
                upto = max(upto, flight.step_seq)
                self._greedy = loads[-1][self._seam.N_LOAD:]  # the step's
                self._seam.count(self, loads, rode)
                n_bytes += sum(load.nbytes for load in loads)
            self._fetched(fetch, n_bytes, first_tokens=n_first,
                          stepped=flight.stepped is not None, rode=rode)
            # the programs this wait saw the end of; the newer pass's
            # are still queued
            acct.landed(upto)
        stepped = 0
        with acct.phase("emit") as sample:
            for row, req, tok in firsts:
                self._token_out(row, req, tok)
            if flight.stepped is not None:
                logits = flight.logits
                greedy = self._seam.greedy(self, logits)
                for row, req in flight.stepped:
                    if self._slot_req.get(row) is not req:
                        continue          # left the row a pass ago
                    if req.temperature == 0.0:
                        tok = int(greedy[row])
                        counts.tokens_greedy_on_device += 1
                    else:
                        # its own rng, on its logits where they lie
                        tok = int(gpt.sample_token(
                            logits[row], temperature=req.temperature,
                            rng=req._next_rng()))
                        counts.tokens_sampled += 1
                    req._emit(tok)
                    stepped += 1
                    self._token_out(row, req, tok)
                sample.set(rows=stepped)
                del logits
                flight.logits = None
                # inside the phase the loop lets go of the interpreter
                # lock once, and every stream it has just woken takes
                # its turn (0.2 - 0.75 ms a pass on the chip: ``PERF.md``
                # section 5).  Releasing the step's logits did that
                # until a greedy pass stopped holding them; the device
                # has the next pass queued meanwhile
                time.sleep(0)
        with self._mlock:
            counts.row_steps += stepped
            counts.row_tokens += stepped
        self._landed_now = True
        self._unit_done(flight.launched, flight.ahead, flight.drained)

    def _token_out(self, row: int, req: GenerationRequest,
                   tok: int) -> None:
        """``tok`` of ``row`` has been emitted: it is the row's next
        input as the host knows it, unless a newer one is already on
        its way (the device fed it); the row is evicted if the token
        ended the request."""
        self._owed[row] -= 1
        if not self._owed[row]:
            self._tokens[row] = tok
        if self._request_finished(req, tok):
            self._paged_evict(row)

    def _start_decoding(self, row: int, req: GenerationRequest,
                        tok: int) -> None:
        """``tok``, the request's first token, has been read and
        emitted (a sampled one's, a full-width prefill's): the row
        turns active, or is evicted if that token ended the request."""
        if self._request_finished(req, tok):
            self._paged_evict(row)
            return
        self._tokens[row] = tok
        self._positions[row] = int(req.prompt.size)
        self._active[row] = True

    def _first_token(self, req: GenerationRequest, last_logits) -> int:
        """A first token no program chose itself (a sampled request's,
        a full-width prefill's), from the last prompt position's logits,
        which are still on the device: the sampling is one more
        dispatch (``engine.sample``), reading the token is the wait for
        the prefill program (``engine.fetch``)."""
        with self._acct.phase("emit") as sample:
            sample.set(rows=1)
            tok = gpt.sample_token(last_logits,
                                   temperature=req.temperature,
                                   rng=req._next_rng())
        self._counts.tokens_sampled += 1
        with self._acct.phase("wait") as fetch:
            self._fetched(fetch, 4)
            return int(tok)

    def _grow_row(self, row: int) -> bool:
        """Pre-step: make the row's write-target block exist and be
        exclusively owned (decode crossed a block boundary, or the tail
        is still shared).  False = ``row`` was preempted."""
        pos = int(self._positions[row])
        bidx = pos // self.pool.block_size
        blocks = self._row_blocks[row]
        if self._window and not self._window_cover(row, pos, pos + 1):
            return False
        if bidx < len(blocks):
            return self._cow_block(row, bidx)
        nb = self._take_block(row)
        if nb is None:
            return False
        blocks.append(nb)
        self._tables[row, bidx] = nb
        return True

    # ------------------------------------------------- speculative decode

    def _spec_cover(self, row: int, upto: int) -> int:
        """Charge the block budget for speculative positions UP FRONT:
        best-effort growth of the row's chain to cover positions
        through ``upto`` (the write-target block at ``positions[row]``
        already exists and is exclusive — _grow_row ran).  Allocation
        and prefix-LRU eviction only — speculation never PREEMPTS a
        neighbor for tokens that are merely hoped for.  Every granted
        block is appended to ``_row_blocks[row]`` immediately, so a
        later preemption of this row refunds the speculative charge
        with the rest of the chain (_release_row decrefs what the row
        holds, no separate ledger to forget).  Returns the last
        position actually covered; the caller caps the draft length."""
        bs = self.pool.block_size
        pos = int(self._positions[row])
        blocks = self._row_blocks[row]
        for bidx in range(pos // bs + 1, upto // bs + 1):
            if bidx < len(blocks):
                continue     # already covered (defensive: the chain is
            #                  trimmed to the write block after a pass)
            bid = self.pool.alloc()
            if bid is None and self.trie is not None \
                    and self.trie.evict(1):
                bid = self.pool.alloc()
            if bid is None:
                return bidx * bs - 1      # covered through prior block
            blocks.append(bid)
            self._tables[row, bidx] = bid
        return upto

    def _spec_rollback(self, row: int) -> None:
        """Refund the rejected part of the speculative block charge:
        drop chain blocks past the row's next write position (that
        block is KEPT — freeing it would thrash against _grow_row on
        the very next pass).  Rejected lanes' K/V beyond the committed
        length is garbage in owned blocks — masked now, overwritten by
        later decode — so rollback is pure budget accounting."""
        keep = int(self._positions[row]) // self.pool.block_size + 1
        blocks = self._row_blocks[row]
        old = len(blocks)
        if self.pool.release_tail(blocks, keep):
            self._tables[row, len(blocks):old] = 0

    def _spec_propose(self) -> tuple:
        """Per-row draft proposals for this pass.  Returns
        ``(drafts [n, k] int32, want [n] int32)``: row r offers
        ``want[r]`` draft tokens (0 = ride the verify pass as a plain
        one-token lane).  Sampled-temperature rows and rows at their
        max_new boundary never draft; block coverage is charged here
        (_spec_cover) and caps a draft the pool cannot hold."""
        ec = self.engine_cfg
        n, k = ec.max_slots, ec.speculate_k
        drafts = np.zeros((n, k), np.int32)
        want = np.zeros(n, np.int32)
        props = {}
        active_rows = 0
        for row in list(self._slot_req):
            if not self._active[row]:
                continue
            active_rows += 1
            req = self._slot_req[row]
            if req.temperature != 0.0:
                continue      # documented per-row fallback (submit())
            w = min(k, req.max_new - len(req.tokens) - 1)
            if w <= 0:
                continue
            if self._spec == "ngram":
                hist = np.concatenate(
                    [req.prompt,
                     np.asarray(req.tokens[req._consumed:], np.int32)])
                prop = ngram_propose(hist, w)
                if prop.size == 0:
                    continue
                props[row] = prop
                w = min(w, int(prop.size))
            want[row] = w
        # batch-coverage gate: the widened verify prices EVERY active
        # row at W lanes, so a pass where only a few rows draft costs
        # more than the plain step saves on the rest of the batch —
        # speculate only when at least half the batch drafts.  Decided
        # BEFORE blocks are charged or draft steps run, so a skipped
        # pass pays nothing.
        if int((want > 0).sum()) * 2 < active_rows:
            want[:] = 0
            return drafts, want
        for row in np.nonzero(want)[0]:
            pos = int(self._positions[row])
            w = min(int(want[row]),
                    self._spec_cover(row, pos + int(want[row])) - pos)
            if w <= 0:                         # pool cannot hold a draft
                want[row] = 0
                continue
            want[row] = w
            if self._spec == "ngram":
                drafts[row, :w] = props[row][:w]
        if self._spec == "self" and want.any():
            self._spec_self_draft(drafts, want)
        return drafts, want

    def _spec_self_draft(self, drafts: np.ndarray,
                         want: np.ndarray) -> None:
        """Fill ``drafts`` with ONE fused draft-burst call: the whole
        k-step autoregressive truncated-layer loop runs on device
        (argmax feeding the next step), so the host pays a single
        dispatch instead of k round-trips.  Rows draft ``want[row]``
        tokens; dead rows sit out via the burst's lane mask.  The
        drafted K/V for layers < draft_layers lands in the REAL pool —
        identical to what the full model writes there, and the verify
        pass rewrites all drafted positions at all layers anyway."""
        w = np.where(self._active, want, 0).astype(np.int32)
        with self._acct.phase("dispatch"):
            self._launched |= _SPEC
            toks, kp, vp = self._draft(
                self.params, self.pool.k, self.pool.v,
                jnp.asarray(self._tables), jnp.asarray(self._tokens),
                jnp.asarray(self._positions), jnp.asarray(w))
            self.pool.swap(kp, vp)
        with self._acct.phase("wait") as fetch:
            toks = np.asarray(toks)
            self._fetched(fetch, toks.nbytes)
        m = np.arange(toks.shape[1])[None, :] < w[:, None]
        drafts[:, :toks.shape[1]][m] = toks[m]

    def _speculative_iteration(self) -> bool:
        """One draft-then-verify pass over the whole batch; False = no
        drafts this pass (caller falls back to the plain step).  The
        accept rule is greedy and token-exact: lane j's verify logits
        are the model's next-token logits GIVEN the drafted prefix, so
        walking lanes while ``argmax == draft`` — and emitting the
        argmax CORRECTION at the first mismatch — reproduces the
        non-speculative greedy stream exactly (>= 1 token per pass).
        Committed lanes' K/V is already in the pool from the verify
        scatter; the rejected tail's block charge is rolled back."""
        drafts, want = self._spec_propose()
        if not want.any():
            return False
        with tracing.span("engine.decode", speculative=True) as sp:
            if sp:
                sp.set(active=int(self._active.sum()))
            self._spec_verify(drafts, want)
        return True

    def _spec_verify(self, drafts: np.ndarray, want: np.ndarray) -> None:
        """The widened verify step over this pass's drafts, then the
        accept/reject walk."""
        force_reject = False
        ctx = self._chaos("infer_speculate",
                          rows=int((want > 0).sum()),
                          drafted=int(want.sum()))
        if ctx is not None and ctx.get("reject_all"):
            # forced FULL rejection (chaos): the verify pass still
            # runs and every draft is discarded — exercising the whole
            # charge -> verify -> reject -> rollback path with parity
            # intact (the correction token is the plain step's token)
            force_reject = True
        n = self.engine_cfg.max_slots
        W = self.engine_cfg.speculate_k + 1
        tok_mat = np.zeros((n, W), np.int32)
        tok_mat[:, 0] = self._tokens
        tok_mat[:, 1:] = drafts
        n_tok = np.where(self._active, want + 1, 1).astype(np.int32)
        with self._acct.phase("pack") as up:
            args = (jnp.asarray(self._tables), jnp.asarray(tok_mat),
                    jnp.asarray(self._positions), jnp.asarray(self._active),
                    jnp.asarray(n_tok))
            if up:
                up.set(bytes=sum(a.nbytes for a in args))
        with self._acct.phase("dispatch"):
            self._launched |= _SPEC
            logits, k, v = self._verify(self.params, self.pool.k,
                                        self.pool.v, *args)
            self.pool.swap(k, v)
        with self._acct.phase("wait") as fetch:
            logits = np.asarray(logits)           # [n, W, V]
            self._fetched(fetch, logits.nbytes)
        with self._acct.phase("emit") as sample:
            with self._mlock:
                self._counts.decode_iterations += 1
                self._counts.spec_passes += 1
                self._counts.occupancy_sum += (float(self._active.sum())
                                               / self.engine_cfg.max_slots)
            stepped, emitted = self._spec_accept(logits, drafts, want,
                                                 force_reject)
            sample.set(rows=stepped)
        self._counts.tokens_sampled += emitted
        with self._mlock:
            self._counts.row_steps += stepped
            self._counts.row_tokens += emitted

    def _spec_accept(self, logits: np.ndarray, drafts: np.ndarray,
                     want: np.ndarray, force_reject: bool) -> tuple:
        """The greedy accept/reject walk over a verify pass's logits
        ``[n, W, V]``; returns (rows stepped, tokens emitted)."""
        n, W = logits.shape[:2]
        greedy = np.asarray(gpt.sample_token(
            logits.reshape(n * W, -1), temperature=0.0)).reshape(n, W)
        stepped = emitted = 0
        for row in list(self._slot_req):
            if not self._active[row]:     # prefilling rows ride along
                continue
            req = self._slot_req[row]
            w = int(want[row])
            if req.temperature != 0.0:
                # sampled lane 0 == the plain step's logits: one token,
                # per-request rng — byte-identical to the fallback path
                tok = int(gpt.sample_token(logits[row, 0],
                                           temperature=req.temperature,
                                           rng=req._next_rng()))
                req._emit(tok)
                stepped += 1
                emitted += 1
                self._positions[row] += 1
                self._tokens[row] = tok
                if self._request_finished(req, tok):
                    self._paged_evict(row)
                continue
            accepted = 0
            finished = False
            for j in range(w + 1):
                tok = int(greedy[row, j])
                req._emit(tok)
                emitted += 1
                self._positions[row] += 1
                self._tokens[row] = tok
                if self._request_finished(req, tok):
                    finished = True       # EOS / max_new mid-burst
                    break
                if j < w and not force_reject \
                        and int(drafts[row, j]) == tok:
                    accepted += 1         # lane j+1's input was right
                    continue
                break                     # first mismatch: corrected
            stepped += 1
            req.spec_drafted += w
            req.spec_accepted += accepted
            with self._mlock:
                self._counts.spec_drafted_tokens += w
                self._counts.spec_accepted_tokens += accepted
            if finished:
                self._paged_evict(row)    # releases the whole chain
            else:
                self._spec_rollback(row)
        return stepped, emitted

    def _grow_rows(self) -> None:
        """Before a decode step: every active row's write-target block
        exists and is its own (``_grow_row``: the block hunt, which may
        preempt); cancelled rows are evicted.  Rows it has seen to
        before in the same pass cost a look."""
        with self._acct.phase("grow") as sp:
            preempted0 = self._counts.preemptions
            for row in [r for r in list(self._slot_req) if self._active[r]]:
                req = self._slot_req.get(row)
                if req is None or not self._active[row]:
                    continue              # preempted by an earlier row's
                #                           block hunt this very pass
                if req.cancelled:         # abandoned: free for live work
                    # (its tokens in flight are read first, as they
                    # would have been by now)
                    self._drain("cancel")
                    if self._slot_req.get(row) is req:
                        self._paged_evict(row, cache_prefix=False)
                    continue
                self._grow_row(row)       # False = row preempted; skip
            sp.set(preempted=self._counts.preemptions - preempted0)

    def _samples(self) -> bool:
        """Does a row the next step steps sample its token?"""
        return any(self._active[row] and req.temperature != 0.0
                   for row, req in self._slot_req.items())

    def _paged_decode_iteration(self, ride=None) -> bool:
        """One decode step over the active rows, dispatched; then the
        pass is done: the pass before it is read (``_pass_done``,
        inside this iteration's span).  ``ride``: the chunk this pass
        prepared for the step to run, (row, tokens, packed); the block
        hunt was made before it was packed.  -> whether the pass was
        ended here."""
        if ride is None:
            self._grow_rows()
        if not self._active.any():
            return False
        # draft-then-verify when configured; False = no row produced a
        # draft this pass (nothing to verify) — the plain one-token
        # step below is the fallback, so an all-sampled or draft-dry
        # batch pays zero speculation overhead.  A speculative pass
        # spans the wall time of ~3 plain steps, and the loop normally
        # advances one prefill chunk per pass — so after a wide pass,
        # run the extra chunks the interleave missed.  Without the
        # compensation, speculation cuts chunk cadence (= TTFT of
        # admitting requests) by the pass width; with it, admission
        # latency stays flat and decode-only passes pay nothing.
        if (self._spec is not None
                and self._speculative_iteration()):
            for _ in range(2):
                if not self._prefilling:
                    break
                self._prefill_one_chunk()
            return False
        with tracing.span("engine.decode", speculative=False) as sp:
            if sp:
                sp.set(active=int(self._active.sum()),
                       state_rows=self.pool.state_rows_in_use,
                       chunk_tokens=ride[1] if ride else 0)
            this, program = self._pass, self._step
            with self._acct.phase("pack") as up:
                packed = pack_step(self._tables_all, self._tokens,
                                   self._positions, self._active)
                if ride:
                    program = self._step_chunk
                    packed = pack_step_chunk(packed, ride[2])
                up.set(bytes=packed.nbytes)
            with self._acct.phase("dispatch"):
                self._launched |= _STEP_CHUNK if ride else _STEP
                logits = self._seam.run(self, program, packed)
            # the step's fetch brings every vector no pass has taken yet
            this.loads, self._load = self._load, []
            this.step_seq = self._acct.launched
            # a pass none of whose rows samples lets go of its logits
            # here: the next pass's are made while this one is unread
            this.logits = (logits if self._sampling and self._samples()
                           else jax.ShapeDtypeStruct(logits.shape,
                                                     logits.dtype))
            if ride:
                self._counts.chunks_in_step += 1
                self._chunk_launched(*ride[:2], logits,
                                     self.engine_cfg.max_slots,
                                     this.loads[-1], in_step=True)
            del logits
            if self._mesh is not None:
                # every shard just committed its slice of the donated
                # scatter — the point where a multi-host straggler or
                # mid-commit death would bite, so it is chaos-testable
                self._chaos("infer_shard_commit",
                            tp_shards=self.pool.heads_shards)
            with self._acct.phase("emit"):
                self._stepped(this)
            self._pass_done()
        return True

    def _stepped(self, this: _Pass) -> None:
        """A decode step over the active rows is on its way (the row
        loop's other half, in the same phase ``emit``): the pass keeps
        who was stepped, what the step reads and writes is counted from
        where the rows stand, and the rows move on without its tokens —
        a position further, the next input token on the device, and out
        of the next step if the token on its way is the request's
        last."""
        this.stepped = stepped = [
            (row, req) for row, req in self._slot_req.items()
            if self._active[row]]
        counts = self._counts
        at = self._positions[self._active]
        bs = self.engine_cfg.kv_block_size
        with self._mlock:
            counts.decode_iterations += 1
            counts.occupancy_sum += len(at) / self.engine_cfg.max_slots
        counts.kv_blocks_attended += int((at // bs + 1).sum())
        counts.kv_blocks_tabled += self._tables.size
        if self._window:
            # the blocks that hold a key inside a live row's
            # window; what the rows hold of the window layers'
            # pool, beside what ONE table a row would hold
            kv = at + 1
            counts.window_blocks_attended += int(
                (-(-kv // bs) - np.maximum(kv - self._window, 0) // bs).sum())
            counts.window_blocks_resident_sum += self.pool.window.n_used
            counts.window_blocks_one_table_sum += self.pool.n_used
        if self._linear:
            counts.linear_state_rows_advanced += len(at)
        if self._snapshots:
            counts.state_snapshots_written += int(((at + 1) % bs == 0).sum())
        for row, req in stepped:
            self._positions[row] += 1
            self._tokens[row] = FEED
            self._owed[row] += 1
            if len(req.tokens) + self._owed[row] >= req.max_new:
                self._active[row] = False

    def _paged_evict(self, row: int, cache_prefix: bool = True) -> None:
        """Natural eviction (EOS / max-tokens / cancel): donate the
        clean KV chain to the prefix index, then release the row."""
        req = self._slot_req[row]
        if cache_prefix and not req.cancelled:
            self._insert_prefix(
                row, self._sequence(req)[:self._cached(row, req)])
        self._release_row(row)
        req._finish()
        self._note_done(req)

    def _request_finished(self, req: GenerationRequest, tok: int) -> bool:
        with self._mlock:
            self._counts.generated_tokens += 1
        eos = self.engine_cfg.eos_token
        return (len(req.tokens) >= req.max_new
                or (eos is not None and tok == eos))

    def _note_done(self, req: GenerationRequest) -> None:
        with self._mlock:
            self._counts.requests_completed += 1
        self._fr_note(req)

    def _fail_all(self, e: BaseException) -> None:
        # what was launched BEFORE the program that failed (the pass in
        # flight; this pass's chunks, where its step failed) and is
        # owed is the streams', if its fetch still brings it
        this, self._pass = self._pass, _Pass()
        flight, self._flight = self._flight, None
        this.launched = self._launched
        for owing in (flight, this):
            if owing is not None and owing.owes:
                try:
                    self._land(owing)
                except Exception:
                    pass
        # a failed chunk/step may have invalidated the DONATED pool
        # buffers; reallocate the pool so the engine keeps serving, drop
        # every reference, and — critically — clear the prefix index:
        # cached prefixes would otherwise point at zeroed blocks and
        # silently corrupt every later prefix hit
        failed = [self._slot_req.pop(row)
                  for row in list(self._slot_req)]
        self._active[:] = False
        self._owed[:] = 0
        self._sampling = 0
        self._prefilling.clear()
        self._load.clear()
        self._feed = self._zero_feed()  # donated, as the pools are
        self._acct.in_flight = 0        # what was launched has failed
        self._row_blocks.clear()
        self._row_wblocks.clear()
        self._tables_all[:, :] = 0
        if self.trie is not None:
            self.trie.clear()
        self.pool.reset()
        with self._cond:
            self._free_rows = list(
                range(self.engine_cfg.max_slots - 1, -1, -1))
            self._cond.notify_all()
        # unblock the waiters only AFTER the pool/index are
        # consistent again, so a result() caller reading stats sees
        # the recovered state, not the mid-teardown one
        for req in failed:
            req._finish(e)

    # ------------------------------------------------------------- admin

    def drain(self) -> None:
        """Begin a graceful drain (planned scale-down): admit nothing
        new — ``submit()`` raises the typed EngineDrainingError so the
        fleet re-routes instead of 500ing — hand already-QUEUED waiters
        back for re-routing the same way, and let the in-flight slots
        decode to completion.  The engine reads drained once
        ``active_slots == 0``; the controller then tears it down.
        Idempotent; a no-op on a stopped engine."""
        with self._cond:
            if self._stopped or self._draining:
                return
            self._draining = True
            waiting, self._waiting = self._waiting, []
            self._cond.notify_all()
        err = EngineDrainingError(
            "engine is draining (planned scale-down)")
        for r in waiting:
            if not r.done:
                r._finish(err)

    # ------------------------------------------- cluster prefix plane

    def _run_ops_locked(self) -> None:
        """Execute queued cross-thread ops on the loop thread (called
        under ``_cond``).  Op errors resolve into the caller's box, the
        loop itself never dies for a bad op.  Op closures must not take
        ``_cond`` (they run holding it) — pool/trie access is safe, the
        row/slot helpers are not."""
        while self._ops:
            fn, box = self._ops.pop(0)
            try:
                box["result"] = fn()
            except BaseException as e:
                box["error"] = e
            box["done"] = True
        self._cond.notify_all()

    def _run_op(self, fn, timeout: float = 10.0):
        """Run ``fn`` on the loop thread and wait for its result — the
        bridge that lets another thread (the fleet's prefix plane)
        touch the loop-thread-only pool/trie.  Raises the op's own
        error, EngineStoppedError on a dead engine, PrefixUnavailable
        on timeout — all of which the caller treats as 'recompute
        locally'."""
        box = {"done": False, "result": None, "error": None}
        deadline = time.monotonic() + timeout
        with self._cond:
            if self._stopped:
                raise EngineStoppedError("engine is shut down")
            self._ops.append((fn, box))
            self._cond.notify_all()
            while not box["done"]:
                left = deadline - time.monotonic()
                if left <= 0:
                    raise PrefixUnavailable(
                        f"engine op timed out after {timeout}s")
                self._cond.wait(left)
        if box["error"] is not None:
            raise box["error"]
        return box["result"]

    def _note_prefix_published(self, tokens: np.ndarray, blocks) -> None:
        """Record a local-trie publication for the cluster directory
        (drained by ``prefix_export``).  Bounded: a fleet that never
        drains costs at most 64 stale records, not unbounded growth."""
        with self._mlock:
            if len(self._prefix_outbox) >= 64:
                self._prefix_outbox.pop(0)
            self._prefix_outbox.append({
                "tokens": [int(t) for t in tokens],
                "blocks": [int(b) for b in blocks],
                "block_size": self.pool.block_size,
                "generation": self.pool.generation,
                # conduit address: lets a FOREIGN fleet process fetch
                # through the node plane's block_fetch handler, which
                # resolves this name in the module engine registry
                "engine": self.name,
            })

    def prefix_export(self) -> list:
        """Drain the prefix publication outbox (cluster-directory feed).
        Empty on an engine with no prefix index — the plane then has
        nothing to advertise for this replica."""
        if self.trie is None:
            return []
        with self._mlock:
            out, self._prefix_outbox = self._prefix_outbox, []
        return out

    def prefix_extract(self, tokens, generation: int) -> dict:
        """EXPORT side of replica→replica prefix adoption: gather the
        K/V bytes of a cached block-aligned prefix to host arrays.
        Re-validates everything the directory advertised — the pool
        generation (StalePrefixGeneration when a donated-pool recovery
        reset it: old block ids must never be served) and the live trie
        (PrefixUnavailable when eviction raced the fetch).  Runs on the
        loop thread via the op queue; a dying engine resolves the op as
        EngineStoppedError.  All three are PrefixTransferError /
        ReplicaDeadError shapes the adopter maps to local recompute."""
        if self.trie is None:
            raise PrefixUnavailable("engine has no prefix index")
        if self.pool.v is None:
            raise PrefixUnavailable("a pool whose values are a view of its "
                                    "keys has no interchange format yet")
        toks = np.asarray(list(tokens), np.int32)
        bs = self.pool.block_size
        n = int(toks.size)
        if n < bs or n % bs:
            raise PrefixUnavailable(
                f"prefix length {n} is not block-aligned (bs={bs})")
        want = int(generation)

        def op():
            if want != self.pool.generation:
                raise StalePrefixGeneration(
                    f"pool generation is {self.pool.generation}, entry "
                    f"advertised {want} (pool was reset since publish)")
            # the trie's match caps at len-1 (the last token's logits
            # always rerun); one probe token past the prefix lets the
            # full chain match
            probe = np.concatenate([toks, np.zeros(1, np.int32)])
            ids, hit = self.trie.match(probe)
            try:
                if hit < n:
                    raise PrefixUnavailable(
                        f"only {hit}/{n} prefix tokens still cached "
                        "(evicted since publish)")
                k, v = self.pool.read_blocks(ids[:n // bs])
            finally:
                for bid in ids:
                    self.pool.decref(bid)
            return {"k": k, "v": v, "generation": self.pool.generation,
                    "n_tokens": n, "block_size": bs}
        return self._run_op(op)

    def prefix_install(self, tokens, payload: dict) -> dict:
        """INSTALL side of prefix adoption: write fetched block K/V
        into freshly-allocated local blocks and publish them to the
        local trie — the next admission's match then adopts them under
        the normal refcount/CoW rules, indistinguishable from a locally
        computed prefix.  Never preempts live rows: under block
        pressure it evicts unreferenced cached prefixes only, then
        gives up with PrefixInstallPressure (adoption is an
        optimization; real work is not)."""
        if self.trie is None:
            raise PrefixUnavailable("engine has no prefix index")
        if self.pool.v is None:
            raise PrefixUnavailable("a pool whose values are a view of its "
                                    "keys has no interchange format yet")
        toks = np.asarray(list(tokens), np.int32)
        bs = self.pool.block_size
        n = int(toks.size)
        if n < bs or n % bs:
            raise PrefixUnavailable(
                f"prefix length {n} is not block-aligned (bs={bs})")
        if int(payload.get("block_size", -1)) != bs:
            raise PrefixUnavailable(
                f"holder block_size {payload.get('block_size')} != "
                f"local {bs} (geometry mismatch)")
        n_b = n // bs
        k_new, v_new = payload["k"], payload["v"]
        expect = (self.cfg.n_layers, n_b, self.cfg.n_heads, bs,
                  self.cfg.head_dim)
        if tuple(np.shape(k_new)) != expect \
                or tuple(np.shape(v_new)) != expect:
            raise PrefixUnavailable(
                f"payload shape {np.shape(k_new)} != expected {expect}")

        def op():
            probe = np.concatenate([toks, np.zeros(1, np.int32)])
            ids, hit = self.trie.match(probe)
            for bid in ids:
                self.pool.decref(bid)
            if hit >= n:
                return {"installed": 0, "already": True}
            fresh = []
            for _ in range(n_b):
                bid = self.pool.alloc()
                while bid is None and self.trie.evict(1):
                    bid = self.pool.alloc()
                if bid is None:
                    for b in fresh:
                        self.pool.decref(b)
                    raise PrefixInstallPressure(
                        f"pool cannot hold a {n_b}-block adopted prefix "
                        "without preempting live requests")
                fresh.append(bid)
            self.pool.write_blocks_at(fresh, k_new, v_new)
            self.trie.insert(toks, fresh)
            # the trie holds its own references now (and dedupe dropped
            # any chunk it already had); releasing ours frees exactly
            # the duplicates — the leak audit in tests pins this
            for b in fresh:
                self.pool.decref(b)
            return {"installed": n_b, "already": False}
        return self._run_op(op)

    def stats(self) -> dict:
        """Every row of ``serve/engine_stats.py``: the counters as they
        stand, the gauges read off the engine here, the ratios."""
        with self._cond:
            waiting = len(self._waiting)
            interactive = sum(1 for r in self._waiting
                              if r.priority <= PRIORITY_INTERACTIVE)
            stopped = self._stopped
            draining = self._draining
            occupied = self.engine_cfg.max_slots - len(self._free_rows)
        out = self._counts.snapshot(self._mlock)
        pool = self.pool.stats()
        mesh = self._mesh
        out.update(
            max_slots=self.engine_cfg.max_slots,
            active_slots=occupied,
            free_slots=self.engine_cfg.max_slots - occupied,
            waiting_requests=waiting,
            waiting_interactive=interactive,
            stopped=stopped,
            draining=draining,
            cache_bytes=pool["bytes_total"],
            cache_bytes_per_device=pool["bytes_per_device"],
            block_size=pool["block_size"],
            blocks_total=pool["blocks_total"],
            blocks_per_device=pool["blocks_per_device"],
            blocks_free=pool["blocks_free"],
            prefix_cached_blocks=(self.trie.cached_blocks
                                  if self.trie is not None else 0),
            pool_generation=pool["generation"],
            loop_account={**self._acct.snapshot(),
                          "passes": self._passes,
                          "drained_by": dict(self._drained_by),
                          "t_made_ns": self._acct.t_made_ns,
                          "t_ns": self._acct.t_ns},
            speculate=self._spec,
            mesh_devices=(int(np.prod(list(mesh.devices.shape)))
                          if mesh is not None else 1),
            mesh_axes=(dict(zip(mesh.axis_names, mesh.devices.shape))
                       if mesh is not None else {}),
            tp_shards=self.pool.heads_shards,
            state_bytes=pool["state_bytes"],
            state_rows_in_use=pool["state_rows_in_use"],
            state_snapshot_bytes=pool["state_snapshot_bytes"],
            window_blocks_total=pool["window_blocks_total"],
            window_blocks_held=pool["window_blocks_held"],
            weight_bytes=self._weight_bytes,
            weight_bytes_cast_per_pass=self._weight_bytes_cast)
        out.update(engine_stats.ratios(out))
        for key in engine_stats.OPERANDS_ONLY:
            del out[key]
        return out

    def shutdown(self, timeout: float = 5.0) -> None:
        with self._cond:
            self._stopped = True
            self._cond.notify_all()
        self._thread.join(timeout=timeout)


def metrics_snapshot() -> list:
    """Per-engine gauges/counters in the metrics exporter's tuple format
    (ray_tpu.metrics.render_prometheus); aggregated by the serve-layer
    /metrics endpoint alongside the per-deployment request counters.
    One series a row of ``serve/engine_stats.py`` that names one, in the
    table's order, then the loop account's two by phase."""
    with _registry_lock:
        engines = dict(_ENGINES)
    rows = [row for row in engine_stats.ROWS if row.metric]
    series = {row.key: {} for row in rows}
    loop_s, starved_s = {}, {}
    for name, eng in sorted(engines.items()):
        st = eng.stats()
        # per-replica/per-model labels (serve fleet sets them) keep a
        # multi-replica fleet from collapsing into one ambiguous series
        key = ((("engine", name),)
               + tuple(sorted(eng.labels.items())))
        for row in rows:
            series[row.key][key] = float(st[row.key])
        # the loop thread's time by phase: over the phases the first
        # adds up to the thread's wall time
        acct = st["loop_account"]
        for by_phase, k in ((loop_s, "ns"), (starved_s, "starved_ns")):
            for phase, ns in (*acct[k].items(), (
                    tracing.UNACCOUNTED, acct["unaccounted_" + k])):
                by_phase[key + (("phase", phase),)] = ns / 1e9
    zero = {(("engine", "none"),): 0.0}
    return [
        *((row.metric, row.metric_kind, row.help, series[row.key] or zero)
          for row in rows),
        ("ray_tpu_inference_loop_seconds_total", "counter",
         "The engine loop thread's wall time by phase (self time; "
         "`wait` is the wait for the device, `parked` an engine with no "
         "work, `unaccounted` what no span site covers)", loop_s or zero),
        ("ray_tpu_inference_loop_starved_seconds_total", "counter",
         "The part of each phase's time during which the loop had no "
         "program in flight on the device", starved_s or zero),
    ]
