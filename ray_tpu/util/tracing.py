"""Spans: the one tracing primitive of every layer.

Reference capability: python/ray/util/tracing/tracing_helper.py — when
tracing is enabled, every ``.remote()`` call opens a client span whose
context is injected into the task spec, and the executing worker opens
a server span as its child, so cross-process traces stitch together in
one trace id.  The same primitive marks the serving engine's passes,
a request's lifecycle, the serve front and the trainer's loop.

Dependency-light (no opentelemetry wheel in this image): a span is a
small object with W3C-style ids (128-bit trace id, 64-bit span id: a
per-process random prefix plus a counter); context propagates
in-process via a contextvar and across threads or processes explicitly
(``inject_context()`` -> ``span(..., parent=ctx)``).

The off/on contract:

  * **Off** (no ``enable_tracing()`` / ``RAY_TPU_TRACING``, no
    ``jax.profiler`` session): ``span()`` is a flag test and returns
    the shared ``NOOP``: no allocation, no lock, no id, no ring append.
    ``NOOP`` is falsy, so a site guards attributes that cost something
    to compute with ``if sp:``.
  * **On**: ``enable_tracing()`` / ``RAY_TPU_TRACING`` / a set
    ``RAY_TPU_TRACE_DIR``, OR a ``jax.profiler`` session is active.
    While a session is active every span is also entered as a
    ``jax.profiler.TraceAnnotation`` of the same name (a span with
    ``step_num=`` as a ``StepTraceAnnotation``), so it lies on the host
    plane of the same ``.xplane.pb`` as the device's ``XLA Ops`` line,
    on the profiler's own timeline.  A process that has not imported
    ``jax`` never imports it here.
  * **Always on**: ``span(..., always=True)`` and ``record_span()``
    (a span built from stamps already taken) record whatever the flag
    says: the serve front's request span and a request's three
    lifecycle spans, a handful of ring appends per request.  They are
    not entered as profiler annotations (they cross ``await``s and
    threads).
  * **The account** (``Account``): one thread's wall time by phase,
    counted at that thread's span sites whatever the flag says.  ``with
    account.phase(name) as sp`` takes the two stamps always and adds
    the phase's self time to plain integer counters; only while
    tracing is on does it also yield the site's span, built from the
    same two stamps, else ``NOOP``.  An account is written by its ONE
    thread without a lock; other threads (``engine_stats()``) read its
    counters as whole Python ints, each one sound, a snapshot of
    several at most one phase boundary apart.  Where the thread ends a
    unit of its time (the engine's scheduler pass, or a stretch with no
    work) ``pass_done(kind, ..)`` adds the unit's time, split into host
    and wait, to its KIND's row, and to ``gaps``, a ``Histogram`` of
    what a token waited.
  * **The histogram** (``Histogram``): durations counted into
    geometric buckets by integer comparisons, one writer, no lock;
    cumulative, so a reader differences two snapshots and interpolates
    a quantile inside a bucket (``Histogram.edge_ns``).
  * **The chain** (``record_account``): what is counted always reaches
    the ring as ONE cumulative always-on span once its writer has spent
    a second since the last, each starting where the last one ended and
    saying whether a profiler session touched its interval
    (``engine.account``, ``front.account``).

Stamps are ``time.monotonic_ns()``, the clock of the benchmark's own
stamps; in a profiler session an annotation also carries its span's
``t0_ns`` as metadata, which places that clock on the session's own
(an event's ``start_ns`` counts from the session's start), and with it
the spans that are no annotations.  Finished spans go to a bounded
in-memory ring, which counts what it evicts (``ring_dropped()``);
``get_finished_spans()`` exports them as dicts whose ``t0_ns`` /
``t1_ns`` are the stamps and whose wall-clock ``start`` / ``end`` are
derived from one per-process anchor (wall minus monotonic, taken
once).  With ``RAY_TPU_TRACE_DIR`` unset no span touches a file.  With
it set, every finished span is also written to one JSONL
file per process, batched: ``_emit`` appends to a pending list under
the buffer lock (the one the ring's append and count are under) and
the actual ``write+flush`` runs under a separate I/O lock, draining everything pending in one write.  Threads that find
the I/O lock busy leave their span pending for the current writer —
the hot path never blocks on disk, and no lock is held across a file
write but the I/O lock itself.  ``flush_spans()`` (also run at exit
and by ``collect_spans``) force-drains; ``collect_spans()`` merges
every process's file.
"""

from __future__ import annotations

import atexit
import bisect
import collections
import contextvars
import glob
import itertools
import json
import operator
import os
import secrets
import sys
import threading
import time
from typing import Any, List, Optional

_current: contextvars.ContextVar[Optional[dict]] = contextvars.ContextVar(
    "ray_tpu_trace_ctx", default=None)

# what a reader needs: a request leaves four spans while tracing is off
# (the last ~2,000 requests), a traced pass ~20 (~400 passes)
RING_SIZE = 8_192
_ring: "collections.deque[Span]" = collections.deque(maxlen=RING_SIZE)
_lock = threading.Lock()          # ring append + count, pending list
_ring_dropped = 0                 # spans the full ring has evicted
_io_lock = threading.Lock()       # file open/write/flush
_pending: List["Span"] = []       # spans awaiting a file write
_file = None
_file_dir: Optional[str] = None   # dir _file was opened in (reset on change)
_enabled: Optional[bool] = None

# wall clock minus monotonic clock, once: export derives start/end
_ANCHOR_NS = time.time_ns() - time.monotonic_ns()

_counter = itertools.count(1)
_span_prefix = secrets.token_hex(4)
_trace_prefix = secrets.token_hex(12)


def _reseed() -> None:
    """A forked child must not repeat its parent's ids."""
    global _span_prefix, _trace_prefix
    _span_prefix, _trace_prefix = secrets.token_hex(4), secrets.token_hex(12)


os.register_at_fork(after_in_child=_reseed)

# jax.profiler's annotation classes, looked up once jax is imported
_annotation = None
_step_annotation = None


def profiling() -> bool:
    """True while a ``jax.profiler`` session is active in this process."""
    global _annotation, _step_annotation
    if _annotation is None:
        prof = getattr(sys.modules.get("jax"), "profiler", None)
        if prof is None:
            return False
        _annotation, _step_annotation = (prof.TraceAnnotation,
                                         prof.StepTraceAnnotation)
    return _annotation.is_enabled()


def tracing_enabled() -> bool:
    """Flag gate (reference: tracing enabled via ray.init tracing
    startup hook / RAY_TRACING_ENABLED)."""
    global _enabled
    if _enabled is None:
        _enabled = os.environ.get("RAY_TPU_TRACING", "").lower() in (
            "1", "true", "yes") or bool(os.environ.get("RAY_TPU_TRACE_DIR"))
    return _enabled


def active() -> bool:
    """Would ``span()`` record now: the flag, or a profiler session."""
    return tracing_enabled() or profiling()


def wall_time(t_ns: int) -> float:
    """Wall-clock seconds of a ``time.monotonic_ns()`` stamp."""
    return (t_ns + _ANCHOR_NS) / 1e9


def enable_tracing(trace_dir: Optional[str] = None) -> None:
    global _enabled
    flush_spans()   # leftover pending spans belong to the PREVIOUS dir
    _enabled = True
    os.environ["RAY_TPU_TRACING"] = "1"
    if trace_dir:
        # the drain notices the dir change and re-points the cached file
        os.environ["RAY_TPU_TRACE_DIR"] = trace_dir


def disable_tracing() -> None:
    global _enabled, _file, _file_dir
    flush_spans()
    _enabled = False
    os.environ.pop("RAY_TPU_TRACING", None)
    os.environ.pop("RAY_TPU_TRACE_DIR", None)
    with _io_lock:
        if _file is not None:
            _file.close()
            _file = None
            _file_dir = None


class _NoopSpan:
    """What a span site gets while tracing is off: one shared, falsy,
    inert object."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __bool__(self):
        return False

    def set(self, **attributes) -> None:
        pass


NOOP = _NoopSpan()


class Span:
    """One recording span; a context manager.  ``set()`` adds
    attributes while it is open."""
    __slots__ = ("name", "kind", "attributes", "trace_id", "span_id",
                 "parent_id", "t0_ns", "t1_ns", "status", "_token", "_ann")

    def __init__(self, name: str, kind: str, parent: Optional[dict],
                 attributes: dict, annotate: bool = False,
                 step_num: Optional[int] = None):
        if parent is None:
            parent = _current.get()
        self.name, self.kind, self.attributes = name, kind, attributes
        if step_num is not None:
            attributes["step"] = step_num
        n = next(_counter)
        if parent:
            self.trace_id = parent["trace_id"]
            self.parent_id = parent.get("span_id")
        else:
            self.trace_id = f"{_trace_prefix}{n & 0xffffffff:08x}"
            self.parent_id = None
        self.span_id = f"{_span_prefix}{n & 0xffffffff:08x}"
        self.status = "ok"
        self.t0_ns = self.t1_ns = 0
        self._token = None
        self._ann = None
        if annotate:
            self._ann = (_annotation(name, **attributes)
                         if step_num is None else
                         _step_annotation(name, step_num=step_num,
                                          **attributes))

    def context(self) -> dict:
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    def set(self, **attributes) -> None:
        self.attributes.update(attributes)
        if self._ann is not None:
            self._ann.set_metadata(**attributes)

    def __bool__(self):
        return True

    def __enter__(self):
        return self.open(time.monotonic_ns())

    def __exit__(self, exc_type, exc, tb):
        return self.close(time.monotonic_ns(), exc_type, exc, tb)

    def open(self, t0_ns: int) -> "Span":
        """Enter with a stamp the caller took (``Account.phase``)."""
        self._token = _current.set(self.context())
        self.t0_ns = t0_ns
        if self._ann is not None:
            self._ann.__enter__()
            # the clock map: this stamp beside the event's ``start_ns``
            self._ann.set_metadata(t0_ns=t0_ns)
        return self

    def close(self, t1_ns: int, exc_type=None, exc=None, tb=None) -> bool:
        self.t1_ns = t1_ns
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        _current.reset(self._token)
        if exc_type is not None:
            self.status = f"error: {exc_type.__name__}"
        _emit(self)
        return False

    def export(self) -> dict:
        return {"name": self.name, "kind": self.kind,
                "trace_id": self.trace_id, "span_id": self.span_id,
                "parent_id": self.parent_id,
                "start": wall_time(self.t0_ns),
                "end": wall_time(self.t1_ns),
                "t0_ns": self.t0_ns, "t1_ns": self.t1_ns,
                "pid": os.getpid(), "attributes": dict(self.attributes),
                "status": self.status}


def span(name: str, *, kind: str = "internal",
         parent: Optional[dict] = None, always: bool = False,
         step_num: Optional[int] = None, **attributes: Any):
    """Open a span: ``with span("engine.decode", rows=3) as sp``.

    Parent = ``parent`` (a context from ``inject_context()``, carried
    across a thread or a process) or the current in-process span.
    Returns ``NOOP`` unless tracing is on (see the module docstring);
    ``always=True`` records regardless.  ``step_num=`` marks a
    training step (attribute ``step``; a ``StepTraceAnnotation`` in a
    profiler session)."""
    if not (always or active()):
        return NOOP
    return Span(name, kind, parent, attributes,
                annotate=not always and profiling(), step_num=step_num)


def record_span(name: str, t0_ns: int, t1_ns: int, *,
                parent: Optional[dict] = None, kind: str = "internal",
                **attributes: Any) -> Span:
    """A finished span from two ``time.monotonic_ns()`` stamps taken
    earlier; recorded whatever the flag says."""
    s = Span(name, kind, parent, attributes)
    s.t0_ns, s.t1_ns = int(t0_ns), int(t1_ns)
    _emit(s)
    return s


def record_account(name: str, t0_ns: int, t1_ns: int, touched: bool,
                   **attributes: Any) -> int:
    """One more link of a chain of cumulative always-on spans: ``name``
    from ``t0_ns``, where the last link ended, to ``t1_ns``, with the
    writer's counters (they only grow: a reader differences two links)
    and ``profiling``: did a ``jax.profiler`` session touch the interval
    (``touched``, or one is active now).  The writer asks for a link
    once it has spent its interval since the last.  -> ``t1_ns``, where
    the chain ends now."""
    record_span(name, t0_ns, t1_ns, profiling=touched or profiling(),
                ring_dropped=ring_dropped(), **attributes)
    return t1_ns


def _edges() -> tuple:
    """0.1 ms, then each edge at most a tenth above the last, to 10 s."""
    edges = [100_000]
    while edges[-1] < 10_000_000_000:
        edges.append(min(edges[-1] * 11 // 10, 10_000_000_000))
    return tuple(edges)


_EDGES = _edges()


class Histogram:
    """Durations (ns) counted into geometric buckets.

    Bucket 0 holds what is under 0.1 ms, bucket ``N - 1`` what is 10 s
    or over; between them bucket ``i`` starts at ``edge_ns(i)`` and ends
    at ``edge_ns(i + 1)``, never more than 10 % above it, so a quantile
    read by linear interpolation inside a bucket is off by less than a
    tenth of itself.  The edges are a function of the index alone:
    a reader takes them from ``edge_ns`` and keeps no copy of the
    layout.  ``add`` finds the bucket by comparing integers (a bisection
    of the edges: no logarithm, no float) and adds ``weight`` to a plain
    int.  ONE thread writes; others read ``snapshot()`` as they read an
    account's counters.  The counts are cumulative.

    The bound: ``N`` = 123 buckets, so a snapshot with every bucket
    counted is a dict of 123 small entries, under 12 KB (a serving
    cell's gaps fill 20-40: 3-5 KB); an ``engine.account`` span that
    carries one, eight kinds' rows and the engine's 46 counters is
    13-18 KB as Python objects.  The ring takes one a second of a busy
    loop beside every request's four spans, so they are a few percent
    of it (a few MB) wherever requests finish; a ring that held nothing
    else (8,192 seconds of one stream that never ends) would hold
    ~150 MB of them."""

    __slots__ = ("counts",)
    N = len(_EDGES) + 1

    def __init__(self):
        self.counts = [0] * self.N

    def add(self, ns: int, weight: int = 1) -> None:
        self.counts[bisect.bisect_right(_EDGES, ns)] += weight

    def snapshot(self) -> dict:
        """{bucket index: count} of the buckets that hold any."""
        return {i: c for i, c in enumerate(self.counts) if c}

    @staticmethod
    def edge_ns(i: int) -> int:
        """Where bucket ``i`` starts (``0 <= i < N``); it ends where the
        next one starts, the last one nowhere."""
        return _EDGES[i - 1] if i else 0


UNACCOUNTED = "unaccounted"
_NS = operator.attrgetter("ns")


class Account:
    """One thread's wall time by phase, counted at its span sites.

    ``phases`` maps a phase to the span its site yields while tracing
    is on (None: the site has no span of its own).  ``with
    account.phase(name) as sp`` enters one; a phase entered inside
    another suspends the outer one, so a phase's ``ns`` is its SELF
    time, and what no phase covers is ``unaccounted``'s: over all of
    them ``ns`` adds up to ``t_ns - t_made_ns`` exactly, ``t_ns`` being
    the newest stamp taken.

    Starved time: ``in_flight`` counts the programs the thread has
    launched and not yet seen the end of: one more where a phase named
    in ``launch`` ends (``launched`` counts them all); where one named
    in ``land`` ends, those launched after the newest one the thread
    said that wait saw the end of (``landed(upto)``, inside the phase,
    ``upto`` being ``launched`` as it stood behind that program's
    launch: a thread that launches ahead of what it reads still has the
    newer programs queued), and none where it said nothing (a wait that
    everything launched ended before).  Time that passes while it is 0
    is ALSO added to the phase's ``starved_ns``: the device had nothing
    queued then.

    ``profiled`` is set where a phase starts inside a ``jax.profiler``
    session (``interval_profiled`` reads and resets it): such time is
    the program's under an instrument, not its own.

    By kind of unit: the thread calls ``pass_done(kind, ..)`` where a
    unit of its time ends: a pass of its work, or a stretch in which it
    had none.  A unit runs from where the last one ended to the newest
    stamp, so no time falls between two units: the thread's own
    turn-around is part of what a token waits.  The time goes to the
    kind's row of ``by_kind``: ``count``, ``ns``, which is ``wait_ns``
    (the ``waits`` phases: the thread itself does nothing) + ``host_ns``
    (every other phase, ``unaccounted`` too) exactly, and whatever the
    caller counted in the unit (``**counted``).  The identity, exact as
    of the last unit's end: the kinds' ``ns`` add up to ``t_ns -
    t_made_ns``.

    ``gaps`` is ONE histogram of the units' times, each weighted by the
    tokens it handed to streams that already had one (``weight``): the
    thread's own view of the gap between a stream's tokens.  A unit that
    hands a stream several tokens (a speculative pass) counts each at
    the unit's whole time: an approximation, the tokens arrive
    together."""

    __slots__ = ("phases", "phase", "unaccounted", "in_flight", "profiled",
                 "t_made_ns", "t_ns", "_open", "by_kind", "gaps", "_waits",
                 "_unit", "launched", "_landed")

    def __init__(self, phases: dict, launch=(), land=(), waits=()):
        # one reusable entry a phase: it owns the phase's counters, and
        # what an entry in progress holds is on the stack
        self.phases = {
            name: _Phase(self, name, span_name,
                         (name in launch) - (name in land))
            for name, span_name in phases.items()}
        self.phase = self.phases.__getitem__
        self.unaccounted = _Phase(self, UNACCOUNTED, None, 0)
        self.in_flight = self.launched = 0
        # the newest launch the ``land`` phase in progress saw the end
        # of, as ``launched`` counted it (None: of everything)
        self._landed = None
        self.profiled = False
        # the open phases, innermost last, each followed by the span it
        # yielded
        self._open = [self.unaccounted, NOOP]
        self.t_made_ns = self.t_ns = time.monotonic_ns()
        self.by_kind = {}
        self.gaps = Histogram()
        self._waits = [self.phases[name] for name in waits]
        # where the unit in progress starts, and the ``waits`` phases'
        # time so far, there
        self._unit = (self.t_made_ns, 0)

    def landed(self, upto: int) -> None:
        """Inside a ``land`` phase: the wait saw the end of the first
        ``upto`` programs launched (the newest such claim of the phase
        counts); what was launched after them stays in flight."""
        self._landed = max(self._landed or 0, upto)

    def pass_done(self, kind: str, weight: int = 0, **counted: int) -> None:
        """A unit of ``kind`` ends at the newest stamp (no clock is
        read).  ``weight``: the tokens it handed to streams that already
        had one (a first token is no gap); ``counted``: what else the
        caller counts a kind, the same keys every time."""
        t0_ns, wait0 = self._unit
        t1_ns, wait1 = self.t_ns, sum(map(_NS, self._waits))
        self._unit = (t1_ns, wait1)
        ns, wait_ns = t1_ns - t0_ns, wait1 - wait0
        row = self.by_kind.get(kind)
        if row is None:
            # (whole from the start: another thread may copy it now)
            row = self.by_kind[kind] = {
                "count": 0, "ns": 0, "host_ns": 0, "wait_ns": 0,
                **dict.fromkeys(counted, 0)}
        row["count"] += 1
        row["ns"] += ns
        row["host_ns"] += ns - wait_ns
        row["wait_ns"] += wait_ns
        for key, n in counted.items():
            row[key] += n
        if weight:
            self.gaps.add(ns, weight)

    def interval_profiled(self) -> bool:
        """Did a phase start inside a profiler session since the last
        call, or is one active now; starts the next interval."""
        now = profiling()
        was, self.profiled = self.profiled or now, now
        return was

    def snapshot(self) -> dict:
        """The counters, copied (from another thread: see the module
        docstring); they cover ``t_made_ns`` to ``t_ns``, the rows by
        kind and ``gaps`` to the last unit's end, ``unit_t_ns``."""
        rest = self.unaccounted
        return {"ns": {n: p.ns for n, p in self.phases.items()},
                "starved_ns": {n: p.starved_ns
                               for n, p in self.phases.items()},
                "count": {n: p.count for n, p in self.phases.items()},
                "unaccounted_ns": rest.ns,
                "unaccounted_starved_ns": rest.starved_ns,
                "by_kind": {kind: dict(row)
                            for kind, row in list(self.by_kind.items())},
                "unit_t_ns": self._unit[0],
                "gaps": self.gaps.snapshot()}


class _Phase:
    """``Account.phase(name)``: entering it stamps the clock, charges
    the time since the newest stamp to the phase that was innermost,
    and yields the site's span (``NOOP`` while tracing is off) built
    from that stamp; leaving it does the same with the phase itself."""
    __slots__ = ("account", "name", "span_name", "launches", "ns",
                 "starved_ns", "count")

    def __init__(self, account: Account, name: str,
                 span_name: Optional[str], launches: int):
        self.account, self.name = account, name
        self.span_name, self.launches = span_name, launches
        self.ns = self.starved_ns = self.count = 0

    def __enter__(self):
        acct = self.account
        now = time.monotonic_ns()
        stack = acct._open
        outer = stack[-2]
        dt = now - acct.t_ns
        acct.t_ns = now
        outer.ns += dt
        if not acct.in_flight:
            outer.starved_ns += dt
        stack.append(self)
        self.count += 1
        sp = NOOP
        in_session = profiling()
        if in_session:
            acct.profiled = True
        if self.span_name is not None and (in_session or tracing_enabled()):
            sp = Span(self.span_name, "internal", None, {},
                      annotate=in_session).open(now)
        stack.append(sp)
        return sp

    def __exit__(self, exc_type, exc, tb):
        acct = self.account
        now = time.monotonic_ns()
        stack = acct._open
        sp = stack.pop()
        stack.pop()
        dt = now - acct.t_ns
        acct.t_ns = now
        self.ns += dt
        if not acct.in_flight:
            self.starved_ns += dt
        if self.launches > 0:
            acct.in_flight += 1
            acct.launched += 1
        elif self.launches:
            upto, acct._landed = acct._landed, None
            acct.in_flight = (0 if upto is None else min(
                acct.in_flight, max(acct.launched - upto, 0)))
        if sp:
            sp.close(now, exc_type, exc, tb)
        return False


def inject_context() -> Optional[dict]:
    """The current span's context, for carrying across a thread or in a
    task spec (reference: tracing_helper.py
    _inject_tracing_into_function); None outside any span.  The serve
    front's span is always on, so a caller that ships the context
    elsewhere only while tracing is on tests ``active()`` itself."""
    return _current.get()


def call_in_context(ctx: Optional[dict], fn, *args, **kwargs):
    """Run ``fn`` with ``ctx`` as the current span context: the
    receiving end of a thread hop (an executor does not carry
    contextvars)."""
    if ctx is None:
        return fn(*args, **kwargs)
    token = _current.set(ctx)
    try:
        return fn(*args, **kwargs)
    finally:
        _current.reset(token)


def _emit(span_: Span) -> None:
    global _ring_dropped
    to_file = bool(os.environ.get("RAY_TPU_TRACE_DIR"))
    with _lock:
        if len(_ring) == RING_SIZE:
            _ring_dropped += 1
        _ring.append(span_)
        if to_file:
            _pending.append(span_)
    if not to_file:
        return
    # opportunistic drain: whoever gets the I/O lock writes the whole
    # batch; a contended emitter's span is picked up by a retry here —
    # the in-flight writer popped its batch BEFORE this append landed,
    # so someone must come back for it or it sits undurable
    while True:
        if not _io_lock.acquire(blocking=False):
            return   # the current writer re-checks after its drain
        try:
            _drain_locked()
        finally:
            _io_lock.release()
        with _lock:
            if not _pending:
                return


def flush_spans() -> None:
    """Force-drain pending spans to the trace file (blocking)."""
    with _io_lock:
        _drain_locked()


atexit.register(flush_spans)


def _drain_locked() -> None:
    """Write+flush everything pending.  Caller holds _io_lock."""
    global _file, _file_dir
    with _lock:
        if not _pending:
            return
        batch, _pending[:] = list(_pending), []
    d = os.environ.get("RAY_TPU_TRACE_DIR")
    if not d:
        return
    if _file is None or _file_dir != d:
        # dir changed between disable/enable cycles: re-point the file
        if _file is not None:
            try:
                _file.close()
            except OSError:
                pass
        os.makedirs(d, exist_ok=True)
        _file = open(os.path.join(d, f"spans-{os.getpid()}.jsonl"), "a")
        _file_dir = d
    _file.write("".join(json.dumps(s.export(), default=str) + "\n"
                        for s in batch))
    _file.flush()


def get_finished_spans(name: Optional[str] = None) -> List[dict]:
    """The ring's spans, oldest first, exported as dicts."""
    return [s.export() for s in _ring.copy()
            if name is None or s.name == name]


def ring_dropped() -> int:
    """Spans the full ring has evicted since the process started: a
    reader that differences it over its window knows whether the ring
    still holds all of it."""
    return _ring_dropped


def clear() -> None:
    with _lock:
        _ring.clear()
        _pending.clear()


def collect_spans(trace_dir: Optional[str] = None) -> List[dict]:
    """Merge every process's span file (worker spans included).  A
    truncated trailing line (a writer crashed or was killed mid-write)
    is skipped instead of poisoning the whole collection."""
    flush_spans()   # this process's pending spans must be readable too
    d = trace_dir or os.environ.get("RAY_TPU_TRACE_DIR")
    if not d:
        return get_finished_spans()
    out = []
    for p in sorted(glob.glob(os.path.join(d, "spans-*.jsonl"))):
        with open(p) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    out.append(json.loads(line))
                except ValueError:
                    continue   # truncated/garbled line: skip, keep rest
    return out
