"""ObjectRef — a future-like handle to a value in the object plane.

Capability parity with the reference's ObjectRef surface
(reference: python/ray/_raylet.pyx ObjectRef; python/ray/includes/object_ref.pxi):
await-able, hashable, picklable (travels inside task args), and resolvable
via ``ray_tpu.get``.
"""

from __future__ import annotations

import asyncio
import collections
import concurrent.futures
import threading
from typing import Any, Callable, Optional

from ray_tpu.core.ids import ObjectID


class _RefTracker:
    """Process-local half of distributed refcounting (scoped-down
    reference: core_worker/reference_count.h:61 — local counts here;
    the node releases storage when the OWNER's count drains; borrower
    chains and lineage are out of scope for v1).

    Counts ObjectRef constructions/destructions per object id and, when
    an id's count hits zero, batches a ``release_refs`` notification to
    the node through the sink installed by the runtime."""

    _FLUSH_BATCH = 64
    _FLUSH_DELAY = 0.5

    def __init__(self):
        self._lock = threading.Lock()
        self._counts: dict[bytes, int] = {}
        self._pending: list[bytes] = []
        self._sink: Optional[Callable[[list], None]] = None
        self._timer: Optional[threading.Timer] = None
        # deaths recorded and not yet counted (see ``decref``)
        self._dead: collections.deque[bytes] = collections.deque()

    def set_sink(self, sink: Optional[Callable[[list], None]]) -> None:
        with self._lock:
            self._sink = sink

    def incref(self, ob: bytes) -> None:
        with self._lock:
            self._counts[ob] = self._counts.get(ob, 0) + 1
        self._settle()

    def decref(self, ob: bytes) -> None:
        """A death never WAITS for a lock.  ``ObjectRef.__del__`` calls
        this, and the collector runs a finalizer on whatever thread
        crosses its threshold, after any call, under any lock — this
        tracker's own included: a reference dying while THIS thread
        made the flush timer under the lock waited for itself for good
        (the tier-1 run that never ended, PR 43).  So a death is
        recorded, and counted by whoever has the lock; and a full batch
        is flushed by the timer's thread, not from here, where the
        sink's own locks (the client's send lock) may be this thread's
        too."""
        self._dead.append(ob)
        self._settle()

    def _settle(self) -> None:
        """Count the recorded deaths, unless another frame is at it:
        that frame looks again after it lets the lock go, so a death
        recorded behind its back is not left lying."""
        while self._dead and self._lock.acquire(blocking=False):
            try:
                self._count_deaths()
            finally:
                self._lock.release()

    def _count_deaths(self) -> None:
        """With the lock held."""
        while self._dead:
            ob = self._dead.popleft()
            c = self._counts.get(ob)
            if c is None:
                continue
            if c > 1:
                self._counts[ob] = c - 1
                continue
            del self._counts[ob]
            if self._sink is None:
                continue
            self._pending.append(ob)
            full = len(self._pending) == self._FLUSH_BATCH
            if full or self._timer is None:
                if self._timer is not None:
                    self._timer.cancel()
                self._timer = threading.Timer(
                    0.0 if full else self._FLUSH_DELAY, self.flush)
                self._timer.daemon = True
                self._timer.start()

    def flush(self) -> None:
        with self._lock:
            self._count_deaths()
            if self._timer is not None:
                self._timer.cancel()
                self._timer = None
            batch, self._pending = self._pending, []
            sink = self._sink
        self._settle()
        if sink is not None and batch:
            try:
                sink(batch)
            except Exception:
                pass   # connection racing shutdown: storage dies with it

    def held_count(self, ob: bytes) -> int:
        self._settle()
        with self._lock:
            return self._counts.get(ob, 0)


_tracker = _RefTracker()


def get_tracker() -> _RefTracker:
    return _tracker


class ObjectRef:
    __slots__ = ("_id", "_owner", "__weakref__")

    def __init__(self, object_id: ObjectID, owner: Optional[str] = None):
        self._id = object_id
        self._owner = owner  # worker id string of the owner process
        _tracker.incref(object_id.binary())

    def __del__(self):
        try:
            _tracker.decref(self._id.binary())
        except Exception:
            pass   # interpreter teardown

    @property
    def id(self) -> ObjectID:
        return self._id

    @property
    def owner(self) -> Optional[str]:
        return self._owner

    def binary(self) -> bytes:
        return self._id.binary()

    def hex(self) -> str:
        return self._id.hex()

    def __hash__(self):
        return hash(self._id)

    def __eq__(self, other):
        return isinstance(other, ObjectRef) and other._id == self._id

    def __repr__(self):
        return f"ObjectRef({self.hex()[:16]}…)"

    # Block-on-result convenience (same as calling ray_tpu.get(ref)).
    def get(self, timeout: Optional[float] = None) -> Any:
        from ray_tpu.core.runtime import get_runtime
        return get_runtime().get([self], timeout=timeout)[0]

    def future(self) -> concurrent.futures.Future:
        from ray_tpu.core.runtime import get_runtime
        return get_runtime().as_future(self)

    def __await__(self):
        fut = self.future()
        return asyncio.wrap_future(fut).__await__()

    def __reduce__(self):
        return (ObjectRef, (self._id, self._owner))


class ObjectRefGenerator:
    """Iterator over a dynamic number of task returns
    (reference: num_returns="dynamic" → ObjectRefGenerator, _raylet.pyx:172)."""

    def __init__(self, refs: list[ObjectRef]):
        self._refs = list(refs)

    def __iter__(self):
        return iter(self._refs)

    def __len__(self):
        return len(self._refs)

    def __getitem__(self, i):
        return self._refs[i]
