"""What held the host up during a window, if anything did (no JAX).

A serving process that streams ~1,800 tokens a second through one
interpreter loses a run's tail to one pause of a second.  ``HostWatch``
names the pause's kind without slowing anything down:

  * a heartbeat thread that sleeps ``tick_s`` and records every wake-up
    more than ``late_s`` overdue (when, and by how much): a pause of THIS
    process, whatever caused it;
  * the garbage collector's own pauses (``gc.callbacks``), by
    generation: a pause that holds the interpreter lock;
  * ``/proc/stat``'s ``steal`` and ``/proc/pressure/cpu`` over the
    window: the machine took the cores away (a neighbour on the host).

A heartbeat gap with no collection inside and no steal is left: a thread
that kept the lock, or the kernel.  ``report()`` goes into a run's
``notes``; nothing is judged from it.
"""

from __future__ import annotations

import gc
import threading
import time


def _proc_cpu() -> dict:
    out = {}
    try:
        with open("/proc/stat") as f:
            v = [int(x) for x in f.readline().split()[1:]]
        out.update(zip(("user", "nice", "system", "idle", "iowait", "irq",
                        "softirq", "steal"), v))
        with open("/proc/pressure/cpu") as f:
            out["pressure_some_us"] = int(
                f.readline().split("total=")[1])
    except (OSError, IndexError, ValueError):
        pass                    # no such file here: the keys stay out
    return out


class HostWatch:
    def __init__(self, tick_s: float = 0.005, late_s: float = 0.1):
        self.tick_s, self.late_s = tick_s, late_s
        self.gaps, self.collections = [], {0: [], 1: [], 2: []}
        self._stop = threading.Event()
        self._began = None

    def _beat(self) -> None:
        last = time.monotonic()
        while not self._stop.wait(self.tick_s):
            now = time.monotonic()
            if now - last - self.tick_s > self.late_s:
                self.gaps.append((now - self.t0, now - last - self.tick_s))
            last = now

    def _collected(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._began = time.monotonic()
        elif self._began is not None:
            self.collections[info["generation"]].append(
                time.monotonic() - self._began)

    def start(self, t0: float) -> None:
        """``t0``: the window's start on ``time.monotonic()``; gaps are
        stamped from it."""
        self.t0, self.cpu0 = t0, _proc_cpu()
        gc.callbacks.append(self._collected)
        threading.Thread(target=self._beat, daemon=True).start()

    def report(self) -> dict:
        self._stop.set()
        gc.callbacks.remove(self._collected)
        cpu1 = _proc_cpu()
        ticks = {k: cpu1[k] - self.cpu0[k] for k in cpu1 if k in self.cpu0}
        return {
            "heartbeat_gaps_over_100ms": [
                {"at_s": round(at, 3), "late_s": round(late, 3)}
                for at, late in self.gaps[:20]],
            "heartbeat_late_total_s": sum(late for _, late in self.gaps),
            "gc": {f"gen{g}": {"n": len(p), "total_s": sum(p),
                               "longest_s": max(p, default=0.0)}
                   for g, p in self.collections.items()},
            "proc_stat_ticks": ticks,
        }
