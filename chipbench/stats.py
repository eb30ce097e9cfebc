"""Percentile, gap and spread arithmetic of the yardstick (no JAX, no numpy).

``percentile`` is the nearest-rank rule the repo's first serving bench
used (copied at PR 25; that file is gone since PR 31), kept here so that
a later PR cannot change it.  ``spread`` is the run-to-run spread the
bounds of ``BENCHMARK.json`` are set from.
"""

from __future__ import annotations


def percentile(values, p: float) -> float:
    """Nearest-rank percentile of ``values`` (p in 0..100)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    i = min(len(xs) - 1, max(0, int(round(p / 100 * (len(xs) - 1)))))
    return xs[i]


def gaps(token_times) -> list:
    """Inter-token gaps of one request: differences of consecutive
    arrival stamps (seconds)."""
    return [b - a for a, b in zip(token_times, token_times[1:])]


class Stamps:
    """Seconds between marks, from the process's start: what ``setup_s``
    is made of, and the compile cache's counts at named moments
    (``notes.setup_stamps``)."""

    def __init__(self, t_start: float):
        self.last, self.notes = t_start, {}

    def mark(self, name: str, at: float = None) -> None:
        import time
        at = time.monotonic() if at is None else at
        self.notes[name] = at - self.last
        self.last = at

    def cache(self, moment: str, counts: dict) -> None:
        """``counts``: the program's ``compile_cache_stats()``."""
        self.notes["cache_hits_" + moment] = counts["hits"]
        self.notes["cache_misses_" + moment] = counts["misses"]


def spread(values, drop_farthest: bool = False) -> float:
    """The distance between the first and the third quartile
    (``statistics.quantiles(values, n=4)``) as a share of the median.
    ``drop_farthest`` leaves out the one run farthest from the median
    first, as the driver does when it asks whether a bound is too tight."""
    import statistics
    xs = list(values)
    if drop_farthest and len(xs) > 2:
        med = statistics.median(xs)
        xs.remove(max(xs, key=lambda x: abs(x - med)))
    q1, _, q3 = statistics.quantiles(xs, n=4)
    return (q3 - q1) / statistics.median(xs)
