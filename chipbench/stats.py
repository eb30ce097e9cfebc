"""Percentile and gap arithmetic of the yardstick (no JAX, no numpy).

``percentile`` is the nearest-rank rule of ``benchmarks/serve_bench.py``
(``_pct``), copied so that a later PR cannot change it.
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
