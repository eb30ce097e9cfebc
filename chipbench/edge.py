"""Where a judged percentile's rank lies among a cell's gaps (no JAX).

A serving cell's gaps come in levels: the decode step alone, and the
step that carries or follows a prompt's chunk.  ``itl_p95_ms`` is a
nearest-rank percentile over all of them pooled, so while the longer
level holds about 5 % of the gaps the rank sits on the edge between the
two and a run lands on either (``PERF.md`` section 2: a judged
percentile may not lie within ``EDGE`` of a group of gaps).
``layer_metrics/chunk_pass_gap_share.serve.py`` reads the share;
``spreads.py`` and ``sweep.py`` mark a reading inside the band.
"""

from __future__ import annotations

EDGE = (2.5, 8.0)       # %, around the p95's 5: half and 1.6 x
NOTE = "chunk_pass_gap_share.serve"     # its key in a line's notes
NEAREST = "p95_edge_gap_share.serve"    # the same, whichever the kinds
MARK = "the judged rank lies on an edge"


def holds_prompt_work(kind: str) -> bool:
    """By the account's own name for a kind of pass (``engine._kind_of``:
    ``step``, ``step_chunk``, ``chunk+step``, ``chunk``, ``prefill``,
    ``spec``, ``host``, ``idle``, ...)."""
    return "chunk" in kind or "prefill" in kind


def prompt_gap_share(by_kind: dict, gaps: dict):
    """% of the histogram's weight (tokens emitted to rows that already
    had one) that the kinds with prompt work emitted; the other kinds
    emit no first token, so their ``tokens`` are gaps alone.  None for
    an empty histogram."""
    total = sum(gaps.values())
    if total <= 0:
        return None
    plain = sum(row.get("tokens", 0) for kind, row in by_kind.items()
                if not holds_prompt_work(kind))
    return 100.0 * (total - plain) / total


def levels(by_kind: dict, gaps: dict) -> list:
    """The kinds of pass that emitted gaps, slowest mean pass first:
    (kind, mean ms a pass, % of the gaps it emitted, % emitted by it and
    every slower kind).  A kind with prompt work counts its first tokens
    in ``tokens``; they are taken off all such kinds in proportion (the
    account does not say which pass emitted which)."""
    total = sum(gaps.values())
    rows = {k: r for k, r in by_kind.items()
            if r.get("count") and r.get("tokens")}
    if total <= 0 or not rows:
        return []
    prompt = sum(r["tokens"] for k, r in rows.items()
                 if holds_prompt_work(k))
    plain = sum(r["tokens"] for k, r in rows.items()
                if not holds_prompt_work(k))
    scale = max(0.0, total - plain) / prompt if prompt else 0.0
    out, above = [], 0.0
    for kind, r in sorted(rows.items(),
                          key=lambda kr: -kr[1]["ns"] / kr[1]["count"]):
        share = 100.0 * r["tokens"] * (
            scale if holds_prompt_work(kind) else 1.0) / total
        above += share
        out.append((kind, r["ns"] / r["count"] / 1e6, share, above))
    return out


def nearest_boundary(by_kind: dict, gaps: dict, rank: float = 5.0):
    """% of the gaps that lie behind the kinds of pass slower than the
    boundary between two kinds which lies nearest the judged
    percentile's ``rank`` from the top (the p95's 5 %); None with fewer
    than two kinds.  Inside ``EDGE`` the rank sits between two levels of
    gaps, whichever kinds they are."""
    cuts = [above for *_, above in levels(by_kind, gaps)[:-1]]
    return min(cuts, key=lambda c: abs(c - rank)) if cuts else None


def on_an_edge(share) -> bool:
    return share is not None and EDGE[0] < share < EDGE[1]


def mark(share) -> str:
    return MARK if on_an_edge(share) else ""
