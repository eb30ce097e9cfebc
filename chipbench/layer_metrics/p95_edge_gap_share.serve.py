"""Share of the window's gaps that lie behind the kinds of pass slower
than the boundary between two kinds which lies NEAREST the judged p95's
rank (%), untraced: the account's kinds sorted by their mean pass, the
slowest first, and of the cumulative shares of the gaps they emitted the
one nearest 5 % (``edge.nearest_boundary``; ``chipbench/pass_ledger.py``).
``chunk_pass_gap_share.serve`` is one such boundary, between the passes
with prompt work and those without; this reads them all, so it also sees
a rank that sits between a step that carries ONE chunk and a pass that
ran two (the granite cell at 11.2 req/s, PR 59: 8 % spread of
``itl_p95_ms`` with 46 % of the gaps behind a chunk-carrying pass).
Between 2.5 and 8 % the judged rank lies on an edge (``PERF.md`` section
2's rule).  A kind's passes differ in length with their rows, so a
boundary between two kinds of nearly equal mean is no edge in the gaps:
read it beside the kinds' own times.  None without the account by kind
or with one kind alone."""

from chipbench import edge, pass_ledger

UNTRACED = True     # also read into an untraced run's ``notes`` (run.py)


def read(obs):
    led = pass_ledger.engine(obs)
    return led and edge.nearest_boundary(led["by_kind"], led["gaps"])
