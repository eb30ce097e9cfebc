"""Share of the window's gaps that lie behind a pass which held prompt
work (%), untraced: of the tokens the engine emitted to rows that
already had one (the weights of the account's ``gaps`` histogram), those
emitted by every KIND of pass that ran a chunk or a prefill, alone or in
or beside the decode step (``chipbench/pass_ledger.py``).  A gap behind
such a pass is a level of its own (the step's time and the chunk's), so
this is where a judged percentile's rank lies: ``itl_p95_ms`` reads the
longer level's spread while the share is well past 5 %, the step pass
while it is well under, and at 5 % the EDGE between the two, where
under 1 % of host time moves the metric by the levels' distance
(``edge.on_an_edge``: 2.5-8 %; ``PERF.md`` section 2's rule).

A row of ``by_kind`` counts ``tokens``, first tokens among them, and a
first token is emitted only by the pass whose chunk ended its prompt:
so the kinds WITHOUT prompt work emitted gaps alone, and the share is
what is left of the histogram's weight.  The kinds are the account's
own names (``ray_tpu/inference/engine.py`` ``_kind_of``), read by what
they say: a name that holds ``chunk`` or ``prefill`` held prompt work.
None without the account by kind (a parent commit) or with no gap."""

from chipbench import edge, pass_ledger

UNTRACED = True     # also read into an untraced run's ``notes`` (run.py)


def read(obs):
    led = pass_ledger.engine(obs)
    return led and edge.prompt_gap_share(led["by_kind"], led["gaps"])
