"""The p95 gap between a row's tokens as the ENGINE emits them (ms),
untraced: the 95th percentile of the account's ``gaps`` histogram over
the window (``chipbench/pass_ledger.py``): every pass's time, weighted
by the tokens it emitted to rows that already had one (a first token is
no gap), interpolated inside a bucket no wider than a tenth of its
value.  ``front_gap_p95_ms.serve`` reads the same gaps where the front
has written them, ``itl_p95_ms`` where the client has read them."""

from chipbench import pass_ledger


def read(obs):
    led = pass_ledger.engine(obs)
    return led and pass_ledger.quantile_ms(led["gaps"], 95)
