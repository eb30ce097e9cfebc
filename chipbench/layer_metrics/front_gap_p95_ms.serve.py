"""The p95 gap between a streamed response's chunks as the serve FRONT
writes them (ms), untraced: the 95th percentile of the proxy's
``write_gaps`` histogram over the window's ``front.account`` spans
(``chipbench/pass_ledger.py``): the time from one chunk written and
drained to the response's next.  Between ``engine_gap_p95_ms.serve``
(the engine's emits) and ``itl_p95_ms`` (the client's reads): where the
three part is the stream's hand-out or the socket."""

from chipbench import pass_ledger


def read(obs):
    gaps = pass_ledger.front_gaps(obs)
    return gaps and pass_ledger.quantile_ms(gaps, 95)
