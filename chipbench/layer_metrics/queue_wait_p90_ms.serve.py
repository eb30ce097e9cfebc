"""Time a request waits for a row (ms): nearest-rank p90 of the
``request.queue`` span (``submit()`` to the admission that led to the
first token) over the requests submitted in the window."""

from chipbench import spans


def read(obs):
    return spans.p90_ms([spans.ms(r["request.queue"])
                         for r in spans.window_requests(obs)])
