"""Time from a request's admission to its first token (ms): nearest-rank
p90 of the ``request.prefill`` span over the requests submitted in the
window: the chunk passes, and the decode iterations between them."""

from chipbench import spans


def read(obs):
    return spans.p90_ms([spans.ms(r["request.prefill"])
                         for r in spans.window_requests(obs)
                         if "request.prefill" in r])
