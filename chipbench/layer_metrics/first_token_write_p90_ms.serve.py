"""The first token's second hop (ms): from the request's ``stream()``
awake with it (``request.decode``'s ``first_yield_ns``) to the first
chunk written and drained (``front.request``'s ``first_chunk_ns``): the
replica's generator, the executor's round trip to the proxy's loop, the
write.  p90 over the same requests as
``first_token_wake_p90_ms.serve`` (``chipbench/pass_ledger.py``
``first_tokens``)."""

from chipbench import pass_ledger, spans


def read(obs):
    return spans.p90_ms([r["write"] for r in pass_ledger.first_tokens(obs)])
