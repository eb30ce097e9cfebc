"""The first token's first hop (ms): from the engine's emit
(``request.prefill``'s end) to the request's ``stream()`` awake with it
(``request.decode``'s ``first_yield_ns``): the condition's wake-up and
the interpreter lock, which the loop thread holds on.  p90 over the
window's requests whose first token no profiler session touched
(``chipbench/pass_ledger.py`` ``first_tokens``).  With
``first_token_write_p90_ms.serve`` it splits what
``front_overhead_p90_ms.serve`` reads whole."""

from chipbench import pass_ledger, spans


def read(obs):
    return spans.p90_ms([r["wake"] for r in pass_ledger.first_tokens(obs)])
