"""Share of the window's prompt tokens served from blocks adopted from
the radix index (%): the engine's ``prefix_hit_tokens`` over those and
the tokens its prefill programs ran (``prefill_tokens``), both as window
deltas.  Beside ``prefix_hit_rate.serve`` (hits over tokens LOOKED UP at
admission) this counts a prompt by what ran: a prompt re-matched while
it prefills, or prefilled again after a preemption, moves it."""


def read(obs):
    c = obs.get("counters") or {}
    if "prefill_tokens" not in c or "prefix_hit_tokens" not in c:
        return None
    total = c["prefix_hit_tokens"] + c["prefill_tokens"]
    return 100.0 * c["prefix_hit_tokens"] / total if total else None
