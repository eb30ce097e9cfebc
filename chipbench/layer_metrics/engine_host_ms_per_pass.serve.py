"""The host's own work in a scheduler pass (ms): mean over the traced
passes of ``engine.pass`` minus its ``engine.fetch`` descendants (the
waits for the device).  The loop is synchronous, so this is time in
which it has given the device no new program."""

from chipbench import spans


def read(obs):
    passes = spans.whole_passes(obs)
    if not passes:
        return None
    host = [spans.ms(p) - sum(spans.ms(s) for s in inside
                              if s["name"] == "engine.fetch")
            for p, inside in passes]
    return sum(host) / len(host)
