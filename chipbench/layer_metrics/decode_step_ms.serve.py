"""Wall time per decode iteration in the window (ms): window seconds
over the engine's ``decode_iterations`` counted in it.  Chunk-prefill
passes interleaved between iterations are inside this time."""


def read(obs):
    c = obs.get("counters")
    if not c or not c.get("decode_iterations"):
        return None
    return 1e3 * obs["window_s"] / c["decode_iterations"]
