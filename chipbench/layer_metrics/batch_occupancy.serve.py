"""Mean share of decode rows active per decode iteration in the window
(%), from the engine's ``batch_occupancy`` and ``decode_iterations``."""


def read(obs):
    c = obs.get("counters")
    if not c or not c.get("decode_iterations"):
        return None
    return 100.0 * c["occupancy_sum"] / c["decode_iterations"]
