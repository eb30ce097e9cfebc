"""Share of prompt tokens served from the radix prefix cache in the
window (%): hit tokens over lookup tokens, both as window deltas."""


def read(obs):
    c = obs.get("counters")
    if not c or not c.get("prefix_lookup_tokens"):
        return None
    return 100.0 * c["prefix_hit_tokens"] / c["prefix_lookup_tokens"]
