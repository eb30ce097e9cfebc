"""Share of the held experts a decode pass touches (%): held experts with
at least one real assignment, per expert layer and decode pass (the
engine's ``expert_touched_held_decode`` over ``decode_iterations`` and
the expert layers), over the experts held.  What the grouped matmuls
must read of the expert weights; it grows with the rows that decode
together and with how evenly the router spreads them."""

from chipbench import nemotron_bytes


def read(obs):
    touched = nemotron_bytes.touched_per_decode(obs)
    if touched is None or not obs.get("expert_layers") or "held" not in obs:
        return None
    n_held = obs["held"][1] - obs["held"][0]
    return 100.0 * touched / (obs["expert_layers"] * n_held)
