"""Routing imbalance over the window (x): the busiest held expert's
assignments over the mean held expert's, per (pass, layer), from the
engine's counters: ``expert_load_max`` sums the per-layer maximum,
``expert_assignments_held`` / experts held the per-layer mean.  1.0 =
perfectly even; the grouped matmul's longest run is this much longer
than an even split's."""


def read(obs):
    c = obs.get("counters") or {}
    if not c.get("expert_assignments_held") or "held" not in obs:
        return None
    n_held = obs["held"][1] - obs["held"][0]
    return c["expert_load_max"] * n_held / c["expert_assignments_held"]
