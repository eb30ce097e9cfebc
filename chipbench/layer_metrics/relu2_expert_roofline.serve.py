"""Share of the HBM roofline the expert ops reach in a decode pass (%):
the least time the chip could take to read the weights the pass needs —
the experts its tokens really touched (the engine's
``expert_touched_held_decode`` counter over the window's decode passes),
the shared expert and the router (``nemotron_bytes.relu2_expert_bytes_
per_decode`` over ``peaks.json``'s bandwidth) — over the traced time of
``relu2_expert_ms_per_decode.serve``.  Memory bound: at <= 64 tokens a
pass the grouped matmuls do ~0.1 of the arithmetic the reads take."""

from chipbench import nemotron_bytes, nemotron_trace


def read(obs):
    ms = nemotron_trace.ms_per_run(obs, "jit_step", nemotron_trace.EXPERTS)
    touched = nemotron_bytes.touched_per_decode(obs)
    if ms is None or touched is None or not obs.get("peaks"):
        return None
    least_s = nemotron_bytes.relu2_expert_bytes_per_decode(
        obs["published"], obs["expert_layers"], touched) \
        / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
