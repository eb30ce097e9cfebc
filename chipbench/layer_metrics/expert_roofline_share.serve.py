"""Share of the HBM roofline the expert ops reach in a decode pass (%):
the least time the chip could take to read the weights the pass needs —
the experts its tokens really touched (the engine's
``expert_touched_held_decode`` counter over the decode passes of the
traced seconds themselves),
the shared expert and the router (``hybrid_bytes.expert_bytes_per_decode``
over ``peaks.json``'s bandwidth) — over the traced time of
``expert_ms_per_decode.serve``.  Memory bound: at <= 64 tokens a pass
the grouped matmuls do ~0.1 of the arithmetic the reads take."""

from chipbench import hybrid_bytes, scoped_trace

LABELS = ("routed_experts", "shared_expert")


def read(obs):
    ms = scoped_trace.ms_per_run(obs, "jit_step", LABELS)
    touched = hybrid_bytes.touched_per_decode(hybrid_bytes.traced(obs))
    if ms is None or touched is None or not obs.get("peaks"):
        return None
    least_s = hybrid_bytes.expert_bytes_per_decode(
        obs["published"], touched) / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
