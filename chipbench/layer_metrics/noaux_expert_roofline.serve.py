"""Share of the HBM roofline the expert ops reach in a decode pass (%):
the least time the chip could take to read the weights the pass needs —
the three matrices of each expert its tokens really touched (the
engine's ``expert_touched_held_decode`` over the TRACED seconds' decode
passes, never all 64), the shared expert's and the router
(``xing4_bytes.routed_expert_bytes_per_decode`` over ``peaks.json``'s
bandwidth) — over the traced time of ``noaux_expert_ms_per_decode.
serve``.  Memory bound: at <= 32 tokens a pass the grouped matmuls do a
fraction of the arithmetic the reads take."""

from chipbench import xing4_bytes as b
from chipbench import xing4_trace as t


def read(obs):
    ms = t.ms_per_run(obs, "jit_step", t.EXPERTS)
    touched = b.touched_per_decode(b.traced(obs))
    if ms is None or touched is None or not obs.get("peaks"):
        return None
    least_s = b.routed_expert_bytes_per_decode(obs["published"], touched) \
        / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
