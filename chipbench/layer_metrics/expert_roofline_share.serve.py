"""Share of the HBM roofline the expert ops reach in a decode pass (%):
the least time the chip could take to read the weights the pass needs
(``hybrid_bytes.expert_bytes_per_decode`` at the window's mean active
rows, over ``peaks.json``'s bandwidth) over the traced time of
``expert_ms_per_decode.serve``.  Memory bound: at <= 64 tokens a pass
the grouped matmuls do ~0.1 of the arithmetic the reads take."""

from chipbench import hybrid_bytes, scoped_trace

LABELS = ("routed_experts", "shared_expert")


def read(obs):
    ms = scoped_trace.ms_per_run(obs, "jit_step", LABELS)
    rows = hybrid_bytes.mean_active_rows(obs)
    if ms is None or rows is None or not obs.get("peaks"):
        return None
    least_s = hybrid_bytes.expert_bytes_per_decode(
        obs["published"], obs["held"], rows) / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
