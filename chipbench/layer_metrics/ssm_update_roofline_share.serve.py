"""Share of the HBM roofline the Mamba-2 state update reaches in a decode
pass (%): the least time to read and write the state of the rows that
advance (``hybrid_bytes.ssm_state_bytes_per_decode`` at the mean active
rows of the traced seconds' decode passes) over the traced time of
``ssm_ms_per_decode.serve``.  The
program rewrites idle rows' state too; that is not credited, so the share
also says how much of the traffic is waste."""

from chipbench import hybrid_bytes, scoped_trace


def read(obs):
    ms = scoped_trace.ms_per_run(obs, "jit_step", ("mixer_ssm",))
    rows = hybrid_bytes.mean_active_rows(hybrid_bytes.traced(obs))
    if ms is None or rows is None or not obs.get("peaks"):
        return None
    least_s = hybrid_bytes.ssm_state_bytes_per_decode(
        obs["published"], rows) / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
