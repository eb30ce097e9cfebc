"""Share of the HBM roofline the grouped Mamba-2 state update reaches in
a decode pass (%): the least time to read and write the SSM and
convolution state of the rows that ADVANCE (``nemotron_bytes.grouped_ssm_
state_bytes_per_decode`` at the window's mean active rows) over the
traced time of ``grouped_ssm_ms_per_decode.serve``.  What the program
moves beyond that (every row's convolution state is rewritten a pass)
lowers the share; it is not credited."""

from chipbench import nemotron_bytes, nemotron_trace


def read(obs):
    ms = nemotron_trace.ms_per_run(obs, "jit_step",
                                   nemotron_trace.GROUPED_SSM)
    rows = nemotron_bytes.mean_active_rows(obs)
    if ms is None or rows is None or not obs.get("peaks"):
        return None
    least_s = nemotron_bytes.grouped_ssm_state_bytes_per_decode(
        obs["published"], rows) / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
