"""Model FLOP/s utilization of the whole training step, in % of the
chip's published bf16 peak: the operations forward and backward need
for a step's tokens (``chipbench/flops.py``; recomputed ones do not
count) over the step's wall time (the profiler's own stalls taken out,
as ``trainer_gap_ms.train`` has it) and the peak of ``peaks.json``."""

from chipbench import flops


def read(obs):
    step_ms, peaks = obs.get("step_wall_ms"), obs.get("peaks")
    if not step_ms or not peaks:
        return None
    config, mix = obs["config"], obs["mix"]
    per_token = flops.train_flops_per_token(
        flops.gpt2_param_count(config), config, mix["seq"])
    tokens_per_s = mix["batch"] * mix["seq"] / (step_ms / 1e3)
    return 100.0 * tokens_per_s * per_token / peaks["bf16_flops_per_s"]
