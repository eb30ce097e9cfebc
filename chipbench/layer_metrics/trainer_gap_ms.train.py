"""Host time a step costs beyond the device's: wall step time (from the
untraced definition: window over steps) minus the step program's device
time (ms).  What the trainer's loop, shard_batch and the loss fetch add."""

from chipbench.readers import load_reader


def read(obs):
    dev = load_reader("step_device_ms.train").read(obs)
    if dev is None or "step_wall_ms" not in obs:
        return None
    return obs["step_wall_ms"] - dev
