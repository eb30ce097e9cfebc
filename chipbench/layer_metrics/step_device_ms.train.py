"""Device time of the compiled train step per step (ms): the summed
duration of the step program's ``XLA Modules`` events in the trace over
the steps traced.  The step program is the one that ran once per step
and took the most time."""


def read(obs):
    t = obs.get("trace")
    steps = obs.get("trace_steps")
    if not t or not steps or not t["module_seconds"]:
        return None
    name = max(t["module_seconds"], key=t["module_seconds"].get)
    return 1e3 * t["module_seconds"][name] / t["module_counts"][name]
