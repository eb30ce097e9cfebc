"""Device time of the paged decode program per run (ms): the summed
duration of ``jit_step`` on the trace's ``XLA Modules`` line over its
count.  What ``decode_pass_ms.serve``, a host span, cannot separate:
the decode program alone, without the chunk programs queued before it.
A program that the trace's start or end cut counts as a whole run, so
with n runs traced this reads up to 1/n low."""

PROGRAM = "jit_step"        # inference/decode.py: the paged engine's step


def read(obs):
    t = obs.get("trace") or {}
    n = (t.get("module_counts") or {}).get(PROGRAM)
    return 1e3 * t["module_seconds"][PROGRAM] / n if n else None
