"""Device time of one fused step+chunk program of a layout with
linear-attention sublayers (ms): the summed duration of
``jit_step_chunk`` on the trace's ``XLA Modules`` line over its count.
The device side of ``linear_step_chunk_pass_wait_ms.serve``, and what
``chunk_program_ms.serve`` + ``decode_program_ms.serve`` were while the
pass ran two programs.  A program the trace's start or end cut counts as
a whole run (up to 1/n low over n runs).  None where the program does
not exist (a parent commit) or did not run in the traced seconds."""

from chipbench.olmo_hybrid_chunks import FUSED


def read(obs):
    t = obs.get("trace") or {}
    n = (t.get("module_counts") or {}).get(FUSED)
    return 1e3 * t["module_seconds"][FUSED] / n if n else None
