"""Device time of one fused step+chunk program (ms): the summed duration
of ``jit_step_chunk`` on the trace's ``XLA Modules`` line over its
count: the decode rows' step and ONE prefill chunk as one program
(``inference/decode.py`` ``make_paged_step_chunk``, ``recurrent.py``
``make_recurrent_step_chunk``).  The device side of the pass that
``step_chunk_pass_ms.serve`` reads on the loop thread, whose own
``..wait_ms`` has not been a program's duration since the loop runs a
pass ahead; in the cells where nearly every chunk rides it is the
program the p95 gap waits for, and neither ``decode_program_ms.serve``
(decode-only passes) nor ``chunk_program_ms.serve`` (lone chunks) sees
it.  A program that the trace's start or end cut counts as a whole run
(up to 1/n low over n runs).  None where the program does not exist or
did not run in the traced seconds."""

PROGRAM = "jit_step_chunk"


def read(obs):
    t = obs.get("trace") or {}
    n = (t.get("module_counts") or {}).get(PROGRAM)
    return 1e3 * t["module_seconds"][PROGRAM] / n if n else None
