"""Device time of one chunk-prefill program (ms): the summed duration
of ``jit_chunk_fn`` on the trace's ``XLA Modules`` line over its count.
The device side of ``prefill_pass_share.serve``, whose host spans hold
only a chunk's dispatch: the program's own time passes in the
``engine.fetch`` of the decode iteration that follows.  A program that
the trace's start or end cut counts as a whole run, so with n runs
traced this reads up to 1/n low."""

PROGRAM = "jit_chunk_fn"    # inference/decode.py: one prefill_chunk window


def read(obs):
    t = obs.get("trace") or {}
    n = (t.get("module_counts") or {}).get(PROGRAM)
    return 1e3 * t["module_seconds"][PROGRAM] / n if n else None
