"""HOST time of one decode iteration (ms): mean duration of the
``engine.decode`` spans of the traced interval: upload, dispatch, the
wait for the logits and sampling.  The wait (``engine.fetch``) lasts
until every program queued before it has run, so the device time of
the chunk-prefill programs dispatched earlier in the same pass IS in
this number; only their host side (``engine.prefill_chunk``) is not.
The decode program alone is ``decode_program_ms.serve``."""

from chipbench import spans


def read(obs):
    d = spans.named(spans.finished_spans(obs), "engine.decode",
                    spans.window_ns(obs))
    return sum(spans.ms(s) for s in d) / len(d) if d else None
