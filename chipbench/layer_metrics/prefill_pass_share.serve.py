"""Share of the engine's pass time that its HOST spends advancing
prefills (%): summed ``engine.prefill_chunk`` over summed
``engine.pass`` of the traced interval.  A chunk span holds the prefix
re-match, the upload and the dispatch of the chunk program, and the
wait only where the chunk ends a prompt and its first token is read;
the program's device time is NOT in it (it passes in the next
``engine.fetch``).  The device side is ``chunk_program_ms.serve``."""

from chipbench import spans


def read(obs):
    passes = spans.whole_passes(obs)
    total = sum(spans.ms(p) for p, _ in passes)
    if not total:
        return None
    chunks = sum(spans.ms(s) for _, inside in passes for s in inside
                 if s["name"] == "engine.prefill_chunk")
    return 100.0 * chunks / total
