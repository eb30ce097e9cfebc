"""Share of the prefill chunks that rode a decode step (%): of the
chunks the engine ran (``chunk_passes``), those that ONE program ran
together with the pass's decode rows (``chunks_in_step``: the weights
stream once for both), both counted by the loop itself and differenced
over the ``engine.account`` spans of the window
(``chipbench/loop_account.py`` ``intervals``: every chained pair, a
profiler session or not, since a count is not stretched by one).  The
rest ran as the chunk program alone: no row was decoding, or the chunk
was not its pass's last.  A program that counts no such chunks (a
parent commit, a family without the fused program's counter) gives
None, as does a window without a chunk."""

from chipbench import loop_account


def read(obs):
    chunks = in_step = 0
    for a, b in loop_account.intervals(obs):
        a, b = a["attributes"], b["attributes"]
        if "chunks_in_step" not in a or "chunks_in_step" not in b:
            return None
        chunks += b["chunk_passes"] - a["chunk_passes"]
        in_step += b["chunks_in_step"] - a["chunks_in_step"]
    if not chunks:
        return None
    return 100.0 * in_step / chunks
