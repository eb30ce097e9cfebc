"""Share of the prefill chunks that rode a decode step in a layout with
linear-attention sublayers (%): ``chunk_in_step_share.serve``'s reading
(``chunks_in_step`` over ``chunk_passes``, the loop's own counts
differenced over the window's ``engine.account`` spans) for the cell
that metric does not list.  0 at a parent commit, whose engine counts
the chunks and lets none ride."""

from chipbench.readers import load_reader


def read(obs):
    return load_reader("chunk_in_step_share.serve").read(obs)
