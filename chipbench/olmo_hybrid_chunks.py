"""A chunk of the ``olmo_hybrid`` layout in whichever program holds it.

Since the delta rule has its two-part form (``ray_tpu/models/hybrid.py``
``TWO_PART``) a chunk that meets decoding rows rides their step:
``jit_step_chunk`` runs the window form of the delta rule and the
head-wise window attention over the chunk's lanes beside the rows' one
token, and ``jit_chunk_fn`` is left with the chunks no row waits behind.
A reader that names ``jit_chunk_fn`` alone loses sight of the kernels
then; these sum a label over BOTH programs' runs, by
``olmo_hybrid_trace``'s table as ``obs["scoped"]`` holds it.  A program
without the fused one (a parent commit) is read through ``jit_chunk_fn``
alone.

In the fused program ``mixer_linear_attention`` also holds the decode
rows' convolution and gated norm (their kernel is ``delta_step``, another
label): a few tenths of a ms beside the chunk's 12-13, counted as time
and not as work, so a share of a roofline reads that little LOW.
"""

from __future__ import annotations

# ray_tpu/inference/decode.py, recurrent.py: a prefill window alone, and
# one together with the pass's decode rows
CHUNK_PROGRAMS = ("jit_chunk_fn", "jit_step_chunk")


def ms_per_chunk(obs: dict, labels: tuple):
    """Mean self milliseconds a run of a program that holds a chunk
    spends in ``labels``, or None where the trace has no such run."""
    scoped = obs.get("scoped") or {}
    held = [scoped[p] for p in CHUNK_PROGRAMS if p in scoped]
    runs = sum(p["runs"] for p in held)
    secs = sum(p["label_seconds"].get(k, 0.0) for p in held for k in labels)
    return 1e3 * secs / runs if runs and secs else None
