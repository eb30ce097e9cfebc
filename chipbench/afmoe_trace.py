"""Device time by MECHANISM of the hybrid programs in their ``afmoe``
layout: the label table of ``scoped_trace.py`` for these widths.

This table is for ``trinity-large-preview-5L-e32``: hidden 3,072, 48
query heads over 8 K/V heads of 128 (``wqkv [3072, 14336]``: q, k, v and
the output gate's projection as one matrix; ``wo [6144, 3072]``), a
dense MLP of 12,288,
32 held gated experts of 3,072 (``w_in [32, 3072, 6144]``, ``w_out [32,
3072, 3072]``) behind a router of 256, one shared expert, and TWO groups
of K/V pools: the full layers' and the window layers', told apart by
their leading dims (``marks``: the two pools' shapes).  An op's text (its
whole HLO line; the chip names a Mosaic kernel's call ``tpu_custom_
call.N``, so a kernel is told by its operands) is labelled, first match
first, by

  * a custom call on a pool: ``swa_decode_attention`` /
    ``full_decode_attention`` (the one-token kernel, by WHICH pool);
  * a custom call on the running output ``f32[48, 128, chunk]``: the
    head-wise window kernel, ``mixer_swa_attention`` where its positions
    operand has two rows (``s32[2, chunk]``: a query's position and the
    last key it no longer sees), else ``mixer_full_attention``;
  * a custom call on the experts' stacks: ``routed_experts`` (the
    grouped matmul);
  * any other op one of whose operands is a pool: ``swa_pool_ops`` /
    ``full_pool_ops`` (the commit of the pass's K/V, which sees a pool
    as stored or flattened to ``[rows x block, width]``; a chunk's
    gathers of key blocks with what is fused onto them);
  * the parameter it reads, by name, then the SHAPE of a weight (a
    product that consumes a prefetched copy, or a slice of one, names no
    parameter but keeps the weight's shape or its minor dim);
  * given the cell's sizes (``marks``): the window form's arrays that
    both kinds of layer have (queries heads-first, a block of values
    transposed, the running softmax): ``attention_walk``, which the
    readers share out by layer count; the routed assignments' leading
    dims.

What matches nothing is ``other`` (norms, residuals, the embedding, the
head, rotary tables).  The event reader's second stage and
``ms_per_run`` are ``scoped_trace``'s own.
"""

from __future__ import annotations

import re

from chipbench.scoped_trace import ms_per_run, summarize  # noqa: F401
from chipbench.trace_reduce import (DEVICE_PREFIX, MODULES_LINE, OPS_LINE,
                                    short_name)

SWA_DECODE = ("swa_decode_attention", "swa_pool_ops")
SWA_PREFILL = ("mixer_swa_attention", "swa_pool_ops")
FULL_PREFILL = ("mixer_full_attention", "full_pool_ops")
WALK = ("attention_walk",)
EXPERTS = ("routed_experts", "shared_expert")
KEY_BLOCK = 1024        # ray_tpu/ops/attention.KEY_BLOCK, by shape

KERNEL = re.compile(r"custom-call|tpu_custom_call")
RULES = (
    (re.compile(r"\[3072,24576\]|\[12288,3072\]"), "dense_mlp"),
    (re.compile(r"ffn____shared|bf16\[3072,3072\]|\[3072,6144\]"),
     "shared_expert"),
    (re.compile(r"ragged-dot|%gmm\b|ffn____(w_in|w_out|router)"
                r"|\[32,3072,6144\]|\[32,3072,3072\]|\[3072,256\]"),
     "routed_experts"),
    (re.compile(r"mixer____(wqkv|wo|q_norm|k_norm)|\[3072,14336\]"
                r"|\[6144,3072\]"), "attention_proj"),
)


def marks_of(engine: dict, full_pool: tuple, window_pool: tuple,
             heads: int = 48, kv_heads: int = 8, head_dim: int = 128,
             top_k: int = 4, experts: int = 256) -> dict:
    """The shapes that depend on the cell, {label: strings one of which
    an op's text holds}: the two groups' pools as stored, the window
    form's arrays at the cell's chunk, the routed assignments."""
    rows, chunk = engine["max_slots"], engine["prefill_chunk"]

    def pool(shape):
        """A pool as stored, or flattened to [rows x block, width]."""
        return ("bf16[" + ",".join(map(str, shape)) + "]",
                f"bf16[{shape[0] * shape[1]},{shape[2]}]")
    walk = [f"[{heads},{chunk},{head_dim}]", f"[{chunk},{heads},{head_dim}]",
            f"[{kv_heads},{head_dim},{KEY_BLOCK}]",
            f"[{KEY_BLOCK},{kv_heads},{head_dim}]",
            f"f32[{heads},{head_dim},{chunk}]", f"f32[{heads},1,{chunk}]"]
    routed = []
    for n in (rows, chunk):
        routed += [f"[{n * top_k}]", f"[{n * top_k},", f"[{n},{top_k}]",
                   f"[{n},{top_k},", f"[{n},{experts}]"]
    return {"full_pool": pool(full_pool), "window_pool": pool(window_pool),
            "window_positions": f"s32[2,{chunk}]",
            "running_output": f"f32[{heads},{head_dim},{chunk}]",
            "attention_walk": tuple(walk), "routed_experts": tuple(routed)}


def label_of(text: str, marks: dict = {}) -> str:
    in_window = any(s in text for s in marks.get("window_pool", ()))
    in_full = any(s in text for s in marks.get("full_pool", ()))
    if KERNEL.search(text):
        if in_window:
            return "swa_decode_attention"
        if in_full:
            return "full_decode_attention"
        if marks.get("running_output", "\0") in text:
            return ("mixer_swa_attention"
                    if marks.get("window_positions", "\0") in text
                    else "mixer_full_attention")
    if in_window:
        return "swa_pool_ops"
    if in_full:
        return "full_pool_ops"
    for pattern, label in RULES:
        if pattern.search(text):
            return label
    for label in ("attention_walk", "routed_experts"):
        if any(s in text for s in marks.get(label, ())):
            return label
    return "other"


def load_events(xplane_path: str, marks: dict = {}, other: dict = None) -> list:
    """``scoped_trace.load_events`` with this table: rows ``[plane, line,
    label, start_ns, duration_ns]``.  ``other``: a dict that receives
    the summed nanoseconds of each op text labelled ``other`` (for the
    builder of this table)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    rows = []
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name not in (MODULES_LINE, OPS_LINE):
                continue
            for ev in line.events:
                if line.name == MODULES_LINE:
                    name = short_name(ev.name)
                else:
                    name = label_of(ev.name, marks)
                    if other is not None:
                        key = (name, ev.name[:400])
                        other[key] = other.get(key, 0) + int(ev.duration_ns)
                rows.append([plane.name, line.name, name, int(ev.start_ns),
                             int(ev.duration_ns)])
    return rows


def shared_out(obs: dict, program: str, own: tuple, share: float):
    """Mean self milliseconds a run of ``program`` spends in the labels
    ``own`` plus ``share`` of the window form's arrays that both kinds of
    attention layer have (``attention_walk``), or None."""
    ms = ms_per_run(obs, program, own)
    if ms is None:
        return None
    return ms + share * (ms_per_run(obs, program, WALK) or 0.0)
