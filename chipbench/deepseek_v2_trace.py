"""Device time by MECHANISM of the hybrid programs in their ``deepseek_v2``
layout: the label table of ``scoped_trace.py`` for these widths.

This table is for ``deepseek-v2-7L-e20``: latent attention (a query
bottleneck of 1,536, 128 heads of 128 + 64 lanes, ONE cached vector of
576 lanes a token stored at 640), a dense MLP of 12,288 in layer 0, 20
held gated experts of 1,536 and one shared MLP of 3,072.  An op's text
(its whole HLO line, the operands with their shapes; the chip names a
Mosaic kernel's call ``tpu_custom_call.N``, so a kernel is told by its
operands) is labelled, first match first, by

  * ``latent_decode_attention``: a custom call one of whose operands is
    the absorbed query ``[rows, 128, 640]`` — the one-token kernel over
    the latent pool, and nothing else in these programs;
  * the parameter it reads, by name (``mixer____wkv_b``,
    ``ffn____shared_in``, ...), or the SHAPE of a weight (the compiler
    prefetches some weights, and the product that consumes the copy
    names no parameter but keeps the weight's shape): the dense MLP
    before the experts (both are ``ffn____w_in``; the dense one is
    ``[5120, 24576]``);
  * ``latent_kvb``: the up-projections ``W_uk`` / ``W_uv`` ``[128, 512,
    128]`` — in the chunk program the decompression of a block of keys
    (part of the window form), in the decode step the absorption into
    the query and the output;
  * given the cell's sizes (``marks``): the window form's own arrays —
    the decompressed keys ``[128, key_block, 128]`` and values ``[128,
    128, key_block]``, the running output ``[128, 128, chunk]`` and
    statistics ``[128, 1, chunk]`` (the window kernel's operands: its
    scores stay in VMEM), a gathered block of latents ``[key_block,
    640]`` — and the routed experts' per-assignment arrays.

What matches nothing is ``other`` (norms, residuals, rotary, embedding,
head).  The event reader's second stage and ``ms_per_run`` are
``scoped_trace``'s own.
"""

from __future__ import annotations

import re

from chipbench.scoped_trace import ms_per_run, summarize  # noqa: F401
from chipbench.trace_reduce import (DEVICE_PREFIX, MODULES_LINE, OPS_LINE,
                                    short_name)

EXPERTS = ("routed_experts", "shared_expert")
WINDOW = ("latent_window", "latent_kvb")
DECODE_KERNEL = ("latent_decode_attention",)
KEY_BLOCK = 1024        # ray_tpu/ops/attention.KEY_BLOCK, by shape

KERNEL = re.compile(r"custom-call|tpu_custom_call")
RULES = (
    (re.compile(r"\[5120,24576\]|\[12288,5120\]"), "dense_mlp"),
    (re.compile(r"ragged-dot|%gmm\b|ffn____w_in|ffn____w_out|ffn____router"
                r"|\[20,5120,3072\]|\[20,1536,5120\]|bf16\[5120,160\]"),
     "routed_experts"),
    (re.compile(r"ffn____shared|\[5120,6144\]|\[3072,5120\]"),
     "shared_expert"),
    (re.compile(r"mixer____w_uk|mixer____w_uv|\[128,512,128\]"),
     "latent_kvb"),
    (re.compile(r"mixer____(wq_a|wq_nope|wq_rope|wkv_a|wo|q_norm|kv_norm)"
                r"|\[5120,1536\]|\[128,128,1536\]|\[64,128,1536\]"
                r"|\[5120,576\]|\[16384,5120\]"), "mixer_latent_proj"),
)


def marks_of(published: dict, rows: int, chunk: int) -> dict:
    """The shapes that depend on the cell, {label: strings one of which
    an op's text holds}: ``rows`` decode rows and a ``chunk`` of prompt
    tokens through ``published``'s router and heads."""
    k, e = published["num_experts_per_tok"], published["n_routed_experts"]
    h = published["num_attention_heads"]
    dn, dv = published["qk_nope_head_dim"], published["v_head_dim"]
    routed = []
    for n in (rows, chunk):
        routed += [f"[{n * k}]", f"[{n * k},", f"[{n},{k}]", f"[{n},{k},",
                   f"[{n},{e}]"]
    window = [f"[{h},{KEY_BLOCK},{dn}]", f"[{h},{dv},{KEY_BLOCK}]",
              f"f32[{h},{dv},{chunk}]", f"f32[{h},1,{chunk}]",
              f"[{KEY_BLOCK},640]", f"[{KEY_BLOCK // 16},16,640]",
              f"[{KEY_BLOCK},512]", f"[{KEY_BLOCK},64]"]
    return {"latent_window": tuple(window), "routed_experts": tuple(routed)}


def label_of(text: str, marks: dict = {}) -> str:
    if KERNEL.search(text) and re.search(r"\[\d+,128,640\]", text):
        return "latent_decode_attention"
    for pattern, label in RULES:
        if pattern.search(text):
            return label
    for label, shapes in marks.items():
        if any(s in text for s in shapes):
            return label
    return "other"


def load_events(xplane_path: str, marks: dict = {}) -> list:
    """``scoped_trace.load_events`` with this table: rows ``[plane, line,
    label, start_ns, duration_ns]``."""
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
                name = short_name(ev.name) if line.name == MODULES_LINE \
                    else label_of(ev.name, marks)
                rows.append([plane.name, line.name, name, int(ev.start_ns),
                             int(ev.duration_ns)])
    return rows
