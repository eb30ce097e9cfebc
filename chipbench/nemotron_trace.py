"""Device time by MECHANISM of the hybrid programs in their ``nemotron_h``
layout: the label table of ``scoped_trace.py`` for the other widths.

``scoped_trace.RULES`` is written for the first hybrid configuration's
shapes (an ``8192,128]`` state, 8448 convolution channels); this table
is for ``nemotron-3-nano-30b-a3b-13L-e64``: single-mixer layers, the
state pool ``4096,128]``, 6144 convolution channels, eight groups of 512
channels under the gated norm.  An op's text (its whole HLO line, the
operands with their shapes) is labelled, first match first, by

  * the parameter it reads, by name (``ffn____w_in``, ``mixer____wqkv``,
    ...: the names are the same in both layouts), and the grouped
    matmul's custom calls, ``ragged-dot`` or ``gmm``;
  * the SHAPE of a weight: the compiler prefetches some weights
    (``copy-start`` / ``copy-done``) and the product that consumes the
    copy names no parameter, but its operand keeps the weight's shape
    (``[2688,3712]``: the shared expert's);
  * else a shape no other mechanism of these programs has: the state
    pool's ``4096,128]`` (the one-token kernel's operand and result),
    the convolution's ``6144]`` channels, the group-wise norm's
    ``8,512]``, the mixer's float32 inner width ``1,4096]``, and, given
    the cell's sizes (``marks``), the kernel's per-row operands ``[rows,
    8,128]`` / ``[rows,32,128]``, the per-head ``[rows,64]`` /
    ``[rows,64,64]`` and the router's ``[rows,128]`` / ``[rows,6]``.

``[4096,2688]`` is the Mamba output projection's shape AND the
attention output projection's: both are ``projection`` here (no metric
reads either).  What matches nothing is ``other`` (norms, residuals,
embedding, head, the paged-attention kernel).  The event reader's second
stage and ``ms_per_run`` are ``scoped_trace``'s own.
"""

from __future__ import annotations

import re

from chipbench.scoped_trace import ms_per_run, summarize  # noqa: F401
from chipbench.trace_reduce import (DEVICE_PREFIX, MODULES_LINE, OPS_LINE,
                                    short_name)

EXPERTS = ("routed_experts", "shared_expert")
GROUPED_SSM = ("grouped_ssm",)

RULES = (
    (re.compile(r"ragged-dot|%gmm\b|ffn____w_in|ffn____w_out|ffn____router"
                r"|\[64,2688,1920\]|\[64,1856,2688\]|bf16\[2688,128\]"),
     "routed_experts"),
    (re.compile(r"ffn____shared|\[2688,3712\]|\[3712,2688\]"),
     "shared_expert"),
    (re.compile(r"mixer____(conv_|dt_bias|A_log|D_|gnorm)"), "grouped_ssm"),
    # the weights' shapes before the mixer's own: the output projection
    # consumes the mixer's float32 [rows,1,4096]
    (re.compile(r"mixer____in_proj|\[2688,10304\]"), "mixer_ssm_proj"),
    (re.compile(r"mixer____wqkv|\[(2688|672),4608\]"), "mixer_attention"),
    (re.compile(r"mixer____out_proj|mixer____wo|\[(4096|1024),2688\]"),
     "projection"),
    (re.compile(r"4096,128\]|[\[,]6144\]|,8,512\]|f32\[\d+,1,4096\]"),
     "grouped_ssm"),
)
# after the cell's marks: what only carries a projection's result on
LATE = (
    (re.compile(r"[\[,]10304\]"), "mixer_ssm_proj"),
    (re.compile(r"[\[,]4608\]"), "mixer_attention"),
)


def marks_of(published: dict, rows: int, chunk: int) -> dict:
    """The shapes that depend on the cell, {label: strings one of which
    an op's text holds}: ``rows`` decode rows and a ``chunk`` of prompt
    tokens through ``published``'s router and Mamba heads."""
    k, e = published["num_experts_per_tok"], published["n_routed_experts"]
    h, p = published["mamba_num_heads"], published["mamba_head_dim"]
    g = published["n_groups"]
    per_chunk = h * p // 128            # ops/ssm.CHUNK_ROWS rows a chunk
    routed, ssm = [], []
    for n in (rows, chunk):
        routed += [f"[{n * k}]", f"[{n * k},", f"[{n},{k}]", f"[{n},{k},",
                   f"[{n},{e}]"]
        ssm += [f"f32[{n},{g},128]", f"f32[{n},{per_chunk},128]",
                f"f32[{n},{h}]", f"f32[{n},{h},{p}]"]
    return {"routed_experts": tuple(routed), "grouped_ssm": tuple(ssm)}


def label_of(text: str, marks: dict = {}) -> str:
    for pattern, label in RULES:
        if pattern.search(text):
            return label
    for label, shapes in marks.items():
        if any(s in text for s in shapes):
            return label
    for pattern, label in LATE:
        if pattern.search(text):
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
