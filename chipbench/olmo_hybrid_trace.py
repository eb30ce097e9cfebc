"""Device time by MECHANISM of the hybrid programs in their
``olmo_hybrid`` layout: the label table of ``scoped_trace.py`` for these
widths.

This table is for ``olmo-hybrid-7b-16L``: linear-attention layers of 30
heads (keys 96, values 192: 11,520 convolved channels, a matrix state
``[96, 5760]`` a row and layer), full-attention layers of 30 heads of
128 over K/V pools of 3,840 lanes, a dense MLP of 11,008.  An op's text
(its whole HLO line, the operands with their shapes; the chip names a
Mosaic kernel's call ``tpu_custom_call.N``, so a kernel is told by its
operands) is labelled, first match first, by

  * ``delta_step``: a custom call one of whose operands is the state
    pool ``[layers, rows, 96, 5760]`` — the one-token kernel;
  * ``window_attention``: a custom call on the running output ``f32[30,
    128, chunk]`` — the head-wise window kernel; ``paged_decode_
    attention``: a custom call on the K/V pool (``marks``);
  * the parameter it reads, by name: ``conv_w``, ``A_log``, ``dt_bias``,
    ``gnorm`` are the linear mixer's own; ``wg``, ``wab`` its
    projections; ``q_norm`` / ``k_norm`` the attention's; ``wqkv`` and
    ``wo`` are BOTH mixers' names (and ``wqkv`` both mixers' shape,
    ``[3840, 11520]``), told apart by the layer's index in the name
    (``marks["attention_layers"]``); ``ffn`` is the dense MLP;
  * the SHAPE of a weight (a product that consumes a prefetched copy
    names no parameter but keeps the weight's shape);
  * given the cell's sizes (``marks``): the delta rule's own arrays —
    blocks of 64 tokens, a head's ``[96, 192]`` state, the per-head
    ``[.., 30, 96]`` / ``[.., 30, 192]`` splits, 5,760 lanes, the
    convolution's ``[.., 3, 11520]`` state and padded window — and the
    window attention's — queries and a key block heads-first, a gathered
    block of keys ``[64, 16, 3840]``.

What matches nothing is ``other`` (the residual stream's norms, the
embedding, the head).  The event reader's second stage and
``ms_per_run`` are ``scoped_trace``'s own.
"""

from __future__ import annotations

import re

from chipbench.scoped_trace import ms_per_run, summarize  # noqa: F401
from chipbench.trace_reduce import (DEVICE_PREFIX, MODULES_LINE, OPS_LINE,
                                    short_name)

# what the per-layer metrics sum: the one-token form (conv, kernel,
# gated norm), the window form (the same label in a chunk program: conv,
# the chunkwise products, gated norm), the head-wise window attention
DELTA_STEP = ("delta_step", "mixer_linear_attention")
DELTA_WINDOW = ("mixer_linear_attention",)
WINDOW_ATTENTION = ("window_attention",)
KEY_BLOCK = 1024        # ray_tpu/ops/attention.KEY_BLOCK, by shape
BLOCK = 64              # ray_tpu/ops/delta_rule.BLOCK, by shape

KERNEL = re.compile(r"custom-call|tpu_custom_call")
SHARED_NAME = re.compile(r"layers___(\d+)___mixer____(?:wqkv|wo)__")
RULES = (
    (re.compile(r"mixer____(conv_w|A_log|dt_bias|gnorm)"),
     "mixer_linear_attention"),
    (re.compile(r"mixer____(wg|wab)|\[3840,5760\]|\[5760,3840\]"
                r"|\[3840,60\]"), "mixer_linear_proj"),
    (re.compile(r"mixer____(q_norm|k_norm)|\[3840,3840\]"),
     "mixer_attention"),
    (re.compile(r"ffn____|\[3840,22016\]|\[11008,3840\]"), "dense_mlp"),
)


def marks_of(published: dict, rows: int, chunk: int,
             attention_layers: tuple = ()) -> dict:
    """The shapes that depend on the cell, {label: strings one of which
    an op's text holds}: ``rows`` decode rows and a ``chunk`` of prompt
    tokens through ``published``'s heads; ``attention_layers``: the
    indices of the full-attention layers."""
    H, K, V = (published["linear_num_value_heads"],
               published["linear_key_head_dim"],
               published["linear_value_head_dim"])
    h = published["num_attention_heads"]
    hd = published["hidden_size"] // h
    C = H * (2 * K + V)
    linear = [f",{BLOCK},{BLOCK}]", f",{BLOCK},{K}]", f",{BLOCK},{V}]",
              f",{K},{V}]", f",{H},{K}]", f",{H},{V}]", f"{H * V}]",
              f",3,{C}]", f"[1,{chunk + 3},{C}]", f"[{rows},4,{C}]",
              f"[4,{C}]", f",{H},{chunk // BLOCK},"]
    # (a gathered block flattened, [1024, 3840], is also the shape of
    # the residual stream at a chunk of 1,024: not a mark)
    window = [f"[{h},{chunk},{hd}]", f"[{chunk},{h},{hd}]",
              f"[{KEY_BLOCK // 16},16,{h * hd}]",
              f"[{h},{hd},{KEY_BLOCK}]", f"[{KEY_BLOCK},{h},{hd}]",
              f"f32[{h},{hd},{chunk}]", f"f32[{h},1,{chunk}]"]
    return {"mixer_linear_attention": tuple(linear),
            "window_attention": tuple(window),
            "attention_layers": tuple(str(i) for i in attention_layers)}


def label_of(text: str, marks: dict = {}) -> str:
    if KERNEL.search(text):
        if re.search(r"f32\[\d+,\d+,96,5760\]", text):
            return "delta_step"
        if re.search(r"f32\[30,128,\d+\]", text):
            return "window_attention"
        if re.search(r"bf16\[\d+,16,3840\]", text):
            return "paged_decode_attention"
    for pattern, label in RULES:
        if pattern.search(text):
            return label
    shared = SHARED_NAME.search(text)
    if shared or "[3840,11520]" in text:
        # a prefetched copy names no layer: three of four are linear
        return ("mixer_attention" if shared and shared.group(1)
                in marks.get("attention_layers", ()) else "mixer_linear_proj")
    for label in ("mixer_linear_attention", "window_attention"):
        if any(s in text for s in marks.get(label, ())):
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
