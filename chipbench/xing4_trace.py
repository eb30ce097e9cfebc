"""Device time by MECHANISM of the hybrid programs in their ``xing4_0``
layout: the label table of ``scoped_trace.py`` for these widths.

This table is for ``xing4.0-29b-a4b-6L``: four residual streams of 3,584
lanes (read flat, ``[tokens, 14336]``), three maps a sublayer from a
``[24, 14336]`` float32 matrix, latent attention (a query bottleneck of
768, 32 heads of 128 + 64 lanes, ONE cached vector of 576 lanes a token
stored at 640), a dense MLP of 9,216 in layer 0, 64 held gated experts
of 1,024 and one shared MLP of 1,024.  An op's text (its whole HLO
line: the profiler keeps neither the ``jax.named_scope`` nor the
``op_name``, so the program's scopes ``mhc_pre_map`` / ``mhc_sinkhorn``
/ ``mhc_mix_in`` / ``mhc_mix_out`` reach a trace only through what an
op's text DOES keep) is labelled, first match first, by

  * ``latent_decode_attention``: a custom call one of whose operands
    has 640 minor lanes behind two more dims (the absorbed query
    ``[rows, 32, 640]``, the pool ``[blocks, 16, 640]``);
  * the stream mix, which no other mechanism's shapes can be taken for
    (14,336 lanes, 24 maps, a 4 x 4 matrix a token), by what an op
    reads (the trace's text has the operands' shapes) or writes:
    ``mhc_pre_map`` — an op that reads a sublayer's ``hc`` parameters
    (``hc____phi`` ...) or the maps' product ``f32[24, tokens]``: the
    norm over 14,336 lanes, the product with ``Phi``, the sigmoids, the
    clamp and the exponentials; ``mhc_mix_out`` — an op that WRITES
    the streams (``H_res X + H_post^T F``): on the chip the compiler
    makes it the EPILOGUE of the sublayer's last product where there is
    one (``W_o`` of the attention, ``W_down`` of the dense MLP: one
    fusion that names the weight, reads the maps' entries and writes
    several streams; my chip run, PR 61), and that op's WHOLE time is
    the mix's here, the product's own included (0.15-0.35 ms each a
    chunk of 1,024): ``mhc_ms_per_chunk.serve`` reads HIGH and the
    roofline share LOW by that much, never the other way;
    ``mhc_mix_in`` — any other op that reads the streams or the maps'
    entries beside ONE stream-wide result (``H_pre X`` and the
    sublayer's input norm, which the compiler fuses behind it);
    ``mhc_sinkhorn`` — an op on the residual map ``f32[4, 4, tokens]``
    alone: the 2 x 20 normalisations, a fusion each of ~0.3 us (the map
    stays in VMEM), and the map's transposition for the mix;
  * then the weights by name or shape and the window form's own arrays,
    as ``deepseek_v2_trace`` does for its widths.

What matches nothing is ``other`` (norms, rotary, embedding, head).
The event reader's second stage and ``ms_per_run`` are
``scoped_trace``'s own.
"""

from __future__ import annotations

import re

from chipbench.deepseek_v2_trace import KERNEL, KEY_BLOCK
from chipbench.scoped_trace import ms_per_run, summarize  # noqa: F401
from chipbench.trace_reduce import (DEVICE_PREFIX, MODULES_LINE, OPS_LINE,
                                    short_name)

EXPERTS = ("routed_experts", "shared_expert")
WINDOW = ("latent_window", "latent_kvb")
DECODE_KERNEL = ("latent_decode_attention",)
MHC = ("mhc_pre_map", "mhc_sinkhorn", "mhc_mix_in", "mhc_mix_out")

STREAMS = re.compile(r"[\[,]14336\]")
# a sublayer's ``hc`` parameters, or the maps' product [24, tokens]
MAPS = re.compile(r"hc____(phi|w|alpha|b)\b|f32\[24,\d+\]")
# the residual map a token, tokens minor ([4, 4, T], and its row or
# column sums [4, T]) or, on its way to the mix, tokens major
SINKHORN = re.compile(r"f32\[4,4,\d+\]|f32\[\d+,4,4\]")
RULES = (
    (re.compile(r"\[3584,18432\]|\[9216,3584\]"), "dense_mlp"),
    (re.compile(r"ragged-dot|%gmm\b|ffn____w_in|ffn____w_out|ffn____router"
                r"|\[64,3584,2048\]|\[64,1024,3584\]|bf16\[3584,64\]"),
     "routed_experts"),
    # (W_2 of the shared expert is [1024, 3584]: a chunk of 1,024
    # tokens' every activation too, so it is told by name alone)
    (re.compile(r"ffn____shared|\[3584,2048\]"), "shared_expert"),
    (re.compile(r"mixer____w_uk|mixer____w_uv|\[32,512,128\]"),
     "latent_kvb"),
    (re.compile(r"mixer____(wq_a|wq_nope|wq_rope|wkv_a|wo|q_norm|kv_norm)"
                r"|\[3584,768\]|\[32,128,768\]|\[64,32,768\]"
                r"|\[3584,576\]|\[4096,3584\]"), "mixer_latent_proj"),
)


def marks_of(published: dict, rows: int, chunk: int) -> dict:
    """The shapes that depend on the cell, as ``deepseek_v2_trace.
    marks_of`` gives them for this router and these heads."""
    k, e = published["num_experts_per_tok"], published["n_routed_experts"]
    h = published["num_attention_heads"]
    dn, dv = published["qk_nope_head_dim"], published["v_head_dim"]
    routed = []
    for n in (rows, chunk):
        padded = -(-n * k // 128) * 128
        routed += [f"[{n * k}]", f"[{n * k},", f"[{n},{k}]", f"[{n},{k},",
                   f"[{n},{e}]", f"[{padded},"]
    window = [f"[{h},{KEY_BLOCK},{dn}]", f"[{h},{dv},{KEY_BLOCK}]",
              f"f32[{h},{dv},{chunk}]", f"f32[{h},1,{chunk}]",
              f"[{KEY_BLOCK},640]", f"[{KEY_BLOCK // 16},16,640]",
              f"[{KEY_BLOCK},512]", f"[{KEY_BLOCK},64]"]
    # a per-token entry of a map, as the mixes read them (one vector of
    # tokens each), and a stream's or the mixed stream's array
    c = published["hidden_size"]
    mhc = [(f"f32[{n}]{{", f"[{n},{c}]") for n in (rows, chunk)]
    return {"latent_window": tuple(window), "routed_experts": tuple(routed),
            "_mhc": tuple(mhc)}


def _result(text: str) -> str:
    """The result's shape(s), a tuple's too: the text between `` = ``
    and the op's name."""
    return re.split(r" [a-z][\w-]*\(", text.split(" = ", 1)[-1], 1)[0]


def label_of(text: str, marks: dict = {}) -> str:
    if KERNEL.search(text) and re.search(r"\[\d+,\d+,640\]", text):
        return "latent_decode_attention"
    if MAPS.search(text):
        return "mhc_pre_map"
    result = _result(text)
    if STREAMS.search(result):
        return "mhc_mix_out"
    for entry, stream in marks.get("_mhc", ()):
        # behind the embedding the compiler keeps the four streams as
        # four arrays [tokens, 3584]: a mix is then told by the entries
        # of the maps it reads, a vector of tokens each (a norm reads
        # one such vector, a mix five or more), and the way out (with,
        # fused behind it, the next sublayer's way in) by the several
        # streams it writes
        if text.count(entry) >= 3 and stream in text:
            return ("mhc_mix_out" if result.count(stream) >= 2
                    else "mhc_mix_in")
    if STREAMS.search(text):
        return "mhc_mix_in"
    if SINKHORN.search(text):
        return "mhc_sinkhorn"
    for pattern, label in RULES:
        if pattern.search(text):
            return label
    for label, shapes in marks.items():
        if not label.startswith("_") and any(s in text for s in shapes):
            return label
    return "other"


def load_events(xplane_path: str, marks: dict = {},
                other: dict = None) -> list:
    """``scoped_trace.load_events`` with this table: rows ``[plane, line,
    label, start_ns, duration_ns]``.  ``other``: a dict that receives
    the summed nanoseconds of each op text with its label (for the
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
                        key = (name, ev.name[:1500])
                        other[key] = other.get(key, 0) + int(ev.duration_ns)
                rows.append([plane.name, line.name, name, int(ev.start_ns),
                             int(ev.duration_ns)])
    return rows
