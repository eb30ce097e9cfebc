"""Device time by MECHANISM of the hybrid programs in their ``lfm2_moe``
layout: the label table of ``scoped_trace.py`` for these widths.

This table is for ``lfm2-8b-a1b-12L``: hidden 2,048; a short-convolution
mixer's ``in_proj [2048, 6144]`` (B | C | u), ``conv_w [3, 2048]`` and
``out_proj [2048, 2048]``; 32 query heads over 8 K/V heads of 64
(``wqkv [2048, 3072]``, ``wo [2048, 2048]``); a dense MLP of 7,168; 32
gated experts of 1,792 (``w_in [32, 2048, 3584]``, ``w_out [32, 1792,
2048]``) behind a router of 32, no shared expert.  An op's text (its
whole HLO line; the profiler keeps neither the ``jax.named_scope`` nor
the ``op_name`` metadata, ``scoped_trace.py`` says) is labelled, first
match first, by

  * a custom call on the K/V pools: ``decode_attention`` (the one-token
    kernel); on the experts' stacks: ``routed_experts`` (the grouped
    matmul);
  * any other op one of whose operands is a K/V pool: ``kv_pool_ops``;
  * the parameter it reads, by name (``mixer____in_proj`` ...), then
    the SHAPE of a weight: the short convolution's two products are
    ``short_conv_proj``, its taps and gates (``conv_w``, the width-6,144
    product's splits, the rows' state ``[rows, 2, 2048]`` and the
    snapshots ``[blocks, 36864]``) ``short_conv_taps``;
  * ``[2048, 2048]`` with no parameter's name is BOTH ``out_proj`` and
    the attention's ``wo`` (a product that consumes a prefetched copy
    names neither): ``square_proj``, which the readers share out by
    layer count (9 : 3), as ``afmoe_trace`` shares its walk;
  * given the cell's sizes (``marks``): the routed assignments' leading
    dims, as they are and padded to whole 128-row tiles (the sorted
    rows, the hidden activation, the un-sort: all the experts
    sublayer's work, so that its roofline share's time leaves none of it
    out).

What matches nothing is ``other`` (norms, residuals, the embedding, the
head, rotary tables).  The event reader's second stage and
``ms_per_run`` are ``scoped_trace``'s own.
"""

from __future__ import annotations

import re

from chipbench.scoped_trace import ms_per_run, summarize  # noqa: F401
from chipbench.trace_reduce import (DEVICE_PREFIX, MODULES_LINE, OPS_LINE,
                                    short_name)

SHORT_CONV = ("short_conv_proj", "short_conv_taps")
SQUARE = ("square_proj",)
EXPERTS = ("routed_experts",)
# the programs that hold a prefill chunk: alone, or with the decode rows
CHUNK_PROGRAMS = ("jit_chunk_fn", "jit_step_chunk")

KERNEL = re.compile(r"custom-call|tpu_custom_call")
RULES = (
    (re.compile(r"\[2048,14336\]|\[7168,2048\]"), "dense_mlp"),
    (re.compile(r"ragged-dot|%gmm\b|ffn____(w_in|w_out|router)"
                r"|\[32,2048,3584\]|\[32,1792,2048\]|\[2048,32\]"),
     "routed_experts"),
    (re.compile(r"mixer____(in_proj|out_proj)|\[2048,6144\]"
                r"|,6144\]\S* (convolution|dot)\("), "short_conv_proj"),
    (re.compile(r"mixer____conv_w|[\[,]6144\]|\[3,2048\]"),
     "short_conv_taps"),
    (re.compile(r"mixer____(wqkv|wo|q_norm|k_norm)|\[2048,3072\]"
                r"|[\[,]3072\]"), "attention_proj"),
    (re.compile(r"\[2048,2048\]"), "square_proj"),
)


def marks_of(engine: dict, cfg) -> dict:
    """The shapes that depend on the cell, {label: strings one of which
    an op's text holds}: the K/V pools as stored, the rows' state and
    the snapshots, the routed assignments.  ``cfg``: the cell's
    ``HybridConfig`` (its geometry properties alone are read)."""
    rows, chunk = engine["max_slots"], engine["prefill_chunk"]
    n_rows, bs = engine["n_blocks"] + 1, engine["kv_block_size"]
    layers, heads, head_dim = cfg.kv_geometry
    width = -(-heads * head_dim // 128) * 128
    convs, (k1, d), _ = cfg.state_geometry
    top_k, experts = cfg.experts_per_token, cfg.n_experts
    routed = []
    for n in (rows, chunk, rows + chunk):
        # (the sorted assignments are padded to whole 128-row tiles for
        # the grouped matmul: ``ops/routed_experts.grouped_matmul``)
        padded = -(-n * top_k // 128) * 128
        routed += [f"[{n * top_k}]", f"[{n * top_k},", f"[{n},{top_k}]",
                   f"[{n},{top_k},", f"[{n},{experts}]", f"[{padded},"]
    return {"kv_pool": (f"bf16[{layers * n_rows},{bs},{width}]",
                        f"bf16[{layers * n_rows * bs},{width}]"),
            "state": (f"[{convs},{rows},{k1},{d}]", f"[{rows},{k1},{d}]",
                      f"[{n_rows},{convs * k1 * d}]", f"[1,{k1},{d}]"),
            "routed_experts": tuple(routed)}


def label_of(text: str, marks: dict = {}) -> str:
    in_pool = any(s in text for s in marks.get("kv_pool", ()))
    if KERNEL.search(text) and in_pool:
        return "decode_attention"
    if in_pool:
        return "kv_pool_ops"
    for pattern, label in RULES:
        if pattern.search(text):
            return label
    if any(s in text for s in marks.get("state", ())):
        return "short_conv_taps"
    if any(s in text for s in marks.get("routed_experts", ())):
        return "routed_experts"
    return "other"


def load_events(xplane_path: str, marks: dict = {}, other: dict = None) -> list:
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
                        key = (name, ev.name[:400])
                        other[key] = other.get(key, 0) + int(ev.duration_ns)
                rows.append([plane.name, line.name, name, int(ev.start_ns),
                             int(ev.duration_ns)])
    return rows


def short_conv_ms(obs: dict, programs: tuple):
    """Mean self milliseconds a run of ``programs`` spends in the short
    convolution's labels plus its share by layer count of
    ``square_proj``, or None.  -> (ms, runs)."""
    scoped = obs.get("scoped") or {}
    convs, layers = obs.get("conv_layers"), len(
        (obs.get("published") or {}).get("layer_types", ()))
    runs = sum(scoped[p]["runs"] for p in programs if p in scoped)
    if not runs or not convs or not layers:
        return None, 0
    secs = 0.0
    for p in programs:
        seconds = scoped.get(p, {}).get("label_seconds", {})
        secs += sum(seconds.get(k, 0.0) for k in SHORT_CONV)
        secs += convs / layers * sum(seconds.get(k, 0.0) for k in SQUARE)
    return (1e3 * secs / runs if secs else None), runs
