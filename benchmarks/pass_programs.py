"""The three programs of a serving pass, alone on the chip at a cell's
shapes: the chunk program (``jit_chunk_fn``), the decode step
(``jit_step``), the two back to back (the pass of two programs) and,
where the layout has it, the two as ONE (``jit_step_chunk``,
``recurrent.has_step_chunk``) — ms a program, so that a fused pass's
arithmetic (chunk + step -> fused) is read before any end-to-end run
(PR 58: OLMo-Hybrid's 59.2 + 12.6 -> ~63; ROADMAP C18).

The cell's own configuration file gives the model and the engine's
geometry; the weights are ``hybrid.init_params`` (a dense layout's time
does not follow their values), ``--rows`` rows decode at ``--keys``
keys each, and the chunk is ``--n-valid`` real tokens of another row's
prompt from position ``--start``.  ``--rows`` and ``--keys`` have no
default: take them from what the cell RUNS, the ledger's
``batch_occupancy.serve`` x the engine's ``max_slots`` and the traffic
file's median prompt + half its median output (olmo's doc3k-r80: 13.5 %
of 32 = 4.3, 4.8 tokens a fused pass by the account, ledger and my chip
runs, PR 58; 3,072 + 80), not from a guess: PR 58's first readings were
taken at 13 rows, a pass the cell does not run.  A reading is host time
over ``--reps`` calls, each program chained on the pools the one before
left (they are donated).  The fused program's logits are compared with
the two programs' on the same inputs first.

    chiprun -- python benchmarks/pass_programs.py \\
        --config olmo-hybrid-7b-16L --rows 5 --keys 3150 \\
        --out chiprun_out/pass_programs.jsonl

``--profile DIR`` then runs each pass three more times inside a
profiler session and prints every program's device ms a run by the
labels of the trace table of the cell's kind
(``chipbench/<kind>_trace.py``, called as ``olmo_hybrid_trace``'s is; a kind whose table is named or
called otherwise reads ``profile_error`` beside its times).

Off the chip this exits 2 (a CPU time is no device number);
``--rehearse`` runs the cell's rehearsal fixture to check the paths and
prints no time."""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
import numpy as np                                           # noqa: E402

from benchmarks.program_text import CELLS                    # noqa: E402
from ray_tpu.inference import decode, recurrent              # noqa: E402
from ray_tpu.inference.cache import BlockPool                # noqa: E402
from ray_tpu.models import hybrid                            # noqa: E402


def build(config: dict, kind: str, rows: int, keys: int, start: int,
          n_valid: int):
    """-> (params, programs by name, their packed arrays, ``fresh()``:
    the programs' other operands anew — pools, state, feed —, the
    shapes, (cfg, the published keys or None))."""
    got = importlib.import_module(
        f"chipbench.traffic.{kind}").model_config(config)
    cfg, published = got if isinstance(got, tuple) else (got, None)
    if cfg.n_window:
        raise SystemExit("two groups of K/V pools: not this bench's yet")
    e = config["engine"]
    bs, C, slots = e.get("kv_block_size", 16), e["prefill_chunk"], \
        e["max_slots"]
    T = -(-e["max_seq"] // bs)
    n_valid = min(n_valid, C)
    keys = min(keys, e["max_seq"] - 1)
    start = min(start, e["max_seq"] - C)
    per_row = -(-(max(keys + 1, start + C)) // bs)
    rows = min(rows, slots - 1, e["n_blocks"] // per_row - 1)
    params = hybrid.init_params(cfg, jax.random.PRNGKey(0))

    def fresh():
        pool = BlockPool(cfg, e["n_blocks"], bs, max_seq=e["max_seq"],
                         state_rows=slots)
        return (pool.pools, () if pool.state is None else pool.state.arrays,
                jnp.zeros(slots, jnp.int32))
    rng = np.random.default_rng(0)
    ids = rng.permutation(np.arange(1, e["n_blocks"] + 1))
    tables = np.zeros((slots, T), np.int32)
    for r in range(rows + 1):            # the last one is the chunk's row
        tables[r, :per_row] = ids[r * per_row:(r + 1) * per_row]
    active = np.arange(slots) < rows
    step = decode.pack_step(
        tables, rng.integers(0, cfg.vocab_size, slots).astype(np.int32),
        np.where(active, keys, 0).astype(np.int32), active)
    toks = np.zeros(C, np.int32)
    toks[:n_valid] = rng.integers(0, cfg.vocab_size, n_valid)
    chunk = decode.pack_chunk(tables[rows], toks, start, rows, n_valid)
    kw = dict(block_size=bs, n_table=T)
    programs = {
        "chunk": recurrent.make_recurrent_chunk_fn(cfg, chunk=C, **kw),
        "step": recurrent.make_recurrent_decode_step(cfg, **kw)}
    packed = {"chunk": chunk, "step": step}
    if recurrent.has_step_chunk(cfg):
        programs["step_chunk"] = recurrent.make_recurrent_step_chunk(
            cfg, chunk=C, **kw)
        packed["step_chunk"] = decode.pack_step_chunk(step, chunk)
    shapes = dict(rows=rows, keys=keys, start=start, n_valid=n_valid,
                  chunk=C, slots=slots)
    return params, programs, packed, fresh, shapes, (cfg, published)


def timed(params, passes: list, operands, reps: int):
    """``passes``: the (program, packed) pairs of ONE pass, run back to
    back ``reps`` times on the operands the call before left.  -> (ms a
    pass, operands)."""
    def once(operands):
        for program, packed in passes:
            _, ints, *operands = program(params, *operands, packed)
        return ints, operands
    ints, operands = once(operands)
    jax.block_until_ready(ints)
    t0 = time.perf_counter()
    for _ in range(reps):
        ints, operands = once(operands)
    jax.block_until_ready(ints)
    return (time.perf_counter() - t0) / reps * 1e3, operands


def profile(table: str, model, shapes: dict, run, trace_dir: str) -> dict:
    """``run()`` inside a profiler session -> {program: {"runs": n,
    "ms_by_label": {label: device ms a run}}} by ``chipbench``'s
    ``table`` for this model."""
    from chipbench import trace_reduce
    mod = importlib.import_module(f"chipbench.{table}")
    cfg, published = model
    jax.profiler.start_trace(trace_dir)
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    marks = mod.marks_of(published, shapes["slots"], shapes["chunk"],
                         [i for i, k in enumerate(cfg.layer_types)
                          if k == hybrid.ATTENTION])
    scoped = mod.summarize(mod.load_events(
        trace_reduce.find_xplane(trace_dir), marks))
    return {name: {"runs": got["runs"], "ms_by_label": {
                label: s / got["runs"] * 1e3 for label, s in sorted(
                    got["label_seconds"].items(), key=lambda kv: -kv[1])}}
            for name, got in scoped.items() if got["runs"]}


def agreement(params, programs, packed, fresh, rows: int) -> dict:
    """The fused program against the chunk program then the step on the
    same inputs: the live rows' and the chunk's last real position's
    logits (widest gap), and whether every greedy token is the same."""
    n = hybrid.N_LOAD
    n_valid = int(packed["chunk"][-1])

    def two():          # (its pools are let go before the next are made)
        l_c, i_c, *rest = programs["chunk"](params, *fresh(),
                                            packed["chunk"])
        l_s, i_s, *_ = programs["step"](params, *rest, packed["step"])
        return (np.asarray(l_c[max(n_valid, 1) - 1]), np.asarray(l_s),
                np.asarray(i_c).tolist(), np.asarray(i_s).tolist())
    last, l_s, i_c, i_s = two()
    l_f, i_f = programs["step_chunk"](params, *fresh(),
                                      packed["step_chunk"])[:2]
    l_f, i_f = np.asarray(l_f), np.asarray(i_f).tolist()
    slots = l_s.shape[0]
    return {"max_abs_logit_diff_rows": float(
                np.abs(l_f[:rows] - l_s[:rows]).max()),
            "max_abs_logit_diff_chunk": float(np.abs(l_f[slots] - last).max()),
            "logit_std": float(l_s[:rows].std()),
            "greedy_equal": (i_f[n:n + rows] == i_s[n:n + rows]
                             and i_f[-1] == i_c[-1])}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="olmo-hybrid-7b-16L",
                    choices=sorted(CELLS))
    ap.add_argument("--rows", type=int, required=True,
                    help="rows decoding: the cell's measured occupancy")
    ap.add_argument("--keys", type=int, required=True,
                    help="keys a decoding row attends: the cell's context")
    ap.add_argument("--start", type=int, default=2048)
    ap.add_argument("--n-valid", type=int, default=1024)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--out", default=None)
    ap.add_argument("--profile", default=None, metavar="DIR")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        print("no chip: a CPU time is no device number (--rehearse checks "
              "the paths on the cell's rehearsal fixture)", file=sys.stderr)
        return 2
    os.chdir(ROOT)
    kind = CELLS[args.config]
    with open(os.path.join("chipbench", "configs",
                           f"{args.config}.json")) as f:
        config = json.load(f)
    if args.rehearse:
        with open(os.path.join(
                "chipbench", "tests", "rehearse_"
                + kind.removeprefix("open_loop_http_") + ".json")) as f:
            config = {**config, **json.load(f)["config"]}
    params, programs, packed, fresh, shapes, model = build(
        config, kind, args.rows, args.keys, args.start, args.n_valid)
    row = {"config": args.config, "device": device.device_kind, **shapes}
    if "step_chunk" in programs:
        row.update(agreement(params, programs, packed, fresh,
                             shapes["rows"]))
    if not args.rehearse:
        operands = fresh()
        passes = {name: [(programs[p], packed[p]) for p in parts]
                  for name, parts in (("chunk", ["chunk"]), ("step", ["step"]),
                                      ("chunk_then_step", ["chunk", "step"]),
                                      ("step_chunk", ["step_chunk"]))
                  if all(p in programs for p in parts)}
        for name, parts in passes.items():
            row[f"ms_{name}"], operands = timed(params, parts, operands,
                                                args.reps)
        if args.profile:
            def run():
                ops = operands
                for parts in passes.values():
                    _, ops = timed(params, parts, ops, 2)
            try:
                row["profile"] = profile(
                    kind.removeprefix("open_loop_http_") + "_trace", model,
                    shapes, run, args.profile)
            except Exception as e:      # the times above are the reading
                row["profile_error"] = repr(e)
    print(json.dumps(row))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
