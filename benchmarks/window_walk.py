"""One layer's window of queries over a row's cached K/V, timed on the
chip in the forms ``decode.paged_attend`` chooses between (PR 53):

  packed  the row's whole table gathered and attended as stored
          (``packed_attention`` under the chunk's mask)
  walk    the table walked a key block at a time, head by head
          (``head_window_attention``: route (a) for heads narrower
          than a lane tile, a gathered key block re-laid a head each)
  pair    route (b), the one NOT taken: a grid step a 128-lane tile =
          two K/V heads of 64, the queries of the other head zero in
          its lanes (the packed trick inside one tile), through the
          same kernel at ``hd`` 128 and half the K/V heads
  parent  ``--parent DIR``: DIR's ``head_window_attention`` (a checkout
          of the parent commit), for the layouts that walked before

at a cell's shapes (``--shape``), over spans of 1 to 4.5 key blocks and
256 / 512 / 1,024 real queries.  ``LAYERS`` layers with pools and
queries of their own run in one program; a reading is the program's
host time over ``--reps`` calls, a layer.  The walk's and the packed
form's outputs are compared on the real lanes first.

    chiprun -- python benchmarks/window_walk.py --shape lfm2 \
        --out chiprun_out/window_walk.jsonl

Readings: PERF.md section 6, PR 53.  Off the chip this exits 2 (a CPU
time is no device number); ``--rehearse`` runs tiny shapes in interpret
mode to check the paths, and prints no time."""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
import numpy as np                                           # noqa: E402

from ray_tpu.inference import decode                         # noqa: E402
from ray_tpu.inference.cache import PoolLayout               # noqa: E402

# (``ray_tpu.ops.attention`` the name is the package's function)
attention = importlib.import_module("ray_tpu.ops.attention")
LAYERS = 3
# heads, K/V heads, lanes a head, chunk, table's keys, cache block, window
SHAPES = {
    "lfm2": (32, 8, 64, 1024, 4608, 64, 0),
    "granite": (32, 8, 128, 256, 2304, 16, 0),
    "nemotron": (32, 2, 128, 128, 3072, 16, 0),
    "olmo": (30, 30, 128, 1024, 8576, 16, 0),
    "trinity": (48, 8, 128, 1024, 33280, 64, 0),
    "trinity_window": (48, 8, 128, 1024, 33280, 64, 4096),
    "tiny": (4, 2, 64, 16, 72, 8, 0),
}


def cases(chunk: int, span: int, key_block: int) -> list:
    """(start, n_valid): whole chunks at every key block of the table,
    then a hit's chunk behind two key blocks with a quarter and a half
    of its lanes real."""
    starts = list(range(0, span - chunk + 1, key_block))
    if starts[-1] != span - chunk:
        starts.append(span - chunk)
    if len(starts) > 6:                  # a long table: six of them
        starts = [starts[i] for i in sorted(
            {round(i * (len(starts) - 1) / 5) for i in range(6)})]
    out = [(s, chunk) for s in starts]
    mid = min(2 * key_block, span - chunk)
    return out + [(mid, chunk // 4), (mid, chunk // 2)]


def build(shape, parent=None):
    h, kv, hd, w, span, bs, window = shape
    T = -(-span // bs)
    n_blocks = 4 * T
    lay = PoolLayout(LAYERS, n_blocks + 1, bs, kv, hd)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    pools = tuple(jax.random.normal(k, lay.shape, jnp.bfloat16)
                  for k in keys[:2])
    q = jax.random.normal(keys[2], (LAYERS, 1, h, w, hd), jnp.bfloat16)
    table = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, n_blocks + 1))[:T], jnp.int32)[None]
    scale = hd ** -0.5

    def positions(start):
        return start + jnp.arange(w, dtype=jnp.int32)

    def over_layers(one):
        def run(q, pools, table, start, n_valid):
            return jnp.stack([one(q[i], pools, table, i, positions(start),
                                  n_valid) for i in range(LAYERS)])
        return jax.jit(run)

    def packed(q, pools, table, layer, pos, n_valid):
        mask = (jnp.arange(T * bs)[None, :] <= pos[:, None])[None, None]
        k, v = (lay.read(p, layer, table) for p in pools)
        return attention.packed_attention(q, k, v, q_per_kv=h // kv,
                                          scale=scale, mask=mask)

    def walk_with(fn, how, kv=kv):
        def walk(q, pools, table, layer, pos, n_valid):
            read_keys, blocks = decode._key_blocks(
                lay, table, attention.real_positions(pos, n_valid))
            return fn(q[0], lambda j, n: tuple(read_keys(p, layer, j)
                                               for p in pools),
                      pos, n_kv_heads=kv, scale=scale, window=window,
                      **how(n_valid), **blocks)[None]
        return walk

    def pair(q, *rest):
        """Route (b): head i's query in its own half of a 128-lane tile
        of two K/V heads, the other half zero; the tile's values come
        back for both halves and each head keeps its own."""
        half = (jnp.arange(h) // (h // kv)) % 2                 # [h]
        own = (jnp.arange(2 * hd)[None, :] // hd) == half[:, None]
        wide = jnp.where(own[:, None, :], jnp.tile(q[0], (1, 1, 2)), 0)
        o = walk_with(attention.head_window_attention,
                      lambda n: {"n_valid": n}, kv=kv // 2)(
            wide.astype(q.dtype)[None], *rest)[0].reshape(h, w, 2, hd)
        return jnp.where(half[:, None, None] == 0, o[:, :, 0], o[:, :, 1]
                         )[None]

    def fresh_walk():
        """(a new program: ``HEAD_TILE`` is read when it is traced)"""
        return over_layers(walk_with(attention.head_window_attention,
                                     lambda n: {"n_valid": n}))

    forms = {"walk": fresh_walk()}
    if not window:
        forms["packed"] = over_layers(packed)
    if hd == 64 and kv % 2 == 0 and not window:
        forms["pair"] = over_layers(pair)
    if parent:
        spec = importlib.util.spec_from_file_location(
            "parent_attention",
            os.path.join(parent, "ray_tpu", "ops", "attention.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        forms["parent"] = over_layers(walk_with(
            mod.head_window_attention, lambda n: {}))
    return forms, fresh_walk, (q, pools, table), T * bs


def timed(fn, args, reps: int) -> float:
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3 / LAYERS


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="lfm2", choices=sorted(SHAPES))
    ap.add_argument("--parent", default=None)
    ap.add_argument("--tiles", default=None,
                    help="score tiles to try, keys x queries: 512x256,..")
    ap.add_argument("--forms", default=None,
                    help="of walk,packed,pair,parent: all that apply")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.rehearse:
        print("no chip: a CPU time is no device number (--rehearse checks "
              "the paths at tiny shapes)", file=sys.stderr)
        return 2
    shape = SHAPES["tiny" if args.rehearse else args.shape]
    forms, fresh_walk, operands, span = build(shape, args.parent)
    if args.forms:
        forms = {k: forms[k] for k in ["walk"] + args.forms.split(",")
                 if k in forms}
    key_block = decode.window_key_block(shape[5])
    tiles = [tuple(map(int, t.split("x")))
             for t in (args.tiles.split(",") if args.tiles else [])]
    rows = []
    for start, n_valid in cases(shape[3], span, key_block):
        call = (*operands, jnp.int32(start), jnp.int32(n_valid))
        row = {"shape": args.shape, "device": jax.devices()[0].device_kind,
               "start": start, "n_valid": n_valid,
               "key_blocks": -(-(start + n_valid) // key_block)}
        want = np.asarray(forms["walk"](*call), np.float32)[..., :n_valid, :]
        for name, fn in forms.items():
            got = np.asarray(fn(*call), np.float32)[..., :n_valid, :]
            row[f"max_abs_diff_{name}"] = float(np.abs(got - want).max())
            if not args.rehearse:
                row[f"ms_a_layer_{name}"] = timed(fn, call, args.reps)
        for tile in tiles if not args.rehearse else []:
            held, attention.HEAD_TILE = attention.HEAD_TILE, tile
            try:
                row["ms_a_layer_walk_%dx%d" % tile] = timed(
                    fresh_walk(), call, args.reps)
            finally:
                attention.HEAD_TILE = held
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
