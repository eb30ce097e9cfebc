"""Hashes of the serving programs' lowered text (StableHLO, nothing
compiled) at every serving cell's shapes, from the cells' own
configuration files: run it in two checkouts and compare — the cheap
proof that a change reaches no program of a cell it should not reach
(PR 49 and PR 53 showed so; ROADMAP C18).

    python benchmarks/program_text.py [--root DIR] > hashes.json

``--root``: the checkout whose ``ray_tpu`` and ``chipbench`` are read
(default: this file's).  Runs on the CPU; no chip, no time."""

from __future__ import annotations

import argparse
import hashlib
import importlib
import inspect
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

CELLS = {"granite-4.0-h-small-10L-e36": "open_loop_http_recurrent",
         "nemotron-3-nano-30b-a3b-13L-e64": "open_loop_http_nemotron_h",
         "olmo-hybrid-7b-16L": "open_loop_http_olmo_hybrid",
         "trinity-large-preview-5L-e32": "open_loop_http_afmoe",
         "deepseek-v2-7L-e20": "open_loop_http_deepseek_v2",
         "lfm2-8b-a1b-12L": "open_loop_http_lfm2",
         "xing4.0-29b-a4b-6L": "open_loop_http_xing4"}


def digest(text: str) -> str:
    return f"{hashlib.sha256(text.encode()).hexdigest()[:16]} {len(text)}"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    root = os.path.abspath(ap.parse_args().root)
    sys.path.insert(0, root)
    os.chdir(root)
    import jax
    import jax.numpy as jnp
    from ray_tpu.inference import decode, recurrent
    from ray_tpu.inference.cache import BlockPool, PoolLayout
    from ray_tpu.models import gpt, hybrid

    def feed(fn, rows: int) -> tuple:
        """The token array, where the checkout's programs take one
        (since PR 55) before their packed array."""
        if "feed" not in inspect.signature(fn).parameters:
            return ()
        return (jax.ShapeDtypeStruct((rows,), jnp.int32),)

    out = {}
    for name, kind in CELLS.items():
        path = os.path.join("chipbench", "configs", f"{name}.json")
        if not os.path.exists(path):
            continue
        with open(path) as f:
            config = json.load(f)
        got = importlib.import_module(
            f"chipbench.traffic.{kind}").model_config(config)
        cfg = got[0] if isinstance(got, tuple) else got
        e = config["engine"]
        bs, C = e.get("kv_block_size", 16), e["prefill_chunk"]
        T = -(-e["max_seq"] // bs)
        params = jax.eval_shape(
            lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0)))
        wg = getattr(cfg, "window_geometry", None)
        span = (wg[3] if wg else 0) + C + bs

        def arrays():
            pool = BlockPool(
                cfg, e["n_blocks"], bs, max_seq=e["max_seq"],
                state_rows=e["max_slots"], window_span=span,
                n_window_blocks=e.get("n_window_blocks"))
            return pool.pools, (() if pool.state is None
                                else pool.state.arrays)
        pools, state = jax.eval_shape(arrays)
        TT = T * (2 if cfg.n_window else 1)
        kw = dict(block_size=bs, n_table=T)
        programs = {
            "chunk": (recurrent.make_recurrent_chunk_fn(cfg, chunk=C, **kw),
                      (TT + C + 3,)),
            "step": (recurrent.make_recurrent_decode_step(cfg, **kw),
                     (e["max_slots"], TT + 3))}
        fused = recurrent.has_step_chunk
        if (fused(cfg) if len(inspect.signature(fused).parameters) == 1
                else fused(cfg, PoolLayout.of(cfg, pools[0]))):
            programs["step_chunk"] = (
                recurrent.make_recurrent_step_chunk(cfg, chunk=C, **kw),
                (e["max_slots"] * (T + 3) + T + C + 3,))
        for which, (fn, shape) in programs.items():
            out[f"{name}/{which}"] = digest(fn.lower(
                params, pools, state, *feed(fn, e["max_slots"]),
                jax.ShapeDtypeStruct(shape, jnp.int32)).as_text())

    with open(os.path.join("chipbench", "configs", "gpt2-xl.json")) as f:
        xl = json.load(f)
    gcfg = gpt.GPTConfig(d_model=xl["n_embd"], n_heads=xl["n_head"],
                         n_layers=xl["n_layer"], d_ff=4 * xl["n_embd"])
    e = xl["engine"]
    bs, C = e.get("kv_block_size", 16), e.get("prefill_chunk", 32)
    T = -(-e.get("max_seq", gcfg.max_seq) // bs)
    params = jax.eval_shape(lambda: gpt.serving_params(
        gpt.init_params(gcfg, jax.random.PRNGKey(0)), gcfg))
    pool = jax.eval_shape(lambda: BlockPool(
        gcfg, e["n_blocks"], bs, max_seq=T * bs).k)
    kw = dict(block_size=bs, n_table=T)
    for which, fn, shape in (
            ("step", decode.make_paged_decode_step(gcfg, **kw),
             (e["max_slots"], T + 3)),
            ("chunk", decode.make_chunk_prefill_fn(gcfg, chunk=C, **kw),
             (T + C + 3,)),
            ("step_chunk", decode.make_paged_step_chunk(gcfg, chunk=C, **kw),
             (e["max_slots"] * (T + 3) + T + C + 3,))):
        out[f"gpt2-xl/{which}"] = digest(fn.lower(
            params, pool, pool, *feed(fn, e["max_slots"]),
            jax.ShapeDtypeStruct(shape, jnp.int32)).as_text())
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
