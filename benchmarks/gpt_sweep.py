"""GPT-2 bench lever sweep → evidence for PERF.md.

Runs the same honest-timing loop as bench.py across a grid of levers
(remat policy, sequence length, batch, optimizer-state dtype) and
prints one JSON line per configuration.  Used to prove (or break) the
box's MFU ceiling with committed numbers rather than journal claims.

Runs on the TPU chip only (off-chip, or on a chip with no published
peak in ray_tpu.util.accelerators.CHIP_PEAKS, it exits with an error):
    python benchmarks/gpt_sweep.py [--steps 20]
A configuration that does not fit the chip's HBM is recorded as such
(RESOURCE_EXHAUSTED) and the sweep goes on; any other failure raises.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_one(name: str, *, batch: int, seq: int, remat, remat_policy,
            mu_dtype: str, steps: int, warmup: int,
            block_q: int = 512, block_k: int = 512) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models import gpt
    from ray_tpu.train.step import make_train_step
    from ray_tpu.util.accelerators import chip_peaks

    peak, _peak_hbm = chip_peaks(jax.devices()[0].device_kind)
    cfg = gpt.GPTConfig.gpt2_124m(max_seq=seq, remat=remat,
                                  remat_policy=remat_policy,
                                  attn_block_q=block_q,
                                  attn_block_k=block_k)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    n_params = int(sum(np.prod(p.shape)
                       for p in jax.tree_util.tree_leaves(params)))

    def loss(p, b):
        return gpt.loss_fn(p, b, cfg)

    mu = {"f32": None, "bf16": jnp.bfloat16}[mu_dtype]
    tx = optax.adamw(3e-4, weight_decay=0.1,
                     **({"mu_dtype": mu} if mu is not None else {}))
    init_fn, step_fn = make_train_step(loss, tx, mesh=None)
    state = init_fn(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq + 1),
                                0, cfg.vocab_size, dtype=jnp.int32)
    b = {"tokens": tokens}

    t0 = time.perf_counter()
    try:
        for _ in range(warmup):
            state, metrics = step_fn(state, b)
        float(np.asarray(metrics["loss"]))
    except jax.errors.JaxRuntimeError as e:
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        # does not fit this chip's HBM: a sweep result, not a fault
        return {"config": name, "error": "RESOURCE_EXHAUSTED: "
                                         + str(e)[:160]}
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step_fn(state, b)
    last = float(np.asarray(metrics["loss"]))
    dt = time.perf_counter() - t0

    # strict per-step host sync pass: bounds dispatch-overlap effects
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step_fn(state, b)
        float(np.asarray(metrics["loss"]))
    dt_sync = time.perf_counter() - t0

    flops_per_token = 6 * n_params + 12 * cfg.n_layers * cfg.d_model * seq
    tps = batch * seq * steps / dt
    return {"config": name, "batch": batch, "seq": seq,
            "remat": remat, "remat_policy": remat_policy,
            "mu_dtype": mu_dtype,
            "tokens_per_s": round(tps, 1),
            "tokens_per_s_strict": round(batch * seq * steps / dt_sync, 1),
            "step_ms": round(1000 * dt / steps, 1),
            "step_ms_strict": round(1000 * dt_sync / steps, 1),
            "mfu": round(flops_per_token * tps / peak, 4),
            "compile_s": round(compile_s, 1),
            "final_loss": round(last, 3)}


GRID = [
    ("base_b16_s1024_dots", dict(batch=16, seq=1024, remat=True,
                                 remat_policy="dots", mu_dtype="f32")),
    ("bf16_moments", dict(batch=16, seq=1024, remat=True,
                          remat_policy="dots", mu_dtype="bf16")),
    ("seq512_b32", dict(batch=32, seq=512, remat=True,
                        remat_policy="dots", mu_dtype="f32")),
    ("seq512_b16", dict(batch=16, seq=512, remat=True,
                        remat_policy="dots", mu_dtype="f32")),
    ("no_remat_b16", dict(batch=16, seq=1024, remat=False,
                          remat_policy="dots", mu_dtype="f32")),
    ("full_remat_b16", dict(batch=16, seq=1024, remat=True,
                            remat_policy=None, mu_dtype="f32")),
    ("b24_dots", dict(batch=24, seq=1024, remat=True,
                      remat_policy="dots", mu_dtype="f32")),
    ("bf16_moments_b24", dict(batch=24, seq=1024, remat=True,
                              remat_policy="dots", mu_dtype="bf16")),
    # round-5: saved flash out/lse (backward skips the fwd kernel)
    ("dots_flash_b16", dict(batch=16, seq=1024, remat=True,
                            remat_policy="dots_flash", mu_dtype="f32")),
    ("dots_flash_b24", dict(batch=24, seq=1024, remat=True,
                            remat_policy="dots_flash", mu_dtype="f32")),
    ("dots_flash_b32", dict(batch=32, seq=1024, remat=True,
                            remat_policy="dots_flash", mu_dtype="f32")),
    ("b32_dots", dict(batch=32, seq=1024, remat=True,
                      remat_policy="dots", mu_dtype="f32")),
    # round-5: pallas tile-size sweep (fwd + both bwd kernels)
    ("dots_flash_bq256", dict(batch=16, seq=1024, remat=True,
                              remat_policy="dots_flash", mu_dtype="f32",
                              block_q=256, block_k=512)),
    ("dots_flash_bk256", dict(batch=16, seq=1024, remat=True,
                              remat_policy="dots_flash", mu_dtype="f32",
                              block_q=512, block_k=256)),
    ("dots_flash_bq1024", dict(batch=16, seq=1024, remat=True,
                               remat_policy="dots_flash", mu_dtype="f32",
                               block_q=1024, block_k=512)),
    ("dots_flash_b256x256", dict(batch=16, seq=1024, remat=True,
                                 remat_policy="dots_flash", mu_dtype="f32",
                                 block_q=256, block_k=256)),
]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--only", default=None,
                    help="comma-separated config names")
    args = ap.parse_args()
    names = set(args.only.split(",")) if args.only else None

    import jax

    from ray_tpu._compile_cache import enable_compile_cache
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"gpt_sweep measures the TPU; jax found platform "
            f"{dev.platform!r} ({dev.device_kind})")
    enable_compile_cache()
    for name, kw in GRID:
        if names and name not in names:
            continue
        out = run_one(name, steps=args.steps, warmup=args.warmup, **kw)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
