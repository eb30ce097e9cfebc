"""Traffic kind ``train_steps``: JaxTrainer fed a fresh seeded batch a step.

The trainer is not edited and not bypassed.  A first short ``fit``
(``warm_steps`` steps, a loss fetched every step) compiles or loads
every program, gives the step time, and its first reported loss is
checked against the plain reference on the same seeded parameters and
batch.  A second ``fit`` is the measured one: ``report_every = k`` makes
the trainer fetch the loss (a concrete host fetch, so the device has
finished) every k steps, and the benchmark's batch iterator stamps the
host clock at every ``next()``.  The stamps that directly follow a
fetch bound whole groups of k finished steps; tokens/s is all the
tokens of the steps between the first and the last of those stamps over
the time between them.  The last step's checkpoint (the trainer writes
one unconditionally) falls after the last stamp.

``notes.setup_stamps`` splits ``setup_s`` into ``import_s`` (process
start to this kind's imports done), ``warm_fit_s`` (the first ``fit``:
weights, every program compiled or loaded, ``warm_steps`` steps and the
checkpoint the trainer writes on the last of them), ``reference_check_s``
and ``measured_fit_start_s`` (the second ``fit`` up to the stamp that
opens the window: trainer built, weights made again, the first k steps
and their fetch), with the compile-cache hits and misses after the warm
fit and at the window's start.
"""

from __future__ import annotations

import gc
import math
import shutil
import tempfile
import time


class Feed:
    """Seeded batches; stamps the host clock and the compile counters
    at every ``next()``; runs ``hooks[i]()`` before handing out batch i."""

    def __init__(self, seed: int, stream: int, vocab: int, batch: int,
                 width: int, hooks=None):
        import numpy as np

        from ray_tpu._compile_cache import compile_cache_stats
        self._rng = np.random.default_rng([int(seed), int(stream)])
        self._shape, self._vocab = (batch, width), vocab
        self._np, self._compile_stats = np, compile_cache_stats
        self.hooks = hooks or {}
        self.stamps, self.compiles = [], []
        self.hook_s = 0.0            # time spent in hooks (the profiler)
        self.first = None

    def __iter__(self):
        return self

    def __next__(self):
        i = len(self.stamps)
        if i in self.hooks:
            t = time.monotonic()
            self.hooks[i]()
            self.hook_s += time.monotonic() - t
        st = self._compile_stats()
        self.compiles.append(st["hits"] + st["misses"])
        self.stamps.append(time.monotonic())
        batch = {"tokens": self._rng.integers(
            0, self._vocab, self._shape, dtype=self._np.int32)}
        if self.first is None:
            self.first = batch
        return batch


def fit(cfg, feed: Feed, num_steps: int, report_every: int, seed31: int,
        trainer_cfg: dict, run_dir: str, name: str) -> list:
    """One ``JaxTrainer.fit`` as a user writes it; returns the reported
    losses."""
    import optax

    from chipbench.model import jitted_init
    from ray_tpu.models import gpt
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    opt = trainer_cfg["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError(f"optimizer {opt['name']!r}: only adamw is wired")
    trainer = JaxTrainer(
        loss_fn=lambda p, b, mesh=None, rules=None: gpt.loss_fn(
            p, b, cfg, mesh=mesh, rules=rules),
        init_params=jitted_init(cfg),
        optimizer=optax.adamw(opt["learning_rate"],
                              weight_decay=opt["weight_decay"]),
        train_data=feed, num_steps=num_steps,
        params_logical=gpt.param_logical_axes(cfg),
        report_every=report_every, seed=seed31,
        scaling_config=ScalingConfig(mesh=dict(trainer_cfg["mesh"])),
        run_config=RunConfig(name=name, storage_path=run_dir))
    result = trainer.fit()
    losses = [float(m["loss"]) for m in result.metrics_history]
    del trainer, result
    gc.collect()
    return losses


def reference_loss(cfg, config: dict, seed31: int, tokens, rows: int,
                   rows_per_call: int) -> float:
    """The plain reference's loss on the first ``rows`` rows, in calls
    of ``rows_per_call`` (its float32 logits are 200 MB a row)."""
    import jax.numpy as jnp

    from chipbench.model import make_params
    from chipbench.reference import gpt2 as ref

    params = make_params(cfg, seed31)
    total = 0.0
    for lo in range(0, rows, rows_per_call):
        part = tokens[lo:min(rows, lo + rows_per_call)]
        total += len(part) * float(ref.loss(params, jnp.asarray(part),
                                            config["n_head"]))
    del params
    gc.collect()
    return total / rows


def run(ctx) -> dict:
    import jax

    from ray_tpu._compile_cache import compile_cache_stats

    from chipbench import flops, stats, trace_reduce
    from chipbench.model import device_memory_peak, fold_seed, gpt_config

    mix, config = ctx.mix, ctx.config
    cfg = gpt_config(config)
    B, S, k = mix["batch"], mix["seq"], mix["report_every"]
    seed31 = fold_seed(ctx.seed, 0)
    run_dir = tempfile.mkdtemp(prefix="chipbench_train_")
    trainer_cfg = config["trainer"]
    stamps = stats.Stamps(ctx.t_start)
    stamps.mark("import_s")
    try:
        # ---- set-up: warm every program, step time, reference check
        warm = Feed(ctx.seed, 1, cfg.vocab_size, B, S + 1)
        warm_losses = fit(cfg, warm, mix["warm_steps"], 1, seed31,
                          trainer_cfg, run_dir, "warm")
        stamps.mark("warm_fit_s")
        stamps.cache("after_warm_fit", compile_cache_stats())
        # the first step compiles or loads: leave it out
        step_s = min(stats.gaps(warm.stamps[1:]))
        ctx.log(f"warm fit: losses {warm_losses}, fastest step {step_s:.4f}s")
        rows = min(mix["loss_rows_checked"], B)
        ref_loss = reference_loss(cfg, config, seed31, warm.first["tokens"],
                                  rows, mix["loss_rows_per_call"])
        loss_diff = abs(warm_losses[0] - ref_loss)
        stamps.mark("reference_check_s")
        ctx.log(f"first loss {warm_losses[0]:.5f} vs reference "
                f"{ref_loss:.5f} on {rows} rows (diff {loss_diff:.5f})")

        # ---- the measured fit
        n_groups = max(2, math.ceil(ctx.seconds / (k * step_s)))
        num_steps = k * (n_groups + 1) + 1
        trace = {}
        hooks = {}
        if ctx.trace:
            lo = k * (1 + n_groups // 2)
            hi = lo + k * mix["trace_intervals"]
            trace["dir"] = tempfile.mkdtemp(prefix="chipbench_trace_")

            # both hooks run right after a loss fetch (the device has
            # finished); starting and stopping the profiler take
            # seconds and stay outside the span
            def start():
                jax.profiler.start_trace(trace["dir"])
                trace["t0"] = time.monotonic()

            def stop():
                trace["t1"] = time.monotonic()
                jax.profiler.stop_trace()
            hooks = {lo: start, hi: stop}
            trace["steps"] = hi - lo
        feed = Feed(ctx.seed, 2, cfg.vocab_size, B, S + 1, hooks=hooks)
        losses = fit(cfg, feed, num_steps, k, seed31, trainer_cfg, run_dir,
                     "measured")
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)

    marks = list(range(k, len(feed.stamps), k))   # stamps after a fetch
    first, last = marks[0], marks[-1]
    window_s = feed.stamps[last] - feed.stamps[first]
    steps = last - first
    tokens_per_s = steps * B * S / window_s
    compiles_in_window = feed.compiles[last] - feed.compiles[first]
    stamps.mark("measured_fit_start_s", at=feed.stamps[first])
    memory_peak = device_memory_peak(jax.devices())
    finite = all(math.isfinite(x) for x in losses + warm_losses)
    checks = {
        "loss_diff": {"value": loss_diff, "limit": mix["loss_tolerance"]},
        "compiles_in_window": {"value": compiles_in_window, "limit": 0},
        "losses_not_finite": {"value": sum(
            not math.isfinite(x) for x in losses + warm_losses), "limit": 0},
        "loss_fetches": {"value": len(losses), "at_least": n_groups},
    }
    correct = (finite and loss_diff <= mix["loss_tolerance"]
               and compiles_in_window == 0 and len(losses) >= n_groups)
    ctx.log(f"{steps} steps in {window_s:.3f}s = {tokens_per_s:.1f} "
            f"tokens/s; compiles in window {compiles_in_window}")

    # the wall time of a step by the untraced definition: starting and
    # stopping the profiler stalls the loop for seconds, taken out here
    obs = {"window_s": window_s, "steps": steps,
           "step_wall_ms": 1e3 * (window_s - feed.hook_s) / steps}
    if ctx.trace:
        rows_ = trace_reduce.load_events(
            trace_reduce.find_xplane(trace["dir"]))
        shutil.rmtree(trace["dir"], ignore_errors=True)
        obs["trace"] = trace_reduce.summarize(
            rows_, window_s=trace["t1"] - trace["t0"])
        obs["trace_steps"] = trace["steps"]
    return {
        "correct": correct, "attempted": steps, "failed": 0,
        "setup_s": feed.stamps[first] - ctx.t_start,
        "end_to_end": {"train_tokens_per_s": tokens_per_s},
        "obs": obs,
        "notes": {"first_loss": warm_losses[0], "reference_loss": ref_loss,
                  "loss_diff": loss_diff,
                  "loss_tolerance": mix["loss_tolerance"],
                  "last_loss": losses[-1], "steps_in_window": steps,
                  "window_s": window_s,
                  "compiles_in_window": compiles_in_window,
                  "warm_step_s": step_s,
                  "flops_per_token": flops.train_flops_per_token(
                      flops.gpt2_param_count(config), config, S),
                  "setup_stamps": {
                      **stamps.notes,
                      "cache_requests_at_window_start": feed.compiles[first]}},
        "checks": checks, "memory_peak_bytes": memory_peak,
    }
