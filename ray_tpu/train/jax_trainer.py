"""JaxTrainer: declarative model+optimizer training (the framework-native
trainer — the reference's closest analogues are its framework trainers,
e.g. TorchTrainer wrapping DDP setup; here the "backend" is a sharded
compiled train step from train.step).

Give it a loss_fn, param init, optax optimizer, a batch iterator and a
mesh spec; it builds the sharded step, runs it, reports metrics, and
checkpoints periodically.  TP/PP/SP/FSDP are *config*, not code: they are
just different mesh axes + sharding rules on the same loss_fn.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Iterable, Optional

import jax
import jax.numpy as jnp
import optax

from ray_tpu.parallel.sharding import DEFAULT_LLM_RULES, Rules
from ray_tpu.train import session
from ray_tpu.train.config import RunConfig, ScalingConfig
from ray_tpu.train.step import TrainState, make_train_step, shard_batch
from ray_tpu.train.trainer import DataParallelTrainer
from ray_tpu.util import tracing


class JaxTrainer(DataParallelTrainer):
    def __init__(self, *, loss_fn: Callable,
                 init_params: Callable[[jax.Array], Any],
                 optimizer: optax.GradientTransformation,
                 train_data: Iterable,
                 num_steps: int,
                 params_logical: Any = None,
                 rules: Rules = DEFAULT_LLM_RULES,
                 eval_fn: Optional[Callable] = None,
                 eval_every: int = 0,
                 report_every: int = 10,
                 checkpoint_every: int = 0,
                 seed: int = 0,
                 scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 **kw):
        self._opts = dict(
            loss_fn=loss_fn, init_params=init_params, optimizer=optimizer,
            train_data=train_data, num_steps=num_steps,
            params_logical=params_logical, rules=rules, eval_fn=eval_fn,
            eval_every=eval_every, report_every=report_every,
            checkpoint_every=checkpoint_every, seed=seed)
        super().__init__(self._train_loop, scaling_config=scaling_config,
                         run_config=run_config, **kw)

    def _train_loop(self, _cfg):
        o = self._opts
        mesh = self.gang.mesh
        loss_fn = o["loss_fn"]
        # model loss_fns that take mesh/rules get them bound here
        try:
            import inspect
            sig = inspect.signature(loss_fn)
            if "mesh" in sig.parameters:
                import functools
                loss_fn = functools.partial(loss_fn, mesh=mesh,
                                            rules=o["rules"])
        except (ValueError, TypeError):
            pass

        init_fn, step_fn = make_train_step(
            loss_fn, o["optimizer"], mesh=mesh,
            params_logical=o["params_logical"], rules=o["rules"])

        restored = session.get_checkpoint()
        params = o["init_params"](jax.random.PRNGKey(o["seed"]))
        state = init_fn(params)
        start_step = 0
        if restored is not None:
            payload = restored.to_dict()
            start_step = int(payload.get("step", 0))

            def put_like(cur, host):
                if isinstance(cur, jax.Array):
                    return jax.device_put(host, cur.sharding)
                return host

            # full-state restore: params AND optimizer moments AND step —
            # re-initializing the optimizer would spike the effective LR
            # after every failover (adam bias correction restarts)
            state = TrainState(
                step=put_like(state.step,
                              jnp.asarray(start_step, jnp.int32)),
                params=jax.tree.map(put_like, state.params,
                                    payload["params"]),
                opt_state=(jax.tree.map(put_like, state.opt_state,
                                        payload["opt_state"])
                           if "opt_state" in payload else state.opt_state))

        data_iter = iter(o["train_data"])
        # replay the iterator to the resume point so deterministic feeds
        # don't re-consume the leading batches
        for _ in range(start_step):
            next(data_iter)
        t0 = time.perf_counter()
        tokens_done = 0
        # spans (recorded while tracing is on or a profiler session
        # runs): siblings that carry the step number; train.step, the
        # profiler's step marker, covers the dispatch only, train.fetch
        # (inside train.report) the wait for the device
        for i in range(start_step, o["num_steps"]):
            with tracing.span("train.next_batch", step=i):
                batch = next(data_iter)
            with tracing.span("train.shard_batch", step=i):
                batch = shard_batch(batch, mesh)
            with tracing.span("train.step", step_num=i):
                state, metrics = step_fn(state, batch)
            leaf = jax.tree.leaves(batch)[0]
            tokens_done += int(leaf.shape[0]) * (
                int(leaf.shape[1]) if leaf.ndim > 1 else 1)

            is_last = i + 1 == o["num_steps"]
            if (i + 1) % o["report_every"] == 0 or is_last:
                with tracing.span("train.report", step=i):
                    # the float() waits for every step dispatched so far
                    with tracing.span("train.fetch", step=i):
                        m = {k: float(v) for k, v in metrics.items()}
                    dt = time.perf_counter() - t0
                    m.update(step=i + 1,
                             throughput=tokens_done / max(dt, 1e-9))
                    if (o["eval_fn"] is not None and o["eval_every"]
                            and (i + 1) % o["eval_every"] == 0):
                        m["eval"] = float(o["eval_fn"](state.params))
                    ckpt = None
                    if (o["checkpoint_every"]
                            and (i + 1) % o["checkpoint_every"] == 0) \
                            or is_last:
                        ckpt = {"params": state.params,
                                "opt_state": state.opt_state,
                                "step": i + 1}
                    # a report that saves is train.checkpoint as well
                    with (tracing.span("train.checkpoint", step=i)
                          if ckpt else tracing.NOOP) as sp:
                        if sp:
                            sp.set(bytes=sum(
                                x.nbytes for x in jax.tree.leaves(ckpt)
                                if hasattr(x, "nbytes")))
                        session.report(m, checkpoint=ckpt)
        self.final_state = state
