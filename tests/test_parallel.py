"""Mesh/sharding/collectives tests on the virtual 8-device CPU mesh
(analogue of the reference's multi-node-in-one-machine fixtures)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ray_tpu.parallel import (create_mesh, mesh_shape, spec_for,
                              DEFAULT_LLM_RULES, collectives as col)


@pytest.fixture(scope="module")
def devices():
    d = jax.devices("cpu")
    assert len(d) >= 8, "conftest must force 8 CPU devices"
    return d


def test_mesh_creation(devices):
    mesh = create_mesh({"dp": 2, "tp": 4}, devices=devices[:8])
    assert mesh_shape(mesh) == {"dp": 2, "tp": 4}


def test_mesh_fill_axis(devices):
    mesh = create_mesh({"dp": -1, "tp": 2}, devices=devices[:8])
    assert mesh_shape(mesh) == {"dp": 4, "tp": 2}


def test_mesh_invalid_shape(devices):
    with pytest.raises(ValueError):
        create_mesh({"dp": 3, "tp": 3}, devices=devices[:8])


def test_spec_for_rules(devices):
    mesh = create_mesh({"dp": 2, "tp": 4}, devices=devices[:8])
    spec = spec_for(("batch", "seq", "embed"), DEFAULT_LLM_RULES, mesh)
    assert spec == PartitionSpec("dp", None, None)
    spec = spec_for(("embed", "mlp"), DEFAULT_LLM_RULES, mesh)
    assert spec == PartitionSpec(None, "tp")


def test_spec_no_duplicate_axes(devices):
    mesh = create_mesh({"dp": 2, "tp": 4}, devices=devices[:8])
    # heads and qkv both map to tp — tp may be used only once
    spec = spec_for(("heads", "qkv"), DEFAULT_LLM_RULES, mesh)
    used = [a for a in spec if a is not None]
    assert len(used) <= 1


def test_compiled_allreduce(devices):
    mesh = create_mesh({"dp": 8}, devices=devices[:8])

    @jax.jit
    def f(x):
        def inner(x):
            return col.allreduce(x, "dp")
        from jax import shard_map
        return shard_map(inner, mesh=mesh, in_specs=PartitionSpec("dp"),
                         out_specs=PartitionSpec("dp"))(x)

    x = jnp.arange(8.0)
    out = f(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 28.0))


def test_compiled_allgather_and_scatter(devices):
    mesh = create_mesh({"dp": 8}, devices=devices[:8])
    from jax import shard_map

    @jax.jit
    def gather(x):
        return shard_map(lambda v: col.allgather(v, "dp"),
                         mesh=mesh, in_specs=PartitionSpec("dp"),
                         out_specs=PartitionSpec(None), check_vma=False)(x)

    x = jnp.arange(8.0)
    np.testing.assert_allclose(np.asarray(gather(x)), np.arange(8.0))

    @jax.jit
    def rs(x):
        return shard_map(lambda v: col.reducescatter(v, "dp"),
                         mesh=mesh, in_specs=PartitionSpec(None),
                         out_specs=PartitionSpec("dp"), check_vma=False)(x)

    out = rs(jnp.ones(8))
    np.testing.assert_allclose(np.asarray(out), np.full(8, 8.0))


def test_compiled_broadcast_and_permute(devices):
    mesh = create_mesh({"dp": 8}, devices=devices[:8])
    from jax import shard_map

    @jax.jit
    def bc(x):
        return shard_map(lambda v: col.broadcast(v, "dp", root=3),
                         mesh=mesh, in_specs=PartitionSpec("dp"),
                         out_specs=PartitionSpec("dp"))(x)

    out = np.asarray(bc(jnp.arange(8.0)))
    np.testing.assert_allclose(out, np.full(8, 3.0))

    @jax.jit
    def shift(x):
        return shard_map(
            lambda v: col.permute(v, "dp", col.ring_perm(8, 1)),
            mesh=mesh, in_specs=PartitionSpec("dp"),
            out_specs=PartitionSpec("dp"))(x)

    out = np.asarray(shift(jnp.arange(8.0)))
    np.testing.assert_allclose(out, np.roll(np.arange(8.0), 1))


def test_gang_single_host(devices):
    from ray_tpu.parallel import form_gang
    gang = form_gang({"dp": 2, "tp": 4}, use_cpu_devices=True)
    assert gang.num_devices == 8
    assert gang.axis_sizes == {"dp": 2, "tp": 4}

    batch = {"x": np.ones((8, 4), np.float32)}
    sharded = gang.put_batch(batch)
    assert sharded["x"].shape == (8, 4)

    def train_like(b):
        return jnp.sum(b["x"])

    assert float(gang.run(train_like, sharded)) == 32.0


def test_host_plane_collectives_between_actors():
    """Out-of-band CPU collectives between actor processes (the Gloo
    analogue; reference: python/ray/util/collective/tests)."""
    import ray_tpu
    ray_tpu.init(num_cpus=2, num_tpus=0)
    try:
        @ray_tpu.remote
        class Member:
            def __init__(self, rank, world):
                from ray_tpu.parallel.collectives import CollectiveGroup
                self.g = CollectiveGroup("grp", world, rank)
                self.rank = rank

            def do_allreduce(self):
                return self.g.allreduce(np.full(3, float(self.rank + 1)))

            def do_bcast(self):
                return self.g.broadcast(
                    np.arange(4.0) if self.rank == 0 else None, root=0)

            def do_gather(self):
                return self.g.allgather(np.array([self.rank]))

        world = 2
        members = [Member.remote(r, world) for r in range(world)]
        outs = ray_tpu.get([m.do_allreduce.remote() for m in members],
                           timeout=120)
        for o in outs:
            np.testing.assert_allclose(o, np.full(3, 3.0))
        outs = ray_tpu.get([m.do_bcast.remote() for m in members],
                           timeout=120)
        for o in outs:
            np.testing.assert_allclose(o, np.arange(4.0))
        outs = ray_tpu.get([m.do_gather.remote() for m in members],
                           timeout=120)
        for o in outs:
            np.testing.assert_allclose(np.concatenate(o), [0, 1])
    finally:
        ray_tpu.shutdown()


# -- pipeline parallelism ---------------------------------------------------

def _pp_loss(mesh, cfg, params, tokens):
    from ray_tpu.models import gpt
    from ray_tpu.train.step import shard_batch
    with mesh:
        batch = shard_batch({"tokens": tokens}, mesh)
        return float(jax.jit(
            lambda p, b: gpt.loss_fn(p, b, cfg, mesh=mesh,
                                     rules=DEFAULT_LLM_RULES))(params, batch))


def test_pipeline_forward_matches_single_device(devices):
    """pp=2 GPipe pipeline: loss parity with the unpipelined model."""
    from ray_tpu.models import gpt
    cfg = gpt.GPTConfig(vocab_size=256, max_seq=32, d_model=32, n_heads=2,
                        n_layers=4, d_ff=64, remat=False,
                        dtype=jnp.float32, pp_microbatches=4)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0, 256,
                                dtype=jnp.int32)
    ref = float(gpt.loss_fn(params, {"tokens": tokens}, cfg))

    mesh = create_mesh({"pp": 2}, devices=jax.devices("cpu")[:2])
    got = _pp_loss(mesh, cfg, params, tokens)
    assert abs(got - ref) < 1e-4, (got, ref)


def test_pipeline_composes_with_dp_tp(devices):
    """pp2 x dp2 x tp2 over 8 devices, gradients flow through the
    pipeline (one real optimizer step changes the loss)."""
    import optax
    from ray_tpu.models import gpt
    from ray_tpu.train.step import make_train_step, shard_batch
    cfg = gpt.GPTConfig(vocab_size=256, max_seq=32, d_model=32, n_heads=2,
                        n_layers=4, d_ff=64, remat=True,
                        dtype=jnp.float32, pp_microbatches=4)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0, 256,
                                dtype=jnp.int32)
    ref = float(gpt.loss_fn(params, {"tokens": tokens}, cfg))

    mesh = create_mesh({"pp": 2, "dp": 2, "tp": 2},
                       devices=jax.devices("cpu")[:8])
    init_fn, step_fn = make_train_step(
        lambda p, b: gpt.loss_fn(p, b, cfg, mesh=mesh,
                                 rules=DEFAULT_LLM_RULES),
        optax.adamw(1e-2), mesh=mesh,
        params_logical=gpt.param_logical_axes(cfg),
        rules=DEFAULT_LLM_RULES)
    with mesh:
        state = init_fn(params)
        batch = shard_batch({"tokens": tokens}, mesh)
        state, m1 = step_fn(state, batch)
        loss1 = float(m1["loss"])
        state, m2 = step_fn(state, batch)
        loss2 = float(m2["loss"])
    assert abs(loss1 - ref) < 1e-4, (loss1, ref)  # step-0 fwd parity
    assert loss2 < loss1  # the optimizer step actually descended


def test_pipeline_layer_sharding_rule(devices):
    """'layers' logical axis maps to pp, so stage param blocks live on
    their stage's devices."""
    mesh = create_mesh({"pp": 2, "dp": 2}, devices=jax.devices("cpu")[:4])
    spec = spec_for(("layers", "embed", "mlp"), DEFAULT_LLM_RULES, mesh)
    assert spec == PartitionSpec("pp", None, None)


def test_pipeline_bert_parity(devices):
    """BERT rides the same generic pipeline runner: pp2 parity."""
    from ray_tpu.models import bert
    cfg = bert.BERTConfig.tiny(n_layers=2, pp_microbatches=2)
    params = bert.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    ref = np.asarray(bert.encode(params, tokens, cfg))

    mesh = create_mesh({"pp": 2}, devices=jax.devices("cpu")[:2])
    with mesh:
        got = np.asarray(jax.jit(
            lambda p, t: bert.encode(p, t, cfg, mesh=mesh,
                                     rules=DEFAULT_LLM_RULES))(params, tokens))
    np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)


# -- mixture of experts / expert parallelism --------------------------------

def test_moe_forward_and_aux(devices):
    """MoE forward runs, aux loss is positive and ~1 when balanced."""
    from ray_tpu.models import gpt
    cfg = gpt.GPTConfig.tiny_moe()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    logits, aux = gpt.forward(params, tokens[:, :-1], cfg, return_aux=True)
    assert logits.shape == (2, 32, cfg.vocab_size)
    # aux = n_layers * E * sum(f_e * P_e) >= n_layers (Cauchy-Schwarz
    # bound: minimized at 1 per layer when perfectly balanced)
    assert float(aux) >= cfg.n_layers * 0.99


def test_moe_ep_mesh_parity(devices):
    """dp2 x ep2: sharding experts over ep reproduces the single-device
    loss exactly (the dispatch einsum becomes the all-to-all)."""
    from ray_tpu.models import gpt
    cfg = gpt.GPTConfig.tiny_moe()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    ref = float(gpt.loss_fn(params, {"tokens": tokens}, cfg))

    from ray_tpu.train.step import shard_batch
    mesh = create_mesh({"dp": 2, "ep": 2}, devices=jax.devices("cpu")[:4])
    with mesh:
        batch = shard_batch({"tokens": tokens}, mesh)
        got = float(jax.jit(
            lambda p, b: gpt.loss_fn(p, b, cfg, mesh=mesh,
                                     rules=DEFAULT_LLM_RULES))(params, batch))
    assert abs(got - ref) < 1e-4, (got, ref)


def test_moe_training_descends(devices):
    """Convergence smoke: tiny MoE GPT memorizes a fixed batch."""
    import optax
    from ray_tpu.models import gpt
    cfg = gpt.GPTConfig.tiny_moe()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    tx = optax.adamw(3e-3)
    opt = tx.init(params)
    step = jax.jit(lambda p, o, b: _sgd_step(p, o, b, cfg, tx))
    losses = []
    for _ in range(15):
        params, opt, l = step(params, opt, {"tokens": tokens})
        losses.append(float(l))
    assert losses[-1] < losses[0] - 0.5, losses


def _sgd_step(params, opt, batch, cfg, tx):
    from ray_tpu.models import gpt
    l, g = jax.value_and_grad(
        lambda p: gpt.loss_fn(p, batch, cfg))(params)
    updates, opt = tx.update(g, opt, params)
    import optax
    return optax.apply_updates(params, updates), opt, l


def test_moe_capacity_drops_tokens(devices):
    """capacity_factor < 1 forces drops: output differs from cf=4 run
    but remains finite (dropped tokens pass through the residual)."""
    from ray_tpu.models import gpt
    base = dict(vocab_size=128, max_seq=32, d_model=32, n_heads=2,
                n_layers=1, d_ff=64, remat=False, dtype=jnp.float32,
                n_experts=4, expert_top_k=1)
    cfg_tight = gpt.GPTConfig(**base, capacity_factor=0.25)
    cfg_loose = gpt.GPTConfig(**base, capacity_factor=4.0)
    params = gpt.init_params(cfg_tight, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 128,
                                dtype=jnp.int32)
    lo_t = gpt.forward(params, tokens, cfg_tight)
    lo_l = gpt.forward(params, tokens, cfg_loose)
    assert bool(jnp.all(jnp.isfinite(lo_t)))
    assert not np.allclose(np.asarray(lo_t), np.asarray(lo_l))


def test_moe_pp_composition(devices):
    """MoE + pipeline: the expert load-balance aux loss rides the
    ppermute hand-off (summed at the last stage) — loss parity with the
    unpipelined MoE model (round-5 composition off the rejected list)."""
    from ray_tpu.models import gpt
    cfg = gpt.GPTConfig(vocab_size=256, max_seq=32, d_model=32, n_heads=2,
                        n_layers=4, d_ff=64, remat=False,
                        dtype=jnp.float32, pp_microbatches=4,
                        n_experts=4, expert_top_k=2)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0, 256,
                                dtype=jnp.int32)
    ref = float(gpt.loss_fn(params, {"tokens": tokens}, cfg))
    mesh = create_mesh({"pp": 2, "ep": 2}, devices=jax.devices("cpu")[:4])
    got = _pp_loss(mesh, cfg, params, tokens)
    assert abs(got - ref) < 5e-4, (got, ref)


def test_1f1b_schedule_tick_optimal_and_safe():
    """The simulated 1F1B table is tick-optimal (2(M+S-1)) and
    dependency-safe for a spread of shapes."""
    from ray_tpu.parallel.pipeline_1f1b import build_1f1b_schedule
    for S, M in ((2, 2), (2, 4), (4, 4), (4, 8), (3, 7)):
        sched = build_1f1b_schedule(S, M)
        T = sched.do_f.shape[0]
        assert T == 2 * (M + S - 1), (S, M, T)
        # every stage runs exactly M forwards and M backwards
        assert sched.do_f.sum(axis=0).tolist() == [M] * S
        assert sched.do_b.sum(axis=0).tolist() == [M] * S


def test_1f1b_value_and_grads_parity(devices):
    """Fused 1F1B loss AND gradients match plain autodiff over the
    composed model (the schedule jax.grad cannot express)."""
    import numpy as np
    from jax import lax
    from ray_tpu.parallel.pipeline_1f1b import pipeline_value_and_grads_1f1b

    S, M, L, D, MB = 4, 8, 8, 16, 4
    rng = np.random.RandomState(0)
    layers = {"w": jnp.asarray(rng.randn(L, D, D) * 0.1, jnp.float32),
              "b": jnp.zeros((L, D), jnp.float32)}
    tail = {"wo": jnp.asarray(rng.randn(D, 7) * 0.1, jnp.float32)}
    x_mb = jnp.asarray(rng.randn(M, MB, D), jnp.float32)
    y_mb = jnp.asarray(rng.randint(0, 7, (M, MB)), jnp.int32)

    def stage_fn(lp, x):
        return lax.scan(
            lambda c, p: (c + jnp.tanh(c @ p["w"] + p["b"]), None),
            x, lp)[0]

    def last_fn(tp, x, y):
        logits = x @ tp["wo"]
        logz = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, y[:, None], 1)[:, 0]
        return jnp.mean(logz - gold)

    def ref_loss(layers, tail, x_mb):
        return jax.vmap(lambda x, y: last_fn(tail, stage_fn(layers, x),
                                             y))(x_mb, y_mb).mean()

    ref_l, (ref_dL, ref_dT, ref_dX) = jax.value_and_grad(
        ref_loss, argnums=(0, 1, 2))(layers, tail, x_mb)

    mesh = create_mesh({"pp": S}, devices=jax.devices("cpu")[:S])
    loss, dP, dT, dX = jax.jit(lambda *a: pipeline_value_and_grads_1f1b(
        stage_fn, last_fn, *a, mesh=mesh))(x_mb, y_mb, layers, tail)
    assert abs(float(ref_l) - float(loss)) < 1e-5
    for k in layers:
        np.testing.assert_allclose(np.asarray(dP[k]),
                                   np.asarray(ref_dL[k]),
                                   rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dT["wo"]),
                               np.asarray(ref_dT["wo"]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dX), np.asarray(ref_dX),
                               rtol=1e-4, atol=1e-5)


def test_1f1b_gpt_train_step(devices):
    """Full GPT through the fused 1F1B schedule: loss parity + gradient
    flow to every parameter (train/step.py train_step_1f1b asserts)."""
    from ray_tpu.models import gpt
    from ray_tpu.train.step import train_step_1f1b
    mesh = create_mesh({"pp": 4, "dp": 2}, devices=jax.devices("cpu")[:8])
    cfg = gpt.GPTConfig(vocab_size=256, max_seq=32, d_model=32,
                        n_heads=2, n_layers=4, d_ff=64,
                        dtype=jnp.float32)
    loss = train_step_1f1b(cfg, mesh, batch_n=16, seq=32)
    assert loss > 0
