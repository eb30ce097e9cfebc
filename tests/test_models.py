"""Model zoo tests (test-strategy analogue of the reference's model
coverage, e.g. rllib/models tests — here the zoo is framework-owned)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt, mlp
from ray_tpu.parallel.mesh import create_mesh


@pytest.fixture(scope="module")
def tiny_cfg():
    return gpt.GPTConfig.tiny()


@pytest.fixture(scope="module")
def tiny_params(tiny_cfg):
    return gpt.init_params(tiny_cfg, jax.random.PRNGKey(0))


def test_gpt_forward_shapes(tiny_cfg, tiny_params):
    toks = jnp.zeros((2, 16), jnp.int32)
    logits = gpt.forward(tiny_params, toks, tiny_cfg)
    assert logits.shape == (2, 16, tiny_cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_gpt_loss_decreases(tiny_cfg, tiny_params):
    import optax
    from ray_tpu.train.step import make_train_step
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                              tiny_cfg.vocab_size)
    batch = {"tokens": toks}
    init_fn, step_fn = make_train_step(
        lambda p, b: gpt.loss_fn(p, b, tiny_cfg), optax.adam(1e-2))
    state = init_fn(tiny_params)
    state, m0 = step_fn(state, batch)
    for _ in range(10):
        state, m = step_fn(state, batch)
    assert float(m["loss"]) < float(m0["loss"])


def test_gpt_sp_matches_reference(tiny_cfg, tiny_params):
    """Ring attention over an sp-sharded mesh == single-device attention."""
    mesh = create_mesh({"dp": 2, "sp": 4}, devices=jax.devices("cpu"))
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 65), 0,
                              tiny_cfg.vocab_size)
    l_sp = jax.jit(lambda p, b: gpt.loss_fn(p, b, tiny_cfg, mesh=mesh))(
        tiny_params, {"tokens": toks})
    l_ref = jax.jit(lambda p, b: gpt.loss_fn(p, b, tiny_cfg))(
        tiny_params, {"tokens": toks})
    np.testing.assert_allclose(float(l_sp), float(l_ref), rtol=1e-4)


def test_gpt_tp_matches_reference(tiny_cfg, tiny_params):
    mesh = create_mesh({"dp": 2, "tp": 4}, devices=jax.devices("cpu"))
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 33), 0,
                              tiny_cfg.vocab_size)
    l_tp = jax.jit(lambda p, b: gpt.loss_fn(p, b, tiny_cfg, mesh=mesh))(
        tiny_params, {"tokens": toks})
    l_ref = jax.jit(lambda p, b: gpt.loss_fn(p, b, tiny_cfg))(
        tiny_params, {"tokens": toks})
    np.testing.assert_allclose(float(l_tp), float(l_ref), rtol=1e-4)


@pytest.mark.parametrize("axes", [{"dp": 2, "tp": 2}, {"pp": 2, "dp": 2},
                                  {"pp": 2, "dp": 2, "tp": 2}],
                         ids=lambda a: "x".join(f"{k}{v}" for k, v in a.items()))
def test_gpt_flash_under_mesh_matches_reference(axes):
    """The (interpreted) flash kernel runs per shard under any mesh —
    nested inside the pipeline's own pp-manual shard_map too — and loss
    and gradients match the unmeshed reference attention."""
    kw = dict(max_seq=128, n_layers=2, dtype=jnp.float32, remat=True,
              remat_policy="dots", pp_microbatches=2)
    params = gpt.init_params(gpt.GPTConfig.tiny(**kw), jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(4), (4, 129), 0,
                                          512)}
    n = int(np.prod(list(axes.values())))
    mesh = create_mesh(axes, devices=jax.devices("cpu")[:n])

    def value_and_grad(impl, mesh):
        cfg = gpt.GPTConfig.tiny(attn_impl=impl, **kw)
        return jax.jit(jax.value_and_grad(
            lambda p, b: gpt.loss_fn(p, b, cfg, mesh=mesh)))(params, batch)

    l_ref, g_ref = value_and_grad("reference", None)
    l_fl, g_fl = value_and_grad("flash", mesh)
    np.testing.assert_allclose(float(l_fl), float(l_ref), rtol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-5), g_fl, g_ref)


def test_gpt_remat_policy_names():
    """One name for one behaviour: None (full recompute) or "dots"."""
    assert gpt._checkpoint_policy(None) is None
    assert callable(gpt._checkpoint_policy("dots"))
    with pytest.raises(ValueError, match="unknown remat_policy"):
        gpt.GPTConfig.tiny(remat_policy="dots_and_more")


def test_gpt_generate(tiny_cfg, tiny_params):
    prompt = jnp.array([[1, 2, 3]], jnp.int32)
    out = gpt.generate(tiny_params, tiny_cfg, prompt, max_new=5,
                       temperature=0.0)
    assert out.shape == (1, 8)
    assert (np.asarray(out[:, :3]) == np.asarray(prompt)).all()


def test_mlp_trains():
    cfg = mlp.MLPConfig(in_dim=8, hidden=(16,), out_dim=3)
    params = mlp.init_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
    y = (x[:, 0] > 0).astype(jnp.int32)
    batch = {"x": x, "y": y}
    loss0 = float(mlp.loss_fn(params, batch, cfg))
    grad = jax.grad(lambda p: mlp.loss_fn(p, batch, cfg))(params)
    params = jax.tree.map(lambda p, g: p - 0.5 * g, params, grad)
    assert float(mlp.loss_fn(params, batch, cfg)) < loss0


# -- resnet ----------------------------------------------------------------

def test_resnet_forward_and_train():
    from ray_tpu.models import resnet
    cfg = resnet.ResNetConfig.tiny(num_classes=4)
    params, state = resnet.init_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 16, 3))
    y = jnp.array([0, 1, 2, 3])
    logits, new_state = resnet.forward(params, state, x, cfg, train=True)
    assert logits.shape == (4, 4)
    # BN running stats moved
    assert not np.allclose(np.asarray(new_state["stem_bn"]["mean"]),
                           np.asarray(state["stem_bn"]["mean"]))

    def step(p, s):
        (l, (s2, m)), g = jax.value_and_grad(
            lambda p: resnet.loss_fn(p, s, {"x": x, "y": y}, cfg),
            has_aux=True)(p)
        p = jax.tree.map(lambda a, b: a - 0.1 * b, p, g)
        return p, s2, l
    l0 = None
    for _ in range(8):
        params, state, l = step(params, state)
        l0 = l if l0 is None else l0
    assert float(l) < float(l0)


def test_resnet_eval_deterministic():
    from ray_tpu.models import resnet
    cfg = resnet.ResNetConfig.tiny()
    params, state = resnet.init_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 3))
    l1, st = resnet.forward(params, state, x, cfg, train=False)
    l2, _ = resnet.forward(params, state, x, cfg, train=False)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2))
    # eval does not update stats
    np.testing.assert_allclose(np.asarray(st["stem_bn"]["mean"]),
                               np.asarray(state["stem_bn"]["mean"]))


def test_resnet50_shapes():
    from ray_tpu.models import resnet
    cfg = resnet.ResNetConfig.resnet50(num_classes=10, cifar_stem=False,
                                       dtype=jnp.float32, num_filters=8)
    params, state = resnet.init_params(cfg, jax.random.PRNGKey(0))
    x = jnp.zeros((1, 64, 64, 3))
    logits, _ = resnet.forward(params, state, x, cfg, train=False)
    assert logits.shape == (1, 10)


# -- bert ------------------------------------------------------------------

def test_bert_mlm_loss_and_mask():
    from ray_tpu.models import bert
    cfg = bert.BERTConfig.tiny()
    params = bert.init_params(cfg, jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                             cfg.vocab_size)
    labels = jnp.full((2, 32), cfg.ignore_index).at[:, 3].set(ids[:, 3])
    loss = bert.loss_fn(params, {"input_ids": ids, "labels": labels}, cfg)
    assert np.isfinite(float(loss))
    # attention_mask: padding must not change unmasked-position loss much
    am = jnp.ones((2, 32), jnp.int32)
    l2 = bert.loss_fn(params, {"input_ids": ids, "labels": labels,
                               "attention_mask": am}, cfg)
    np.testing.assert_allclose(float(loss), float(l2), rtol=1e-5)


def test_bert_trains():
    import optax
    from ray_tpu.models import bert
    from ray_tpu.train.step import make_train_step
    cfg = bert.BERTConfig.tiny(n_layers=1)
    params = bert.init_params(cfg, jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                             cfg.vocab_size)
    labels = ids  # predict every token (degenerate MLM)
    batch = {"input_ids": ids, "labels": labels}
    init_fn, step_fn = make_train_step(
        lambda p, b: bert.loss_fn(p, b, cfg), optax.adam(1e-2))
    s = init_fn(params)
    s, m0 = step_fn(s, batch)
    for _ in range(10):
        s, m = step_fn(s, batch)
    assert float(m["loss"]) < float(m0["loss"])


def test_bert_tp_matches_reference():
    from ray_tpu.models import bert
    cfg = bert.BERTConfig.tiny()
    params = bert.init_params(cfg, jax.random.PRNGKey(0))
    mesh = create_mesh({"dp": 2, "tp": 4}, devices=jax.devices("cpu"))
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0,
                             cfg.vocab_size)
    batch = {"input_ids": ids, "labels": ids}
    l_tp = jax.jit(lambda p, b: bert.loss_fn(p, b, cfg, mesh=mesh))(
        params, batch)
    l_ref = jax.jit(lambda p, b: bert.loss_fn(p, b, cfg))(params, batch)
    np.testing.assert_allclose(float(l_tp), float(l_ref), rtol=1e-4)


# -- rl model zoo ----------------------------------------------------------

def test_actor_critic_fcnet():
    from ray_tpu.models.zoo import ActorCritic, ModelConfig
    net = ActorCritic(ModelConfig(kind="fcnet", obs_shape=(4,),
                                  num_actions=2, fcnet_hiddens=(32,)))
    params = net.init(jax.random.PRNGKey(0))
    logits, value = net.apply(params, jnp.zeros((3, 4)))
    assert logits.shape == (3, 2) and value.shape == (3,)


def test_actor_critic_visionnet():
    from ray_tpu.models.zoo import ActorCritic, ModelConfig
    net = ActorCritic(ModelConfig(kind="visionnet", obs_shape=(84, 84, 4),
                                  num_actions=6))
    params = net.init(jax.random.PRNGKey(0))
    obs = jnp.zeros((2, 84, 84, 4), jnp.uint8)
    logits, value = net.apply(params, obs)
    assert logits.shape == (2, 6) and value.shape == (2,)


def test_actor_critic_lstm():
    from ray_tpu.models.zoo import ActorCritic, ModelConfig
    net = ActorCritic(ModelConfig(kind="lstm", obs_shape=(4,),
                                  num_actions=2, cell_size=16))
    assert net.is_recurrent
    params = net.init(jax.random.PRNGKey(0))
    obs = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 4))
    logits, value, state = net.apply_seq(params, obs)
    assert logits.shape == (2, 5, 2) and value.shape == (2, 5)
    assert state[0].shape == (2, 16)
    # carry state across windows
    logits2, _, state2 = net.apply_seq(params, obs, state)
    assert not np.allclose(np.asarray(logits), np.asarray(logits2))


def test_actor_critic_gtrxl_causal():
    from ray_tpu.models.zoo import ActorCritic, ModelConfig
    net = ActorCritic(ModelConfig(kind="gtrxl", obs_shape=(4,),
                                  num_actions=3, attn_dim=16,
                                  attn_layers=1))
    params = net.init(jax.random.PRNGKey(0))
    obs = jax.random.normal(jax.random.PRNGKey(1), (1, 6, 4))
    logits, _, _ = net.apply_seq(params, obs)
    # causality: perturbing the future must not change the past
    obs2 = obs.at[:, 4:].add(1.0)
    logits2, _, _ = net.apply_seq(params, obs2)
    np.testing.assert_allclose(np.asarray(logits[:, :4]),
                               np.asarray(logits2[:, :4]), atol=1e-5)
