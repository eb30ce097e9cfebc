"""Attention kernels: flash (pallas, interpreted on CPU) and ring
attention vs the reference implementation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.ops import attention, flash_attention, mha_reference, ring_attention


def _qkv(key, b=2, h=4, s=256, d=64, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, h, s, d), dtype)
    k = jax.random.normal(kk, (b, h, s, d), dtype)
    v = jax.random.normal(kv, (b, h, s, d), dtype)
    return q, k, v


# On TPU the MXU runs f32 matmuls at bf16-ish precision by default, so two
# correct implementations with different blocking differ at ~1e-2.
TOL = dict(atol=2e-2, rtol=2e-2) if jax.default_backend() == "tpu" \
    else dict(atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_reference(causal):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    out_ref = mha_reference(q, k, v, causal=causal)
    out_flash = flash_attention(q, k, v, causal=causal,
                                block_q=128, block_k=128)
    np.testing.assert_allclose(out_ref, out_flash, **TOL)


def test_flash_grads_match_reference():
    q, k, v = _qkv(jax.random.PRNGKey(1), s=128)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       block_q=128, block_k=64) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        a, b = np.asarray(a), np.asarray(b)
        # a handful of elements hit the worst-case MXU rounding; bound the
        # bulk tightly and the tail loosely
        assert np.mean(np.abs(a - b)) < 1e-3
        np.testing.assert_allclose(a, b, atol=0.1, rtol=0.1)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_fused_pallas_backward(causal):
    """Block-aligned shapes route to the fused pallas dkv/dq kernels
    (block_k % 128 == 0); verify against the dense reference grads."""
    q, k, v = _qkv(jax.random.PRNGKey(7), b=2, h=2, s=512, d=64)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=128, block_k=128) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        a, b = np.asarray(a), np.asarray(b)
        assert np.mean(np.abs(a - b)) < 1e-3
        np.testing.assert_allclose(a, b, atol=0.1, rtol=0.1)


def test_flash_fused_backward_cross_length():
    """q shorter than kv (block-aligned): fused kernels honor the causal
    diagonal offset used by decode-style shapes."""
    key = jax.random.PRNGKey(8)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, 2, 128, 64))
    k = jax.random.normal(kk, (1, 2, 384, 64))
    v = jax.random.normal(kv, (1, 2, 384, 64))

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       block_q=128, block_k=128) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        a, b = np.asarray(a), np.asarray(b)
        assert np.mean(np.abs(a - b)) < 1e-3
        np.testing.assert_allclose(a, b, atol=0.1, rtol=0.1)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_ragged_kv_padding(causal):
    """kv_len not a multiple of block_k (200 % 128 != 0): the forward
    zero-pads kv and masks padded columns — regression for the former
    in-kernel ds-clamp scheme, which read zeros past the array bound in
    interpret mode."""
    key = jax.random.PRNGKey(9)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, 2, 200, 64))
    k = jax.random.normal(kk, (1, 2, 200, 64))
    v = jax.random.normal(kv, (1, 2, 200, 64))
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)

    def loss_f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=128, block_k=128) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

    g_f = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_r, g_f):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3)


def test_attention_dispatch_runs():
    q, k, v = _qkv(jax.random.PRNGKey(2), s=128)
    out = attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, mha_reference(q, k, v, causal=True),
                               **TOL)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_reference(cpu_mesh_devices, causal):
    b, h, s, d = 2, 2, 256, 32
    q, k, v = _qkv(jax.random.PRNGKey(3), b=b, h=h, s=s, d=d)
    mesh = Mesh(np.array(cpu_mesh_devices[:4]), ("sp",))
    shd = NamedSharding(mesh, P(None, None, "sp", None))

    def f(q, k, v):
        return ring_attention(q, k, v, "sp", causal=causal)

    out = jax.jit(shard_map(
        f, mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None)))(
        jax.device_put(q, shd), jax.device_put(k, shd),
        jax.device_put(v, shd))
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **TOL)


def test_ring_attention_grad(cpu_mesh_devices):
    b, h, s, d = 1, 2, 128, 16
    q, k, v = _qkv(jax.random.PRNGKey(4), b=b, h=h, s=s, d=d)
    mesh = Mesh(np.array(cpu_mesh_devices[:4]), ("sp",))
    shd = NamedSharding(mesh, P(None, None, "sp", None))
    spec = P(None, None, "sp", None)

    def ring_loss(q, k, v):
        f = shard_map(lambda a, b_, c: ring_attention(a, b_, c, "sp"),
                      mesh=mesh, in_specs=(spec,) * 3, out_specs=spec)
        return jnp.sum(f(q, k, v) ** 2)

    def ref_loss(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(
        jax.device_put(q, shd), jax.device_put(k, shd),
        jax.device_put(v, shd))
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   **TOL)


def test_flash_cross_length_causal():
    """Decode-with-kv-cache shape: q shorter than kv, causal offset must
    match mha_reference's (k_len - q_len) convention."""
    import jax, jax.numpy as jnp
    from ray_tpu.ops.attention import mha_reference
    from ray_tpu.ops.flash_attention import flash_attention
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (1, 2, 64, 64))
    k = jax.random.normal(k2, (1, 2, 128, 64))
    v = jax.random.normal(k3, (1, 2, 128, 64))
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    ref = mha_reference(q, k, v, causal=True)
    import numpy as np
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_ragged_kv_blocks():
    """kv_len not a multiple of block_k: the clamped last block must not
    double-count keys."""
    import jax, jax.numpy as jnp, numpy as np
    from ray_tpu.ops.attention import mha_reference
    from ray_tpu.ops.flash_attention import flash_attention
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(k1, (1, 1, 96, 64))
    k = jax.random.normal(k2, (1, 1, 96, 64))
    v = jax.random.normal(k3, (1, 1, 96, 64))
    for causal in (False, True):
        out = flash_attention(q, k, v, causal=causal,
                              block_q=32, block_k=64)
        ref = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def test_attention_mask_flash_raises():
    import jax, jax.numpy as jnp, pytest as _pytest
    from ray_tpu.ops.attention import attention
    q = jnp.zeros((1, 1, 8, 16))
    mask = jnp.ones((1, 1, 8, 8), bool)
    with _pytest.raises(ValueError):
        attention(q, q, q, mask=mask, impl="flash")


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_forward_rule_keeps_one_lse_a_row(dtype):
    """What the backward is handed and no more: q, k, v, the output in
    the inputs' dtype and ONE float32 a query row of logsumexp (the
    backward kernels turn a block of it into their lane tile)."""
    import importlib
    # the module, not the same-named function ray_tpu.ops re-exports
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    b, h, sq, skv, d = 2, 3, 128, 256, 64
    q = jax.ShapeDtypeStruct((b, h, sq, d), dtype)
    kv = jax.ShapeDtypeStruct((b, h, skv, d), dtype)
    out, res = jax.eval_shape(
        lambda q, k, v: fa._fwd_rule(q, k, v, None, True, 128, 128),
        q, kv, kv)
    assert (out.shape, out.dtype) == ((b, h, sq, d), dtype)
    assert [(r.shape, r.dtype) for r in res] == [
        ((b, h, sq, d), dtype), ((b, h, skv, d), dtype),
        ((b, h, skv, d), dtype), ((b, h, sq, d), dtype),
        ((b * h, sq), jnp.float32)]


@pytest.mark.parametrize("sq, skv, block", [
    (256, 256, 128),      # block-aligned: the fused pallas backward
    (200, 200, 128),      # ragged: the plain-jax backward
    (128, 384, 128),      # cross-length causal (q at the tail of kv)
])
def test_flash_grads_under_dots_checkpoint(sq, skv, block, capsys):
    """A layer rematerialised under the policy gpt's "dots" builds keeps
    the flash forward's named residuals: its gradients are those of the
    layer with no checkpoint, and of the flash call the forward pass
    saves the two named values alone."""
    from jax.ad_checkpoint import print_saved_residuals
    from ray_tpu.models.gpt import _checkpoint_policy
    b, h, d = 1, 2, 64
    kx, ky, kw = jax.random.split(jax.random.PRNGKey(11), 3)
    x = jax.random.normal(kx, (b, sq, h * d))
    y = jax.random.normal(ky, (b, skv, h * d))
    w = jax.random.normal(kw, (4, h * d, h * d)) * (h * d) ** -0.5

    def heads(t):
        return t.reshape(b, -1, h, d).transpose(0, 2, 1, 3)

    def layer(x, y, w):
        o = flash_attention(heads(x @ w[0]), heads(y @ w[1]),
                            heads(y @ w[2]), causal=True,
                            block_q=block, block_k=block)
        return jnp.sum((o.transpose(0, 2, 1, 3).reshape(b, sq, h * d)
                        @ w[3]) ** 2)

    kept = jax.checkpoint(layer, policy=_checkpoint_policy("dots"))
    g_plain = jax.grad(layer, argnums=(0, 1, 2))(x, y, w)
    g_kept = jax.grad(kept, argnums=(0, 1, 2))(x, y, w)
    for a, b_ in zip(g_plain, g_kept):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), **TOL)
    capsys.readouterr()
    print_saved_residuals(kept, x, y, w)
    saved = [line for line in capsys.readouterr().out.splitlines()
             if "(flash_attention)" in line]
    # (a value saved for a name shows as the policy's own marker op)
    assert [line.split()[0] for line in saved] == [
        f"f32[{b},{h},{sq},{d}]", f"f32[{b * h},{sq}]"], saved
