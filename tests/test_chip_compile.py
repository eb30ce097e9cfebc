"""Real-size compiles for a DESCRIBED TPU v5e (no chip attached).

The TPU compiler is installed here and compiles for a topology that is
described, not attached — so the main path's kernels are checked at
GPT-2 124M widths on every PR at no chip time: what Mosaic or the
partitioner would refuse on the chip, it refuses here.  A compile that
passes is not a chip run (``chip_smoke.py`` is).

Everything that touches the topology — the description itself, the
shardings, meshes and shapes built from it — lives in module-scoped,
non-autouse fixtures of THIS file and runs in this process only: one
process may load the TPU library at a time, and every xdist worker
imports every test file.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from ray_tpu.inference.decode import make_paged_decode_step
from ray_tpu.models import gpt
from ray_tpu.parallel.sharding import DEFAULT_LLM_RULES, spec_for

# the modules, not the same-named functions ray_tpu.ops re-exports
attention_mod = importlib.import_module("ray_tpu.ops.attention")
flash_mod = importlib.import_module("ray_tpu.ops.flash_attention")

QKV = (16, 12, 1024, 64)      # GPT-2 124M training attention, bf16


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def for_tpu(topo):
    """Steer the backend-asking code onto its TPU branch (this process
    computes on CPU, so ``jax.default_backend()`` says "cpu"), with the
    persistent compile cache off: an entry written for a described chip
    cannot be read back without one, and warns on every later compile."""
    from jax.experimental.compilation_cache import compilation_cache
    mp = pytest.MonkeyPatch()
    mp.setattr(flash_mod, "_interpret_mode", lambda: False)
    mp.setattr(attention_mod, "on_tpu", lambda: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
    mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo, for_tpu):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def qkv_one_chip(one_chip):
    return jax.ShapeDtypeStruct(QKV, jnp.bfloat16, sharding=one_chip)


@pytest.fixture(scope="module")
def mesh_2x2(topo, for_tpu):
    return Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "tp"))


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


def _sum_grad(attend):
    return jax.grad(lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))


def test_flash_forward_compiles(qkv_one_chip):
    x = qkv_one_chip
    _compile(lambda q, k, v: flash_mod.flash_attention(q, k, v), x, x, x)


def test_flash_forward_backward_compiles(qkv_one_chip):
    x = qkv_one_chip
    _compile(_sum_grad(flash_mod.flash_attention), x, x, x)


def test_flash_lse_variant_compiles(qkv_one_chip):
    x = qkv_one_chip
    _compile(_sum_grad(
        lambda q, k, v: flash_mod.flash_attention_with_lse(q, k, v)[0]),
        x, x, x)


@pytest.mark.parametrize("remat_policy", ["dots", "dots_flash"])
def test_attend_compiles_under_dp_tp_mesh(mesh_2x2, remat_policy):
    """The partitioner refuses a bare Mosaic call under a mesh
    ("cannot be automatically partitioned"); gpt._attend must hand it
    over per shard.  q/k/v sharded batch->dp, heads->tp."""
    cfg = gpt.GPTConfig.gpt2_124m(remat=True, remat_policy=remat_policy)
    spec = spec_for(("batch", "heads", "seq", "kv"), DEFAULT_LLM_RULES,
                    mesh_2x2)
    x = jax.ShapeDtypeStruct(QKV, jnp.bfloat16,
                             sharding=NamedSharding(mesh_2x2, spec))
    text = _compile(_sum_grad(
        lambda q, k, v: gpt._attend(q, k, v, cfg, mesh_2x2,
                                    DEFAULT_LLM_RULES)), x, x, x)
    # per shard: 16/2 batch rows x 12/2 heads
    assert "bf16[8,6,1024,64]" in text


def test_loss_grad_compiles_under_pp_dp_mesh(topo, for_tpu):
    """A pp mesh: the pipeline binds ``pp`` manual itself and the flash
    call nests inside it, manual over the remaining axes only.  Through
    ``gpt.loss_fn`` at 124M width, 4 layers (2 a stage)."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("pp", "dp"))
    cfg = gpt.GPTConfig.gpt2_124m(n_layers=4, remat=True,
                                  remat_policy="dots")

    def on(logical, s):
        spec = spec_for(logical, DEFAULT_LLM_RULES, mesh)
        return jax.ShapeDtypeStruct(s.shape, s.dtype,
                                    sharding=NamedSharding(mesh, spec))

    params = jax.tree.map(
        on, gpt.param_logical_axes(cfg),
        jax.eval_shape(lambda: gpt.init_params(cfg, jax.random.PRNGKey(0))),
        is_leaf=lambda x: isinstance(x, tuple))
    batch = {"tokens": on(("batch", None), jax.ShapeDtypeStruct(
        (QKV[0], cfg.max_seq + 1), jnp.int32))}
    text = _compile(jax.value_and_grad(
        lambda p, b: gpt.loss_fn(p, b, cfg, mesh=mesh,
                                 rules=DEFAULT_LLM_RULES)), params, batch)
    # per shard: 4 microbatches of 16 rows, each halved over dp
    assert "bf16[2,12,1024,64]" in text


def test_paged_decode_step_compiles_at_124m(one_chip):
    """One engine program at full width: the paged decode step over the
    default serving geometry (8 rows, 16-token blocks, 1024-token
    tables).  Its attention is the reference by design (per-row kv
    lengths), so no Mosaic kernel is expected — only that the TPU
    compiler takes the program and it fits the chip."""
    cfg = gpt.GPTConfig.gpt2_124m()
    rows, bs = 8, 16
    n_table = cfg.max_seq // bs
    n_blocks = rows * n_table + 1

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda s: on_chip(s.shape, s.dtype),
        jax.eval_shape(lambda: gpt.init_params(cfg, jax.random.PRNGKey(0))))
    pool = on_chip((cfg.n_layers, n_blocks, cfg.n_heads, bs, cfg.head_dim),
                   cfg.dtype)
    step = make_paged_decode_step(cfg, block_size=bs, n_table=n_table)
    compiled = step.lower(
        params, pool, pool, on_chip((rows, n_table), jnp.int32),
        on_chip((rows,), jnp.int32), on_chip((rows,), jnp.int32),
        on_chip((rows,), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < 15.75 * 2 ** 30, f"decode step needs {used / 2**30:.1f} GiB"
