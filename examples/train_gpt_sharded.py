"""Train GPT-2 on a device mesh with ray_tpu.train.JaxTrainer.

On a TPU host this process drives ALL local chips itself (one process
holds the chips; ``mesh={"dp": -1}`` spans them, flash attention runs
per shard under shard_map).  On CPU it is a smoke test over 8 virtual
devices on a dp/tp/sp mesh:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_gpt_sharded.py
The full-width GPT-2 124M proof on the chip is ``python chip_smoke.py``.
Compiled programs are cached in $JAX_COMPILATION_CACHE_DIR, else in
<checkout>/.jax_cache.
"""
import os as _os
import sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import optax

from ray_tpu.models import gpt
from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

CFG = gpt.GPTConfig(vocab_size=512, max_seq=128, d_model=128,
                    n_heads=4, n_layers=2, d_ff=512, remat=True)


def batches(steps: int = 10, batch: int = 8):
    key = jax.random.PRNGKey(1)
    for _ in range(steps):
        key, sub = jax.random.split(key)
        yield {"tokens": jax.random.randint(sub, (batch, CFG.max_seq + 1),
                                            0, CFG.vocab_size, jnp.int32)}


if __name__ == "__main__":
    from ray_tpu._compile_cache import enable_compile_cache
    enable_compile_cache()
    on_cpu = jax.devices()[0].platform != "tpu"
    trainer = JaxTrainer(
        loss_fn=lambda p, b, mesh=None, rules=None: gpt.loss_fn(
            p, b, CFG, mesh=mesh, rules=rules),
        init_params=lambda rng: gpt.init_params(CFG, rng),
        optimizer=optax.adamw(3e-4),
        train_data=batches(),
        num_steps=10,
        params_logical=gpt.param_logical_axes(CFG),
        report_every=2,
        scaling_config=ScalingConfig(
            mesh={"dp": 2, "tp": 2, "sp": 2} if on_cpu else {"dp": -1},
            use_cpu_devices=on_cpu),
        run_config=RunConfig(storage_path="/tmp/rt_gpt_example"))
    result = trainer.fit()
    print("final metrics:", result.metrics)
