"""From a configuration file to the program's own objects, and seeds."""

from __future__ import annotations


def gpt_config(config: dict):
    """``chipbench/configs/<name>.json`` -> ``ray_tpu.models.gpt.GPTConfig``."""
    from ray_tpu.models import gpt
    return gpt.GPTConfig(
        vocab_size=config["vocab_size_padded"], max_seq=config["n_positions"],
        d_model=config["n_embd"], n_heads=config["n_head"],
        n_layers=config["n_layer"], d_ff=config["n_inner"],
        **config.get("gpt_config", {}))


def fold_seed(seed: int, stream: int) -> int:
    """A 31-bit seed for ``jax.random.PRNGKey`` from ``--seed`` (which
    may exceed 32 signed bits) and a stream number."""
    import numpy as np
    return int(np.random.SeedSequence([int(seed), int(stream)])
               .generate_state(1)[0] >> 1)


def jitted_init(cfg):
    """``key -> params``: the weights made on the device in ONE jitted
    call, not leaf by leaf."""
    import jax
    from ray_tpu.models import gpt
    return jax.jit(lambda key: gpt.init_params(cfg, key))


def make_params(cfg, seed31: int, served_as=None):
    """The weights from the seed.  ``served_as`` (a configuration's
    ``weights_served_as``: {dtype name: [per-layer leaf names]}) stores
    those leaves in the type they are served in, inside the same jitted
    call, so the float32 form of a leaf is never held."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import gpt
    dtype_of = {leaf: jnp.dtype(name)
                for name, leaves in (served_as or {}).items()
                for leaf in leaves}

    def init(key):
        params = gpt.init_params(cfg, key)
        layers = {k: v.astype(dtype_of.get(k, v.dtype))
                  for k, v in params["layers"].items()}
        return {**params, "layers": layers}
    return jax.jit(init)(jax.random.PRNGKey(seed31))


def device_memory_peak(devs) -> int:
    """Peak bytes on the fullest chip so far.  The TPU runtime counts
    buffers (``peak_bytes_in_use``) and the scratch memory of running
    programs (``peak_bytes_reserved``) apart, and their peaks need not
    coincide: the larger of the two is a lower bound of the true peak."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)),
                   int(stats.get("peak_bytes_reserved", 0)))
    return peak
