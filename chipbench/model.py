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


def make_params(cfg, seed31: int):
    import jax
    return jitted_init(cfg)(jax.random.PRNGKey(seed31))
