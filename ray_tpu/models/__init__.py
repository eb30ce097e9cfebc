"""Model zoo: TPU-first reference models for train/tune/rllib/serve.

The reference framework ships no model library of its own (it trains
user-supplied torch/TF models — e.g. the ResNet/GPT configs in its AIR
benchmarks, doc/source/ray-air/benchmarks.rst); here the flagship models
are part of the framework so every layer above (train, tune, rllib,
serve, bench) exercises the same TPU-native compute path: pure-jax
pytree params with logical sharding axes, scan-over-layers, pallas
attention, bf16 matmuls on the MXU.
"""

from ray_tpu.models.bert import (BERT, BERTConfig)
from ray_tpu.models.gpt import (GPT, GPTConfig)
from ray_tpu.models.hybrid import HybridConfig
from ray_tpu.models.mlp import (MLP, MLPConfig)
from ray_tpu.models.resnet import (ResNet, ResNetConfig)
from ray_tpu.models.zoo import (ActorCritic, ModelConfig)

__all__ = ["BERT", "BERTConfig", "GPT", "GPTConfig", "HybridConfig", "MLP",
           "MLPConfig",
           "ResNet", "ResNetConfig", "ActorCritic", "ModelConfig"]
