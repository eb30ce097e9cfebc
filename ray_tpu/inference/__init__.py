"""ray_tpu.inference: continuous-batching LLM inference under Serve.

The "millions of users" leg of the north star (ROADMAP item 2): an
end-to-end inference product over the sharded GPT —

  * decode.py  — the GPT's compiled serving programs: block-table
                 paged decode step, chunked prefill, the widened
                 speculative verify step and the truncated-layer draft
                 step, each the model's ONE layer function
                 (``gpt._transformer_layer``) handed the paged
                 attention step; the full-width prefill
                 (``gpt.forward(return_kv=True)``, the cold-start
                 path); the host-side n-gram drafter.  All compiled
                 once per geometry.  With a mesh the paged programs run
                 tensor-parallel (pools heads-sharded, one collective
                 per layer); MoE configs decode via the layer's own
                 expert dispatch.
  * cache.py   — BlockPool (refcounted token blocks, copy-on-write
                 tails, scratch-block scatter discipline) + RadixIndex
                 (prefix reuse trie, LRU eviction) + StatePool (the
                 per-row recurrent state of a model's state-space
                 layers, owned by the BlockPool).
  * recurrent.py — the decode and chunk-prefill programs of the second
                 model family (models/hybrid.py: Mamba-2 + attention
                 mixers, routed experts): the model's one layer
                 function over both kinds of pool.
  * engine.py  — the Orca-style iteration-level scheduler over the
                 paged cache: block-budget admission with prefix-hit
                 credit, occupancy-aware chunked prefill, block-
                 pressure preemption, streams tokens per request.
  * serving.py — the Serve deployment (POST /v1/generate, JSON +
                 chunked token streaming, replica autoscaling, block/
                 prefix gauges for the fleet router).

Quick start::

    from ray_tpu import serve
    from ray_tpu.inference import build_gpt_deployment
    serve.run(build_gpt_deployment(), use_actors=False, http=True)
    # curl -d '{"prompt": [1,2,3], "max_tokens": 8}' \
    #      http://127.0.0.1:<port>/v1/generate

Measured on the chip by ``chipbench/`` (``BENCHMARK.json``'s serving
cells; ``PERF.md`` has the readings).
"""

from __future__ import annotations

from ray_tpu.inference.cache import BlockPool, RadixIndex, StatePool
from ray_tpu.inference.decode import (SpeculationUnsupported,
                                      make_chunk_prefill_fn,
                                      make_paged_decode_step,
                                      make_paged_draft_step,
                                      make_prefill_fn,
                                      make_spec_verify_step,
                                      ngram_propose)
from ray_tpu.inference.engine import (PRIORITY_BATCH, PRIORITY_INTERACTIVE,
                                      EngineConfig, EngineDrainingError,
                                      EngineStoppedError,
                                      GenerationRequest, InferenceEngine,
                                      metrics_snapshot)
from ray_tpu.inference.serving import (GPTServer, build_gpt_deployment,
                                       encode_prompt, parse_stream_chunks)

__all__ = [
    "BlockPool", "RadixIndex", "StatePool", "SpeculationUnsupported",
    "make_chunk_prefill_fn", "make_paged_decode_step",
    "make_paged_draft_step", "make_prefill_fn", "make_spec_verify_step",
    "ngram_propose",
    "EngineConfig", "EngineDrainingError", "EngineStoppedError",
    "GenerationRequest",
    "InferenceEngine", "PRIORITY_BATCH", "PRIORITY_INTERACTIVE",
    "metrics_snapshot", "GPTServer", "build_gpt_deployment",
    "encode_prompt", "parse_stream_chunks",
]

from ray_tpu import usage_stats as _usage_stats
_usage_stats.record_library_usage("inference")
