"""ray_tpu.inference: continuous-batching LLM inference under Serve.

The "millions of users" leg of the north star (ROADMAP item 2): an
end-to-end inference product over the sharded GPT —

  * decode.py  — compiled decode programs: block-table paged decode
                 step + chunked prefill (production), slot step + full
                 prefill via the ordinary training forward
                 (``gpt.forward(return_kv=True)`` — also the paged
                 cold-start path), speculative-decoding bodies (widened
                 verify step, truncated-layer draft step, host-side
                 n-gram drafter), all compiled once per geometry.  With
                 a mesh the paged bodies run tensor-parallel (pools
                 heads-sharded, one collective per layer) and MoE
                 configs decode via the training forward's expert
                 dispatch.
  * cache.py   — BlockPool (refcounted token blocks, copy-on-write
                 tails, scratch-block scatter discipline) + RadixIndex
                 (prefix reuse trie, LRU eviction) + StatePool (the
                 per-row recurrent state of a model's state-space
                 layers, owned by the BlockPool); KVCacheManager is
                 the legacy slot pool (A/B baseline).
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

Benchmark receipt: benchmarks/serve_bench.py → SERVE_r17.json
(paged+prefix vs the r14 slot engine, continuous batching vs naive
sequential, AND tp-sharded vs single-device decode, all same-box
same-run A/B).
"""

from __future__ import annotations

from ray_tpu.inference.cache import (BlockPool, KVCacheManager, RadixIndex,
                                     StatePool)
from ray_tpu.inference.decode import (MoEDecodeUnsupported,
                                      SpeculationUnsupported,
                                      make_chunk_prefill_fn,
                                      make_decode_step,
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
    "BlockPool", "KVCacheManager", "RadixIndex", "StatePool",
    "MoEDecodeUnsupported", "SpeculationUnsupported",
    "make_chunk_prefill_fn", "make_decode_step",
    "make_paged_decode_step", "make_paged_draft_step", "make_prefill_fn",
    "make_spec_verify_step", "ngram_propose",
    "EngineConfig", "EngineDrainingError", "EngineStoppedError",
    "GenerationRequest",
    "InferenceEngine", "PRIORITY_BATCH", "PRIORITY_INTERACTIVE",
    "metrics_snapshot", "GPTServer", "build_gpt_deployment",
    "encode_prompt", "parse_stream_chunks",
]

from ray_tpu import usage_stats as _usage_stats
_usage_stats.record_library_usage("inference")
