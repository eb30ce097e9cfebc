"""Serve deployment for the inference engine: POST /v1/generate.

Each replica owns one InferenceEngine (its own cache pool + decode
loop); Serve's router spreads requests over replicas and the
AutoscalingConfig grows/shrinks the replica set on per-replica in-flight
load — which for this deployment IS the engine queue depth, since every
in-flight request is either holding a decode slot or parked in the
engine's admission queue.  `max_concurrent_queries` is set well above
`max_slots` so the engine (not the router) does the queueing and the
continuous-batching loop sees the real backlog.

Request JSON (POST /v1/generate — any /v1/* path routes here, the
deployment is named "v1"):

    {"prompt": [1, 2, 3] | "text",     # token ids, or a string encoded
                                       #   bytewise modulo the vocab
     "max_tokens": 16,                 # default engine_cfg.default_max_new
     "temperature": 0.0,               # 0 = greedy
     "seed": 0,
     "stream": false,
     "priority": "interactive",        # or "batch" (default): engine
                                       #   admission + ingress queue class
     "model": "variant-id"}            # multiplexed deployments only

Non-streaming replies {"tokens": [...], "n": n, "ttft_s": ..., ...};
``stream: true`` returns a generator the asyncio proxy flushes as
chunked transfer-encoding — one JSON document per chunk, each carrying
one token, then a final ``{"done": true, ...}`` chunk.
"""

from __future__ import annotations

import json
from typing import Optional, Sequence, Union

import jax

from ray_tpu.inference.engine import (EngineConfig, EngineDrainingError,
                                      EngineStoppedError, InferenceEngine,
                                      init_params_for, parse_priority)
from ray_tpu.models.gpt import GPTConfig
from ray_tpu.serve import engine_stats
from ray_tpu.serve.deployment import (AutoscalingConfig, Deployment,
                                      DeploymentOptions)

DEFAULT_ROUTE = "v1"

# what a replica reports of its engines to the router's probe
# (``GPTServer.fleet_stats``), each by its rule in ``engine_stats``.  The
# blocks are there because the occupancy router and the autoscaler must
# see BLOCK pressure, not just row counts: a replica whose rows are free
# but whose pool is nearly full is not spare capacity
_FLEET_STATS = (
    "max_slots", "active_slots", "waiting_requests", "waiting_interactive",
    "blocks_total", "blocks_free", "block_utilization",
    "mesh_devices", "tp_shards",
    "prefix_hit_tokens", "prefix_lookup_tokens", "prefix_hit_rate",
    "spec_drafted_tokens", "spec_accepted_tokens", "spec_accept_rate",
    "tokens_per_step",
)


def encode_prompt(prompt: Union[str, Sequence[int]],
                  vocab_size: int) -> list[int]:
    """Token ids pass through; strings encode bytewise modulo the vocab
    (the repo ships no tokenizer — this keeps the HTTP surface usable
    end-to-end and is trivially reversible for vocab >= 256)."""
    if isinstance(prompt, str):
        if not prompt:
            raise ValueError("empty prompt")
        return [b % vocab_size for b in prompt.encode("utf-8")]
    return [int(t) for t in prompt]


class GPTServer:
    """Replica body: one engine per replica — or, with ``variants``, an
    LRU of per-variant engines (model multiplexing behind one
    deployment).

    Params are derived from ``seed`` at replica init (deterministic
    across replicas, so any replica answers any request identically
    under greedy decoding — the property the fleet's
    resume-on-replica-death replay relies on), or passed in directly
    for in-process use.  When built under the serve controller the
    replica tag names the engine(s) and labels their /metrics series.
    """

    def __init__(self, cfg=None,
                 engine_cfg: Optional[EngineConfig] = None,
                 seed: int = 0, params=None,
                 engine_name: Optional[str] = None,
                 variants: Optional[dict] = None,
                 multiplex_capacity: int = 2,
                 warm_on_init: bool = False,
                 mesh=None, rules=None):
        self.cfg = cfg or GPTConfig.tiny()
        self.engine_cfg = engine_cfg or EngineConfig()
        # tensor-parallel serving: every engine this replica builds
        # (multiplexed variants included) shares the one mesh — pools
        # heads-sharded, tables/radix replicated (see inference.decode)
        self.mesh = mesh
        self.rules = rules
        self._warm = warm_on_init
        self._closed = False
        self._draining = False
        from ray_tpu.serve.controller import get_replica_context
        ctx = get_replica_context()
        self.replica_tag = (ctx.replica_tag if ctx is not None
                            else (engine_name or ""))
        self._labels = ({"deployment": ctx.deployment,
                         "replica": ctx.replica_tag}
                        if ctx is not None else {})
        self._mux = None
        self.engine = None
        if variants and params is not None:
            raise ValueError(
                "params and variants are mutually exclusive: each "
                "variant derives its own params from its catalog seed")
        if variants:
            # model multiplexing: model_id -> seed (each variant is an
            # independently seeded param set + engine/KV pool);
            # LRU-resident per replica, the fleet router prefers
            # replicas already holding the requested variant
            from ray_tpu.serve.fleet.multiplex import ModelMultiplexer
            self._mux = ModelMultiplexer(
                variants,
                lambda mid, spec: self._build_engine(mid, int(spec)),
                lambda eng: eng.shutdown(timeout=2.0),
                capacity=multiplex_capacity)
            # default variant resident from birth; a WARM replica
            # preloads a full working set so scale-up cost stays in the
            # controller, not head-of-line on the first requests
            preload = (list(variants)[:multiplex_capacity]
                       if warm_on_init else [None])
            for mid in preload:
                self._mux.get(mid)
        else:
            self.engine = self._build_engine(None, seed, params=params,
                                             name_override=engine_name)

    def _build_engine(self, model_id: Optional[str], seed: int,
                      params=None, name_override=None) -> InferenceEngine:
        name = name_override
        if name is None and self.replica_tag:
            name = self.replica_tag + (f":{model_id}" if model_id else "")
        labels = dict(self._labels)
        if model_id:
            labels["model"] = model_id
        kw = {}
        if self.mesh is not None:
            kw["mesh"] = self.mesh
            if self.rules is not None:
                kw["rules"] = self.rules
        # masters made here are held by nobody once the engine has
        # derived the tree it serves (gpt.serving_params)
        eng = InferenceEngine(
            params if params is not None
            else init_params_for(self.cfg, jax.random.PRNGKey(seed)),
            self.cfg, self.engine_cfg, name=name, labels=labels, **kw)
        if self._warm:
            # compile every program of a pass off the request path, so
            # a freshly scaled-up replica doesn't serve its first
            # requests cold
            eng.warm_up(timeout=300)
        return eng

    def _engine_for(self, req: dict) -> InferenceEngine:
        if self._closed:
            raise EngineStoppedError("replica closed")
        if self._draining:
            # the route/drain race window: the router picked this
            # replica just as the controller marked it DRAINING — the
            # typed error re-routes (never a 500, never a failure count)
            raise EngineDrainingError("replica is draining (scale-down)")
        if self._mux is None:
            return self.engine
        return self._mux.get(req.get("model"))

    def __call__(self, req):
        if not isinstance(req, dict):
            raise ValueError(
                "expected a JSON object body, e.g. "
                '{"prompt": [1, 2, 3], "max_tokens": 16}')
        if "prompt" not in req:
            raise ValueError('missing required field "prompt"')
        prompt = encode_prompt(req["prompt"], self.cfg.vocab_size)
        handle = self._engine_for(req).submit(
            prompt,
            max_new=req.get("max_tokens"),
            temperature=float(req.get("temperature", 0.0)),
            seed=int(req.get("seed", 0)),
            priority=parse_priority(req.get("priority")))
        if req.get("stream"):
            return self._stream(handle)
        try:
            toks = handle.result(timeout=float(req.get("timeout", 120.0)))
        except TimeoutError:
            # shed the abandoned generation: nobody will read it, so it
            # must not keep holding a decode slot against live requests
            handle.cancel()
            raise
        return {
            "tokens": toks,
            "n": len(toks),
            "ttft_s": (handle.first_token_s or 0) - handle.created_s,
            "latency_s": (handle.finished_s or 0) - handle.created_s,
        }

    @staticmethod
    def _stream(handle):
        def gen():
            i = 0
            try:
                for tok in handle.stream():
                    yield {"token": int(tok), "index": i}
                    i += 1
                yield {"done": True, "n": i,
                       "latency_s": (handle.finished_s or 0)
                       - handle.created_s}
            finally:
                # client disconnect mid-stream closes the generator
                # (GeneratorExit lands here): stop decoding for nobody
                if not handle.done:
                    handle.cancel()
        return gen()

    def _engines(self) -> list:
        if self._mux is not None:
            return self._mux.loaded_bodies()
        return [self.engine] if self.engine is not None else []

    # surfaced for tests / the metrics endpoint via the engine registry
    def engine_stats(self):
        if self._mux is not None:
            raise RuntimeError("multiplexed replica: use fleet_stats()")
        return self.engine.stats()

    def fleet_stats(self) -> dict:
        """The router's probe surface: engine load + loaded variants.
        Multiplexed replicas aggregate over resident engines (total
        slots grow with residency — the router sees real capacity),
        each key by its rule in ``serve/engine_stats.py``."""
        engines = self._engines()
        stats = [e.stats() for e in engines]
        return {
            **engine_stats.reduce(stats, _FLEET_STATS),
            "models": (self._mux.loaded_models()
                       if self._mux is not None else []),
            "stopped": self._closed or not engines
            or all(s["stopped"] for s in stats),
            # replica-LEVEL drain flag: the router skips draining
            # replicas as candidates without dead-marking them (they are
            # alive — just not accepting new work).  Deliberately NOT
            # derived from the engines' own draining flags: an engine
            # drained out-of-band is the route/drain race, and the typed
            # EngineDrainingError out of submit() is what covers it.
            "draining": self._draining,
        }

    # --------------------------------------------- cluster prefix plane
    # Replica-body surface of serve/fleet/prefix_directory.py: the fleet
    # calls these through the same handle plumbing as __call__, so for
    # actor replicas the K/V payload rides the existing object/transfer
    # plane.  All failure modes are typed (PrefixTransferError /
    # ReplicaDeadError shapes) and the plane maps every one of them to
    # local-recompute fallback.

    def prefix_export(self) -> list:
        """Drain all resident engines' prefix publication outboxes
        (tagged with the request ``model`` for multiplexed replicas)."""
        if self._closed:
            return []
        out = []
        if self._mux is not None:
            for mid, eng in zip(self._mux.loaded_models(),
                                self._mux.loaded_bodies()):
                for ex in eng.prefix_export():
                    ex["model"] = mid
                    out.append(ex)
        elif self.engine is not None:
            out.extend(self.engine.prefix_export())
        return out

    def prefix_extract(self, model, tokens, generation: int) -> dict:
        """Holder side of replica→replica prefix adoption (see
        InferenceEngine.prefix_extract for the validation ladder)."""
        req = {"model": model} if model is not None else {}
        return self._engine_for(req).prefix_extract(tokens, generation)

    def prefix_install(self, model, tokens, payload: dict) -> dict:
        """Adopter side: install fetched K/V blocks into the local
        radix index (see InferenceEngine.prefix_install)."""
        req = {"model": model} if model is not None else {}
        return self._engine_for(req).prefix_install(tokens, payload)

    def loaded_variants(self) -> list:
        return self._mux.loaded_models() if self._mux is not None else []

    def multiplex_stats(self) -> Optional[dict]:
        return self._mux.stats() if self._mux is not None else None

    def drain(self) -> None:
        """Replica drain hook (DeploymentState.drain_replicas): stop
        admitting — queued engine waiters are handed back as
        EngineDrainingError for re-routing — while in-flight slots
        decode to completion.  The controller polls ``fleet_stats``
        until active_slots reaches 0 (or the drain deadline) before
        tearing the replica down."""
        self._draining = True
        for eng in self._engines():
            eng.drain()

    def health(self):
        st = self.fleet_stats()
        return not st["stopped"]

    def teardown(self):
        """Replica teardown hook (DeploymentState.scale_to): stop the
        engine loop(s) so a scaled-down replica releases its KV pool
        and thread instead of leaking them."""
        self._closed = True
        if self._mux is not None:
            self._mux.unload_all()
        elif self.engine is not None:
            self.engine.shutdown(timeout=2.0)

    def __del__(self):   # best-effort: teardown() is the real path
        try:
            for eng in self._engines():
                eng.shutdown(timeout=0.5)
        except Exception:
            pass


def build_gpt_deployment(*, name: str = DEFAULT_ROUTE,
                         cfg=None,
                         engine_cfg: Optional[EngineConfig] = None,
                         seed: int = 0,
                         num_replicas: int = 1,
                         max_concurrent_queries: int = 64,
                         autoscaling: Optional[AutoscalingConfig] = None,
                         params=None,
                         variants: Optional[dict] = None,
                         multiplex_capacity: int = 2,
                         warm_on_init: bool = False,
                         mesh=None, rules=None) -> Deployment:
    """A ready-to-``serve.run`` deployment wrapping GPTServer.  Route is
    /<name>/... — the default name "v1" makes POST /v1/generate work.

    Pass ``autoscaling`` (e.g. AutoscalingConfig(min_replicas=1,
    max_replicas=4, target_ongoing_requests=max_slots)) to scale the
    replica set on queue depth; each new replica brings its own engine
    and cache pool.  ``variants`` ({model_id: seed}) turns each replica
    into a model-multiplexed server: at most ``multiplex_capacity``
    variants resident per replica, LRU-evicted; requests pick one with
    the ``model`` field.  ``warm_on_init`` runs every program of a pass
    once at replica construction (``InferenceEngine.warm_up``) so
    scale-ups don't serve cold.  ``mesh`` (+
    optional ``rules``) serves every replica tensor-parallel: params
    and KV pools heads-sharded over the mesh's ``tp`` axis, one decode
    program shared across replicas of the same geometry.
    """
    return Deployment(
        GPTServer,
        DeploymentOptions(name=name, num_replicas=num_replicas,
                          max_concurrent_queries=max_concurrent_queries,
                          autoscaling=autoscaling),
        init_args=(),
        init_kwargs=dict(cfg=cfg, engine_cfg=engine_cfg, seed=seed,
                         params=params, variants=variants,
                         multiplex_capacity=multiplex_capacity,
                         warm_on_init=warm_on_init,
                         mesh=mesh, rules=rules))


def parse_stream_chunks(raw: bytes) -> list[dict]:
    """Decode the chunked-transfer JSON documents a streamed /v1/generate
    response carries (helper for clients and tests: one dict per chunk,
    in arrival order)."""
    out = []
    rest = raw
    while rest:
        head, _, rest = rest.partition(b"\r\n")
        if not head:
            continue
        n = int(head, 16)
        if n == 0:
            break
        out.append(json.loads(rest[:n]))
        rest = rest[n:]
        if rest.startswith(b"\r\n"):
            rest = rest[2:]
    return out
