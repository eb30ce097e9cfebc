"""Tensor-parallel paged decode: greedy token parity on real >1-device
tp meshes (virtual CPU devices — conftest forces 8 via
``XLA_FLAGS=--xla_force_host_platform_device_count``), the compile
cache's mesh-identity keying, and the pin that dense ``mesh=None``
builds stay annotation-free (pre-change behavior, byte-identical
jaxpr-wise).

Scenario matrix per ISSUE 17: {prefix reuse, chunked prefill,
preemption, speculation} × {2, 4}-device tp meshes, MoE decode parity
vs the training-forward oracle, donated-pool recovery under a mesh,
and the stats/metrics serving-geometry surface.  Every multi-device
test skips with a reason when forcing virtual devices was unavailable
(e.g. the backend initialized before conftest's flag).

The oracle is the full-recompute ``gpt.generate`` — the same greedy
parity contract tests/test_paged_cache.py pins for ``mesh=None``.
Everything runs tiny at f32 (argmax parity must not hinge on bf16
ties); prompts/max_new stay small because these ride tier-1.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.inference import EngineConfig, InferenceEngine
from ray_tpu.models import gpt
from ray_tpu.parallel.mesh import create_mesh


@pytest.fixture(scope="module")
def cfg():
    return gpt.GPTConfig.tiny(dtype=jnp.float32, max_seq=64)


@pytest.fixture(scope="module")
def params(cfg):
    return gpt.init_params(cfg, jax.random.PRNGKey(0))


def _ref_tokens(params, cfg, prompt, max_new):
    out = gpt.generate(params, cfg, jnp.asarray([prompt], jnp.int32),
                       max_new=max_new, temperature=0.0)
    return np.asarray(out)[0, len(prompt):].tolist()


def _tp_mesh(n):
    """A {tp: n} mesh over the first n virtual CPU devices, or skip
    with the reason when the device-count flag could not take effect."""
    if jax.device_count() < n:
        pytest.skip(
            f"need {n} CPU devices for a tp={n} mesh, have "
            f"{jax.device_count()} (XLA_FLAGS "
            f"--xla_force_host_platform_device_count unavailable — "
            f"backend initialized before conftest could force it)")
    return create_mesh({"tp": n}, devices=jax.devices()[:n])


# module-scoped meshes: every engine of one geometry reuses ONE mesh
# object, so the mesh-identity compile cache (decode._cached) turns the
# whole file into one compile set per geometry instead of one per test
@pytest.fixture(scope="module")
def mesh2():
    return _tp_mesh(2)


@pytest.fixture(scope="module")
def mesh4():
    return _tp_mesh(4)


def _mesh_for(n, mesh2, mesh4):
    return mesh2 if n == 2 else mesh4


# ------------------------------------------------------- compile cache

def test_fn_cache_hits_on_mesh_identity(cfg):
    """The r17 satellite fix: a meshed build must HIT the compile cache
    when the same mesh object comes back (a sharded fleet replica would
    otherwise pay N identical multi-second compiles — the exact
    regression PR 7 fixed for the no-mesh path).  Keyed on
    (id(mesh), shape): same object → same compiled fn; a DIFFERENT mesh
    object (even of identical shape) → a fresh build."""
    from ray_tpu.inference.decode import make_paged_decode_step
    mesh_a = _tp_mesh(2)
    fn1 = make_paged_decode_step(cfg, block_size=8, n_table=8,
                                 mesh=mesh_a)
    fn2 = make_paged_decode_step(cfg, block_size=8, n_table=8,
                                 mesh=mesh_a)
    assert fn1 is fn2, "same mesh object missed the compile cache"
    # jax interns value-equal Mesh objects, so a replica REBUILDING the
    # same-geometry mesh gets the same object back — and therefore the
    # same compiled fn (the fleet-scale-out case the fix is for)
    mesh_b = create_mesh({"tp": 2}, devices=jax.devices()[:2])
    assert mesh_b is mesh_a
    assert make_paged_decode_step(cfg, block_size=8, n_table=8,
                                  mesh=mesh_b) is fn1
    # a genuinely DIFFERENT mesh (same shape, different device order)
    # must not collide
    mesh_c = create_mesh({"tp": 2}, devices=jax.devices()[:2][::-1])
    fn3 = make_paged_decode_step(cfg, block_size=8, n_table=8,
                                 mesh=mesh_c)
    assert fn3 is not fn1, \
        "distinct meshes must not collide in the compile cache"
    # the no-mesh entry is its own key, untouched by meshed builds
    fn_none = make_paged_decode_step(cfg, block_size=8, n_table=8)
    assert fn_none is make_paged_decode_step(cfg, block_size=8,
                                             n_table=8)
    assert fn_none is not fn1


def test_dense_no_mesh_builds_are_annotation_free(cfg, params):
    """Pin that ``mesh=None`` builds are the PRE-CHANGE programs: the
    sharding annotations added for tensor parallelism compile away to
    literally nothing without a mesh (gpt._constrain returns its input
    unchanged), so the traced jaxpr carries zero sharding_constraint
    equations and zero collectives — dense single-device configs are
    byte-identical to what shipped before this change."""
    from ray_tpu.inference.decode import (make_chunk_prefill_fn,
                                          make_paged_decode_step)
    step = make_paged_decode_step(cfg, block_size=8, n_table=8)
    from ray_tpu.inference.cache import PoolLayout

    def zeros_pool(shards):
        return jnp.zeros(PoolLayout(cfg.n_layers, 17, 8, cfg.n_heads,
                                    cfg.head_dim, shards).shape,
                         jnp.float32)

    pool = zeros_pool(1)
    feed = jnp.zeros(2, jnp.int32)          # the rows' next tokens
    jaxpr = str(jax.make_jaxpr(step)(
        params, pool, pool, feed, jnp.zeros((2, 8 + 3), jnp.int32)))
    for prim in ("sharding_constraint", "psum", "all_gather",
                 "all_to_all"):
        assert prim not in jaxpr, \
            f"mesh=None decode step grew a {prim} equation"
    chunk = make_chunk_prefill_fn(cfg, chunk=16, block_size=8, n_table=8)
    jaxpr_c = str(jax.make_jaxpr(chunk)(
        params, pool, pool, feed, jnp.zeros(8 + 16 + 3, jnp.int32)))
    assert "sharding_constraint" not in jaxpr_c
    # positive control: the SAME builder with a mesh is annotated (the
    # assertion above is meaningful, not vacuously matching a renamed
    # primitive)
    mesh = _tp_mesh(2)
    step_sh = make_paged_decode_step(cfg, block_size=8, n_table=8,
                                     mesh=mesh)
    sh_pool = zeros_pool(2)
    jaxpr_sh = str(jax.make_jaxpr(step_sh)(
        params, sh_pool, sh_pool, feed, jnp.zeros((2, 8 + 3), jnp.int32)))
    assert "sharding_constraint" in jaxpr_sh


# ------------------------------------------------- sharded greedy parity

@pytest.mark.parametrize("n", [2, 4])
def test_sharded_parity_prefix_and_chunked(n, cfg, params, mesh2, mesh4):
    """Greedy tokens on a tp mesh match the full-recompute oracle
    token-for-token: cold full prefill, radix prefix reuse (replicated
    host-side tables adopting heads-sharded blocks), and chunked
    prefill under concurrency.  Also pins the serving-geometry stats
    surface: tp_shards/mesh_devices real, block counts global AND
    per-device (equal by construction — heads are what's split)."""
    mesh = _mesh_for(n, mesh2, mesh4)
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=2, kv_block_size=8, prefill_chunk=16), mesh=mesh)
    try:
        st = eng.stats()
        assert st["mesh_devices"] == n
        assert st["tp_shards"] == n
        assert st["mesh_axes"] == {"tp": n}
        assert st["blocks_per_device"] == st["blocks_total"]
        assert st["cache_bytes_per_device"] == st["cache_bytes"] // n
        spec = eng.pool.k.sharding.spec
        assert "tp" in str(spec[-1]), \
            f"pool heads are not tp-sharded: {spec}"

        warm = [7, 3, 1, 4, 1, 5, 9, 2, 6]
        got = eng.generate(warm, max_new=6, timeout=300)
        assert got == _ref_tokens(params, cfg, warm, 6)
        # prefix reuse: the same prompt adopts cached blocks
        assert eng.generate(warm, max_new=6, timeout=300) == got
        assert eng.stats()["prefix_hit_tokens"] > 0
        # chunked prefill: two LONG prompts in flight together force
        # the interleaved chunk path; parity must hold for both
        rng = np.random.default_rng(7)
        jobs = [(p := rng.integers(0, cfg.vocab_size, 24).tolist(),
                 eng.submit(p, max_new=6)) for _ in range(2)]
        for p, handle in jobs:
            assert handle.result(timeout=300) \
                == _ref_tokens(params, cfg, p, 6)
    finally:
        eng.shutdown()


@pytest.mark.parametrize("n", [2, 4])
def test_sharded_parity_under_preemption(n, cfg, params, mesh2, mesh4):
    """Block-pressure preemption on a tp mesh: requeue + resume with
    emitted tokens folded into the prompt, every stream still
    oracle-exact.  The preemption logic is host-side and
    shard-oblivious — this pins that the sharded pool's donate/commit
    cycle keeps it that way."""
    mesh = _mesh_for(n, mesh2, mesh4)
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=4, max_seq=32, kv_block_size=8, n_blocks=6,
        prefill_chunk=16), mesh=mesh)
    try:
        rng = np.random.default_rng(1)
        jobs = []
        for _ in range(5):
            p = rng.integers(0, cfg.vocab_size,
                             int(rng.integers(6, 18))).tolist()
            jobs.append((p, eng.submit(p, max_new=8)))
        for p, h in jobs:
            assert h.result(timeout=300) \
                == _ref_tokens(params, cfg, p, 8)
        st = eng.stats()
        assert st["preemptions"] > 0, \
            "6 blocks under 5 concurrent requests never preempted"
    finally:
        eng.shutdown()


@pytest.mark.parametrize("n", [2, 4])
def test_sharded_parity_speculative_ngram(n, cfg, params, mesh2, mesh4):
    """Draft-then-verify on a tp mesh (n-gram drafter): the widened
    verify step runs per-device attention over local heads and the
    greedy accept rule stays token-identical to non-speculative decode
    — so to the oracle."""
    mesh = _mesh_for(n, mesh2, mesh4)
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=2, kv_block_size=8, prefill_chunk=16,
        speculate="ngram", speculate_k=4), mesh=mesh)
    try:
        # repetitive prompt: the n-gram drafter actually drafts
        p = [5, 6, 7, 5, 6, 7, 5, 6, 7]
        assert eng.generate(p, max_new=8, timeout=300) \
            == _ref_tokens(params, cfg, p, 8)
        assert eng.stats()["spec_drafted_tokens"] > 0
    finally:
        eng.shutdown()


def test_sharded_parity_speculative_self(cfg, params, mesh2):
    """Truncated-layer self-draft burst on a tp mesh: the drafter
    writes layers < draft_layers straight into the heads-sharded pool
    and verify overwrites every drafted position — parity holds."""
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=2, kv_block_size=8, prefill_chunk=16,
        speculate="self", speculate_k=4, draft_layers=1), mesh=mesh2)
    try:
        p = [9, 8, 7, 6, 5, 4]
        assert eng.generate(p, max_new=8, timeout=300) \
            == _ref_tokens(params, cfg, p, 8)
        assert eng.stats()["spec_drafted_tokens"] > 0
    finally:
        eng.shutdown()


# ------------------------------------- the programs' own greedy tokens

def test_in_program_argmax_over_sharded_logits_dp_tp(cfg, params):
    """dp2 x tp2: the decode step's and the chunk's greedy tokens, and
    those of the ONE program that runs both in a pass that holds both
    (ISSUE 41: its window is one batch row, its one-token kernel runs
    per shard on the rows' queries), are
    taken inside the program, over logits whose vocabulary is split
    across the tp shards, and equal the host's argmax of the gathered
    logits, ties included.  Every vocabulary row has a twin in the
    OTHER shard here (the tied head's upper half is a copy of its
    lower), so every maximum is an exact tie across shards, and the
    lowest index has to win it as ``np.argmax`` and ``gpt.sample_token``
    have it."""
    if jax.device_count() < 4:
        pytest.skip(f"need 4 CPU devices, have {jax.device_count()}")
    mesh = create_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    half = cfg.vocab_size // 2
    wte = params["wte"]
    twins = {**params, "wte": wte.at[half:].set(wte[:half])}
    eng = InferenceEngine(twins, cfg, EngineConfig(
        max_slots=4, kv_block_size=8, prefill_chunk=8), mesh=mesh)
    seen = {"step": 0, "chunk": 0, "step_chunk": 0}

    def checked(kind, program):
        def run(params_, k, v, feed, packed):
            logits, greedy, k, v, feed = program(params_, k, v, feed,
                                                 packed)
            assert feed.sharding.is_fully_replicated
            assert "tp" in str(logits.sharding.spec[-1])
            host = np.asarray(logits)
            np.testing.assert_array_equal(host[:, :half], host[:, half:])
            if kind == "chunk":
                host = host[packed[-1] - 1][None]     # n_valid's row
            assert greedy.dtype == jnp.int32
            assert np.asarray(greedy).tolist() \
                == host.argmax(-1).tolist()
            assert (np.asarray(greedy) < half).all()
            seen[kind] += 1
            return logits, greedy, k, v, feed
        return run
    for kind in seen:
        setattr(eng, "_" + kind, checked(kind, getattr(eng, "_" + kind)))
    try:
        rng = np.random.default_rng(2)
        jobs = [(p := rng.integers(0, cfg.vocab_size, n).tolist(),
                 eng.submit(p, max_new=6)) for n in (5, 13, 19)]
        for p, handle in jobs:
            assert handle.result(timeout=300) \
                == _ref_tokens(twins, cfg, p, 6)
        st = eng.stats()
        assert st["tokens_sampled"] == 0
        assert st["tokens_greedy_on_device"] == 18
    finally:
        eng.shutdown()
    # a pass's last chunk rode the step wherever a row was decoding
    assert seen["step"] + seen["step_chunk"] >= 5
    assert seen["chunk"] + seen["step_chunk"] == 1 + 2 + 3
    assert seen["step_chunk"] == st["chunks_in_step"] >= 1


# ----------------------------------------------------------- MoE decode

def test_sharded_moe_parity(mesh2):
    """The MoE wall is down ON A MESH too: paged decode + chunked
    prefill over an MoE config dispatch experts via gpt._moe_mlp
    (capacity_factor=4.0 ≥ E/k so capacity never binds — the exact
    regime where incremental windows route like the full-sequence
    oracle) and match the training-forward oracle token-for-token."""
    moe_cfg = gpt.GPTConfig.tiny_moe(capacity_factor=4.0)
    moe_params = gpt.init_params(moe_cfg, jax.random.PRNGKey(3))
    eng = InferenceEngine(moe_params, moe_cfg, EngineConfig(
        max_slots=2, kv_block_size=8, prefill_chunk=16), mesh=mesh2)
    try:
        p = [11, 12, 13, 14, 15]
        assert eng.generate(p, max_new=8, timeout=300) \
            == _ref_tokens(moe_params, moe_cfg, p, 8)
    finally:
        eng.shutdown()


# ------------------------------------------------------------- recovery

def test_sharded_recovery_reallocates_every_shard(cfg, params, mesh2):
    """Donated-pool recovery under a mesh: a step failure fails the
    in-flight requests, and reset() reallocates the pool SHARDED (every
    device's shard, same NamedSharding the compiled steps donate-commit
    into) — the engine keeps serving with oracle parity."""
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=2, kv_block_size=8, prefill_chunk=16), mesh=mesh2)
    try:
        warm = [4, 8, 15, 16, 23, 42]
        assert eng.generate(warm, max_new=4, timeout=300) \
            == _ref_tokens(params, cfg, warm, 4)
        sharding_before = eng.pool.k.sharding

        real_step = eng._step
        boom = {"armed": True}

        def failing_step(*a):
            if boom.pop("armed", False):
                raise RuntimeError("injected sharded step failure")
            return real_step(*a)

        eng._step = failing_step
        bad = eng.submit([1, 2], max_new=8)
        with pytest.raises(RuntimeError, match="injected sharded"):
            bad.result(timeout=60)
        st = eng.stats()
        assert st["blocks_free"] == st["blocks_total"]
        assert eng.pool.k.sharding.is_equivalent_to(
            sharding_before, eng.pool.k.ndim), \
            "recovery reallocated the pool with a different sharding"
        assert eng.generate(warm, max_new=4, timeout=300) \
            == _ref_tokens(params, cfg, warm, 4)
    finally:
        eng.shutdown()


# ----------------------------------------------------- geometry surface

def test_sharded_metrics_and_timeline_geometry(cfg, params, mesh2):
    """The /metrics gauges and timeline slice args carry the serving
    geometry: mesh_devices/tp_shards real on a meshed engine, and the
    flight-recorder engine_request event (what ``ray_tpu timeline``
    renders as slice args) includes them."""
    from ray_tpu import inference
    from ray_tpu.core import flight_recorder as fr

    eng = InferenceEngine(params, cfg, EngineConfig(
        max_slots=2, kv_block_size=8), mesh=mesh2)
    try:
        rec = fr.enable()
        try:
            eng.generate([1, 2, 3], max_new=4, timeout=300)
            events = [e for e in rec.export_ingress()
                      if e.get("kind") == "engine_request"]
        finally:
            fr.disable()
        assert events, "no engine_request event recorded"
        assert events[-1]["mesh_devices"] == 2
        assert events[-1]["tp_shards"] == 2

        snap = inference.metrics_snapshot()
        by_name = {t[0]: t[3] for t in snap}
        key = ((("engine", eng.name),)
               + tuple(sorted(eng.labels.items())))
        assert by_name["ray_tpu_inference_mesh_devices"][key] == 2.0
        assert by_name["ray_tpu_inference_tp_shards"][key] == 2.0
    finally:
        eng.shutdown()
