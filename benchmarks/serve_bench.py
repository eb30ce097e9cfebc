"""Inference serving benchmark → SERVE_r17.json.

Same-box, same-run A/B receipts for the inference engine, round 17:
the r16 arms (paged KV cache vs the r10/r14 slot engine, speculative
decoding) plus TENSOR-PARALLEL SHARDED DECODE: the same request set on
the paged engine unmeshed vs on a tp=2 mesh, in one process.

Arms:

  * continuous_batching   — r10's gate on the paged engine: the same
    request set sequential (max_slots=1) vs concurrent (max_slots=8);
    ratio >= 2.0.
  * shared_prefix         — N requests over K distinct prompt HEADS
    (the system-prompt shape): slot engine re-prefills every prompt in
    full; the paged engine adopts the cached head blocks by refcount
    and prefills only the divergent tail.  Gate: paged/slot req/s
    ratio >= 1.5 at equal pool bytes.
  * mixed_storm           — long-prompt storm over a mixed-length
    request set at EQUAL POOL BYTES: the slot engine's worst-case
    stripes cap it at pool_tokens/max_seq concurrent requests; the
    paged engine admits by actual block usage (and chunked prefill
    keeps short requests' first tokens flowing while long prompts
    prefill).  Gates: strictly higher peak concurrent requests, zero
    silently-dropped requests in BOTH arms.
  * speculation           — the SAME shared-prefix + trace-replay-mix
    request set on the paged engine with ``speculate=None`` (baseline)
    vs the n-gram prompt-lookup drafter vs the truncated-layer
    self-drafter.  Gates: mean emitted tokens per (row, step) > 1.5 on
    at least one speculative arm, and that arm's TTFT p99 AND ITL p99
    beat the non-speculative baseline.  Output is token-exact by the
    greedy accept rule, so this is pure latency, not quality trade.
  * sharded_decode        — the same shared-prefix request set on the
    paged engine unmeshed vs sharded over a tp=2 mesh (heads-sharded
    block pools, replicated tables, one collective per layer).  On
    this box the "mesh" is virtual CPU devices carved from one host
    (``--xla_force_host_platform_device_count``), so the sharded arm
    is SLOWER — there is no extra silicon, only added collectives.
    The gate is therefore token EXACTNESS plus the per-device
    accounting (bytes_per_device == total/tp), not speed; the speed
    story needs real chips and is ROADMAP item 1's next receipt.
    BOTH halves run inside one ``--shard-child`` subprocess: the
    parent's backend initializes on one device, and forcing 8 virtual
    devices process-wide measurably shifts the OTHER arms' in-run
    ratios (the spec baseline sped up ~30% under it), so the device
    split is confined to the child while the A/B itself stays
    same-process.

Every arm now records ITL (inter-token latency) p50/p99 alongside
TTFT.  ITL here is the normalized per-request definition (NVIDIA
GenAI-Perf / vLLM "TPOT"): (e2e - TTFT) / (generated tokens - 1) per
request — the steady-state per-token rate each stream experiences,
which is the number speculation actually moves.  The raw consecutive
token-arrival gaps are reported too (gap_p50/p99): under burst
emission a speculative pass lands k tokens at once, so the raw-gap
p99 degenerates to the pass period and measures emission granularity,
not stream rate.

Both halves of every arm run in the same process minutes apart, so
only in-run ratios are portable (PERF.md box-variance caveat); loadavg
is stamped per phase.

Run:  JAX_PLATFORMS=cpu python benchmarks/serve_bench.py [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

ROUND = 17


def _pct(xs, p):
    xs = sorted(xs)
    if not xs:
        return 0.0
    i = min(len(xs) - 1, max(0, int(round(p / 100 * (len(xs) - 1)))))
    return xs[i]


def make_requests(n, *, seed, vocab, prompt_len, max_new):
    import numpy as np
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        pl = int(rng.integers(prompt_len // 2, prompt_len + 1))
        out.append((rng.integers(0, vocab, pl).tolist(),
                    int(rng.integers(max_new // 2, max_new + 1))))
    return out


def make_shared_prefix_requests(n, *, seed, vocab, heads, head_len,
                                tail_len, max_new):
    """N requests over K distinct prompt heads (shared system prompts),
    each with a divergent random tail."""
    import numpy as np
    rng = np.random.default_rng(seed)
    head_toks = [rng.integers(0, vocab, head_len).tolist()
                 for _ in range(heads)]
    out = []
    for i in range(n):
        head = head_toks[i % heads]
        tail = rng.integers(0, vocab, tail_len).tolist()
        out.append((head + tail, max_new))
    return out


def make_mixed_requests(*, seed, vocab, n_short, n_long, short_len,
                        long_len, short_new, long_new):
    """Short interactive requests interleaved with long-prompt storms."""
    import numpy as np
    rng = np.random.default_rng(seed)
    out = []
    longs = set(np.linspace(0, n_short + n_long - 1, n_long).astype(int))
    for i in range(n_short + n_long):
        if i in longs:
            pl = int(rng.integers(long_len // 2, long_len + 1))
            out.append((rng.integers(0, vocab, pl).tolist(), long_new))
        else:
            pl = int(rng.integers(short_len // 2, short_len + 1))
            out.append((rng.integers(0, vocab, pl).tolist(), short_new))
    return out


def run_engine_arm(params, cfg, reqs, engine_cfg, *, concurrent=True):
    """Drive one engine over the request set; returns throughput +
    latency + capacity stats.  ``concurrent=False`` = strict
    one-at-a-time (the sequential baseline)."""
    from ray_tpu.inference import InferenceEngine
    eng = InferenceEngine(params, cfg, engine_cfg)
    # warm ALL compiled programs off the clock with a dedicated prompt
    # (NOT from the request set, so the timed region's prefix hits are
    # earned, not inherited from warmup): the first run takes the cold
    # full-width prefill, the second hits the prefix cache and takes
    # the chunked path; both compile the decode step
    wp = [(i % 7) + 1 for i in range(int(cfg.max_seq) * 3 // 4)]
    eng.generate(wp, max_new=2, timeout=600)
    eng.generate(wp, max_new=2, timeout=600)
    if engine_cfg.speculate is not None:
        # max_new=2 never speculates (prefill emits the first token, so
        # the draft budget is min(k, 2-1-1) = 0) and the verify/draft
        # programs would compile INSIDE the timed region; the repeating
        # warmup prompt guarantees the n-gram drafter fires too
        eng.generate(wp, max_new=engine_cfg.speculate_k + 4, timeout=600)
    lat, ttft, itl, gap, toks, errors = [], [], [], [], 0, 0

    def _collect(h, out):
        lat.append(h.finished_s - h.created_s)
        ttft.append(h.first_token_s - h.created_s)
        # ITL = normalized per-request (e2e - TTFT)/(tokens - 1), the
        # stream's steady-state token period; raw consecutive arrival
        # gaps go in ``gap`` (burst emission makes raw-gap percentiles
        # measure emission granularity, not rate — see module doc)
        if len(h.token_times) > 1:
            itl.append((h.finished_s - h.first_token_s)
                       / (len(h.token_times) - 1))
        gap.extend(b - a for a, b in zip(h.token_times, h.token_times[1:]))
        return len(out)

    t0 = time.perf_counter()
    if concurrent:
        handles = [eng.submit(p, max_new=m) for p, m in reqs]
        for h in handles:
            try:
                out = h.result(timeout=900)
            except Exception:
                errors += 1
                continue
            toks += _collect(h, out)
    else:
        for p, m in reqs:
            h = eng.submit(p, max_new=m)
            try:
                out = h.result(timeout=900)
            except Exception:
                errors += 1
                continue
            toks += _collect(h, out)
    wall = time.perf_counter() - t0
    st = eng.stats()
    eng.shutdown()
    out = {
        "requests": len(reqs),
        "completed": len(lat),
        "errors": errors,
        "dropped": len(reqs) - len(lat) - errors,   # MUST be 0
        "wall_s": round(wall, 3),
        "req_s": round(len(lat) / wall, 2),
        "tokens_s": round(toks / wall, 1),
        "p50_s": round(_pct(lat, 50), 4),
        "p99_s": round(_pct(lat, 99), 4),
        "ttft_p50_s": round(_pct(ttft, 50), 4),
        "ttft_p99_s": round(_pct(ttft, 99), 4),
        "itl_p50_s": round(_pct(itl, 50), 4),
        "itl_p99_s": round(_pct(itl, 99), 4),
        "gap_p50_s": round(_pct(gap, 50), 4),
        "gap_p99_s": round(_pct(gap, 99), 4),
        "tokens_per_step": round(st["tokens_per_step"], 3),
        "batch_occupancy": round(st["batch_occupancy"], 3),
        "max_slots": st["max_slots"],
        "peak_active_requests": st["peak_active_requests"],
        "cache_bytes": st["cache_bytes"],
        "paged": st["paged"],
    }
    if st["paged"]:
        out.update({
            "pool_tokens": st["blocks_total"] * st["block_size"],
            "prefix_hit_rate": round(st["prefix_hit_rate"], 4),
            "prefix_hit_tokens": st["prefix_hit_tokens"],
            "preemptions": st["preemptions"],
        })
    else:
        out["pool_tokens"] = st["max_slots"] * engine_cfg_max_seq(
            engine_cfg, cfg)
    if st["speculate"] is not None:
        out.update({
            "speculate": st["speculate"],
            "spec_drafted_tokens": st["spec_drafted_tokens"],
            "spec_accepted_tokens": st["spec_accepted_tokens"],
            "spec_accept_rate": round(st["spec_accept_rate"], 4),
            "spec_passes": st["spec_passes"],
        })
    return out


def engine_cfg_max_seq(ecfg, cfg):
    return int(ecfg.max_seq or cfg.max_seq)


def _bench_model():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    # big enough that compute (not per-call dispatch) dominates — the
    # prefill/decode cost ratios then resemble the real serving shape
    cfg = gpt.GPTConfig(vocab_size=512, max_seq=256, d_model=256,
                        n_heads=8, n_layers=6, d_ff=1024, remat=False,
                        dtype=jnp.float32)
    return cfg, gpt.init_params(cfg, jax.random.PRNGKey(0))


def _make_phase(phases):
    def phase(name, fn):
        l0 = os.getloadavg()[0]
        t0 = time.perf_counter()
        result = fn()
        phases[name] = {
            "loadavg_1m_before": round(l0, 2),
            "loadavg_1m_after": round(os.getloadavg()[0], 2),
            "phase_wall_s": round(time.perf_counter() - t0, 1),
        }
        return result
    return phase


def run_exact_arm(params, cfg, reqs, engine_cfg, *, mesh=None):
    """Drive one engine over the request set and keep every output
    token: the sharded A/B gate is exactness, so the tokens ARE the
    measurement.  Returns (stats, list-of-token-lists)."""
    from ray_tpu.inference import InferenceEngine
    eng = InferenceEngine(params, cfg, engine_cfg, mesh=mesh)
    wp = [(i % 7) + 1 for i in range(int(cfg.max_seq) * 3 // 4)]
    eng.generate(wp, max_new=2, timeout=600)   # compile off the clock
    eng.generate(wp, max_new=2, timeout=600)   # chunked-path compile
    t0 = time.perf_counter()
    handles = [eng.submit(p, max_new=m) for p, m in reqs]
    outs = [list(h.result(timeout=900)) for h in handles]
    wall = time.perf_counter() - t0
    st = eng.stats()
    eng.shutdown()
    stats = {
        "requests": len(reqs),
        "wall_s": round(wall, 3),
        "req_s": round(len(reqs) / wall, 2),
        "tokens_s": round(sum(len(o) for o in outs) / wall, 1),
        "mesh_devices": st.get("mesh_devices", 1),
        "tp_shards": st.get("tp_shards", 1),
        "blocks_total": st["blocks_total"],
        "blocks_per_device": st.get("blocks_per_device"),
        "cache_bytes": st["cache_bytes"],
        "cache_bytes_per_device": st.get("cache_bytes_per_device"),
        "prefix_hit_tokens": st["prefix_hit_tokens"],
    }
    return stats, outs


def run_sharded_ab(q, phase):
    """Arm 4, both halves — runs inside the ``--shard-child``
    subprocess, whose backend was forced onto 8 virtual CPU devices
    before init (the parent's stays on one)."""
    import jax

    from ray_tpu.inference import EngineConfig
    from ray_tpu.parallel.mesh import create_mesh

    assert jax.device_count() >= 2, \
        "shard child must run under a forced multi-device backend"
    cfg, params = _bench_model()
    tp_mesh = create_mesh({"tp": 2}, devices=jax.devices()[:2])
    reqs = make_shared_prefix_requests(
        6 if q else 12, seed=29, vocab=cfg.vocab_size, heads=3,
        head_len=96, tail_len=8, max_new=8)
    shard_cfg = EngineConfig(max_slots=4, kv_block_size=16,
                             prefill_chunk=16)
    sh_single, out_a = phase("sharded_single", lambda: run_exact_arm(
        params, cfg, reqs, shard_cfg))
    sh_tp2, out_b = phase("sharded_tp2", lambda: run_exact_arm(
        params, cfg, reqs, shard_cfg, mesh=tp_mesh))
    return {
        "workload": {"n": len(reqs), "heads": 3, "head_len": 96,
                     "tail_len": 8, "max_new": 8},
        "note": "tp=2 over virtual CPU devices on ONE host: no "
                "extra silicon, collectives are pure overhead — "
                "gates pin exactness + per-device accounting, "
                "not speed.  This arm ALWAYS runs in a forced-CPU "
                "child process and can never see a chip, whatever "
                "the parent runs on; the on-chip tp=2 check is "
                "`python chip_smoke.py --chips 4`",
        "platform": jax.devices()[0].platform,
        "single_device": sh_single,
        "tp2": sh_tp2,
        "token_exact": out_a == out_b,
    }


_CHILD_MARK = "SHARD_CHILD_JSON:"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="SERVE_r17.json")
    ap.add_argument("--shard-child", action="store_true",
                    help="internal: run only the sharded A/B and emit "
                         "its section as marked JSON on stdout")
    args = ap.parse_args()
    q = args.quick

    if args.shard_child:
        child_phases = {}
        section = run_sharded_ab(q, _make_phase(child_phases))
        print(_CHILD_MARK + json.dumps({"section": section,
                                        "phases": child_phases}))
        return 0

    import jax

    from ray_tpu._compile_cache import enable_compile_cache
    from ray_tpu.inference import EngineConfig

    enable_compile_cache()
    cfg, params = _bench_model()

    phases = {}
    phase = _make_phase(phases)

    # ---- arm 0: the r10 acceptance, now on the paged engine ------------
    reqs0 = make_requests(8 if q else 24, seed=7, vocab=cfg.vocab_size,
                          prompt_len=16, max_new=16 if q else 24)
    seq_base = phase("sequential", lambda: run_engine_arm(
        params, cfg, reqs0, EngineConfig(max_slots=1), concurrent=False))
    cont = phase("continuous", lambda: run_engine_arm(
        params, cfg, reqs0, EngineConfig(max_slots=8)))

    # ---- arm 1: shared-prefix (N requests over K prompt heads — the
    # shared-system-prompt shape: long head, short divergent tail,
    # short completion).  Equal pool bytes: slot 8 x 256 stripes ==
    # paged 128 x 16 blocks.
    reqs1 = make_shared_prefix_requests(
        12 if q else 24, seed=11, vocab=cfg.vocab_size, heads=4,
        head_len=192, tail_len=8, max_new=4)
    sp_slot = phase("shared_prefix_slot", lambda: run_engine_arm(
        params, cfg, reqs1, EngineConfig(max_slots=8, paged=False)))
    sp_paged = phase("shared_prefix_paged", lambda: run_engine_arm(
        params, cfg, reqs1, EngineConfig(max_slots=8, kv_block_size=16,
                                         prefill_chunk=16)))

    # ---- arm 2: long-prompt storm over a mixed-length set at EQUAL
    # pool bytes: slot worst-case stripes allow 4 concurrent (4 x 256);
    # the paged engine spends the same 1024 tokens by actual usage over
    # 12 decode rows, chunk-prefilling the long prompts
    reqs2 = make_mixed_requests(
        seed=13, vocab=cfg.vocab_size,
        n_short=8 if q else 18, n_long=3 if q else 6,
        short_len=16, long_len=200, short_new=8, long_new=8)
    ms_slot = phase("mixed_storm_slot", lambda: run_engine_arm(
        params, cfg, reqs2, EngineConfig(max_slots=4, paged=False)))
    ms_paged = phase("mixed_storm_paged", lambda: run_engine_arm(
        params, cfg, reqs2, EngineConfig(max_slots=12, kv_block_size=16,
                                         n_blocks=64, prefill_chunk=16)))

    # ---- arm 3: speculative decoding A/B — the SAME shared-prefix +
    # trace-replay-mix request set, paged engine, speculate off vs the
    # n-gram prompt-lookup drafter vs the truncated-layer self-drafter.
    # All-at-once submission (closed-loop storm): high occupancy is the
    # regime where the batch-coverage gate lets speculation run, and
    # queueing pressure is where its extra tokens per pass move the
    # tails — drained backlog (TTFT p99) and per-stream token period
    # (ITL p99, the normalized definition — see module doc).
    import random as _random
    reqs3 = (make_shared_prefix_requests(
                 12 if q else 20, seed=17, vocab=cfg.vocab_size, heads=4,
                 head_len=96, tail_len=8, max_new=32 if q else 40)
             + make_mixed_requests(
                 seed=19, vocab=cfg.vocab_size,
                 n_short=6 if q else 10, n_long=2 if q else 4,
                 short_len=16, long_len=120,
                 short_new=32 if q else 40, long_new=32 if q else 40))
    _random.Random(23).shuffle(reqs3)     # interleave heads/shorts/longs

    def spec_cfg(**kw):
        return EngineConfig(max_slots=8, kv_block_size=16,
                            prefill_chunk=16, **kw)

    spec_off = phase("speculate_off", lambda: run_engine_arm(
        params, cfg, reqs3, spec_cfg()))
    # n-gram drafting is free (host-side lookup, no draft model), so a
    # wide window costs only verify lanes — and its acceptance is high
    # when it fires at all; the self-drafter pays a fused k-step draft
    # burst per pass, so its window stays narrower
    spec_ngram = phase("speculate_ngram", lambda: run_engine_arm(
        params, cfg, reqs3, spec_cfg(speculate="ngram", speculate_k=8)))
    spec_self = phase("speculate_self", lambda: run_engine_arm(
        params, cfg, reqs3, spec_cfg(speculate="self", speculate_k=4,
                                     draft_layers=2)))

    # best = ONE arm must earn all three speculation gates (token rate
    # AND both latency tails — no cherry-picking TTFT from one drafter
    # and ITL from the other); prefer an arm that sweeps, else judge
    # the highest per-row token rate (both drafters are reported)
    def _sweeps(a):
        return (a["tokens_per_step"] > 1.5
                and a["ttft_p99_s"] < spec_off["ttft_p99_s"]
                and a["itl_p99_s"] < spec_off["itl_p99_s"])

    spec_best = next((a for a in (spec_ngram, spec_self) if _sweeps(a)),
                     max((spec_ngram, spec_self),
                         key=lambda a: a["tokens_per_step"]))

    # ---- arm 4: tensor-parallel sharded decode A/B — the same
    # shared-prefix request set on the paged engine unmeshed vs on a
    # tp=2 mesh.  Runs in ONE child process whose backend is forced
    # onto 8 virtual CPU devices (__graft_entry__._cpu_env) — the
    # parent initialized on one device, and forcing the split here
    # would perturb every arm above (module docstring).  Both halves
    # share the child, so the A/B comparison stays same-process.
    import subprocess

    from __graft_entry__ import _cpu_env
    cmd = [sys.executable, os.path.abspath(__file__), "--shard-child"]
    if q:
        cmd.append("--quick")
    proc = phase("sharded_ab_child", lambda: subprocess.run(
        cmd, env=_cpu_env(8), capture_output=True, text=True,
        timeout=1200))
    if proc.returncode != 0:
        sys.stderr.write(proc.stdout + proc.stderr)
        raise RuntimeError("sharded A/B child failed")
    payload = next(ln[len(_CHILD_MARK):]
                   for ln in proc.stdout.splitlines()
                   if ln.startswith(_CHILD_MARK))
    child = json.loads(payload)
    # child-side phases carry the loadavg stamps for the two halves;
    # the parent's sharded_ab_child phase bounds the whole subprocess
    phases.update(child["phases"])
    sharded = child["section"]
    sh_single, sh_tp2 = sharded["single_device"], sharded["tp2"]

    ratio_cont = round(cont["req_s"] / seq_base["req_s"], 2)
    ratio_prefix = round(sp_paged["req_s"] / sp_slot["req_s"], 2)
    gates = {
        "continuous_ratio_ge_2": ratio_cont >= 2.0,
        "shared_prefix_ratio_ge_1.5": ratio_prefix >= 1.5,
        "storm_peak_concurrency_strictly_higher":
            ms_paged["peak_active_requests"] > ms_slot["peak_active_requests"],
        "storm_equal_pool_tokens":
            ms_paged["pool_tokens"] == ms_slot["pool_tokens"],
        "zero_dropped": all(
            a["dropped"] == 0 and a["errors"] == 0
            for a in (seq_base, cont, sp_slot, sp_paged, ms_slot,
                      ms_paged, spec_off, spec_ngram, spec_self)),
        "spec_tokens_per_step_gt_1.5":
            spec_best["tokens_per_step"] > 1.5,
        "spec_ttft_p99_improves":
            spec_best["ttft_p99_s"] < spec_off["ttft_p99_s"],
        "spec_itl_p99_improves":
            spec_best["itl_p99_s"] < spec_off["itl_p99_s"],
        "sharded_token_exact": sharded["token_exact"],
        "sharded_mesh_really_used":
            sh_tp2["mesh_devices"] == 2 and sh_tp2["tp_shards"] == 2,
        "sharded_bytes_per_device_halved":
            sh_tp2["cache_bytes_per_device"] * 2 == sh_tp2["cache_bytes"]
            and sh_tp2["cache_bytes"] == sh_single["cache_bytes"],
    }

    artifact = {
        "round": ROUND,
        "quick": bool(q),
        "_conditions": {
            "phases": phases,
            "backend": jax.default_backend(),
            "physical_cores": os.cpu_count(),
            "note": "same-run A/B; only in-run ratios are portable "
                    "across days (PERF.md box-variance caveat)",
        },
        "model": {"d_model": cfg.d_model, "n_layers": cfg.n_layers,
                  "n_heads": cfg.n_heads, "d_ff": cfg.d_ff,
                  "vocab": cfg.vocab_size, "max_seq": cfg.max_seq,
                  "dtype": "float32"},
        "baseline_sequential": seq_base,
        "continuous_batching": cont,
        "ratio_req_s": ratio_cont,
        "shared_prefix": {
            "workload": {"n": len(reqs1), "heads": 4, "head_len": 192,
                         "tail_len": 8, "max_new": 4},
            "slot_engine_r14": sp_slot,
            "paged_prefix_engine": sp_paged,
            "ratio_req_s": ratio_prefix,
        },
        "mixed_storm": {
            "workload": {"n": len(reqs2),
                         "short": "8..16 tok prompts, 8 new",
                         "long": "100..200 tok prompts, 8 new"},
            "slot_engine_r14": ms_slot,
            "paged_prefix_engine": ms_paged,
            "peak_concurrent": {
                "slot": ms_slot["peak_active_requests"],
                "paged": ms_paged["peak_active_requests"],
            },
            "ttft_p99_short_biased": {
                "slot": ms_slot["ttft_p99_s"],
                "paged": ms_paged["ttft_p99_s"],
            },
        },
        "speculation": {
            "workload": {"n": len(reqs3),
                         "shape": "shared-prefix heads + trace-replay "
                                  "short/long mix, decode-heavy",
                         "itl_definition": "normalized per-request "
                                           "(e2e - ttft)/(tokens - 1); "
                                           "raw gaps under gap_*"},
            "baseline_off": spec_off,
            "ngram_drafter": spec_ngram,
            "self_drafter": spec_self,
            "best_arm": spec_best.get("speculate"),
            "ttft_p99": {"off": spec_off["ttft_p99_s"],
                         "ngram": spec_ngram["ttft_p99_s"],
                         "self": spec_self["ttft_p99_s"]},
            "itl_p99": {"off": spec_off["itl_p99_s"],
                        "ngram": spec_ngram["itl_p99_s"],
                        "self": spec_self["itl_p99_s"]},
            "tokens_per_step": {"off": spec_off["tokens_per_step"],
                                "ngram": spec_ngram["tokens_per_step"],
                                "self": spec_self["tokens_per_step"]},
        },
        "sharded_decode": sharded,
        "gates": gates,
    }
    out = json.dumps(artifact, indent=1)
    print(out)
    with open(args.out, "w") as f:
        f.write(out + "\n")
    ok = all(gates.values())
    for g, passed in gates.items():
        print(f"  gate {g}: {'PASS' if passed else 'FAIL'}")
    print(f"continuous/sequential {ratio_cont}x | shared-prefix "
          f"paged/slot {ratio_prefix}x | peak "
          f"{ms_slot['peak_active_requests']} -> "
          f"{ms_paged['peak_active_requests']} | spec "
          f"tok/step {spec_off['tokens_per_step']} -> "
          f"{spec_best['tokens_per_step']} ({spec_best.get('speculate')}), "
          f"itl p99 {spec_off['itl_p99_s']}s -> "
          f"{spec_best['itl_p99_s']}s | tp2 "
          f"{'exact' if gates['sharded_token_exact'] else 'DIVERGED'} "
          f"({'PASS' if ok else 'FAIL'})")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
