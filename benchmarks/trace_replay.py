"""Trace-replay load harness → SERVE_r14.json.

Replays bursty / diurnal arrival processes against the fleet serving
layer (admission + occupancy router + autoscaler, serve/fleet/) and
records the degradation curve — p99 vs offered load — plus the
autoscaling trace and a full request accounting.  Round 14 adds the
**scale-down storm A/B** (ISSUE 14): the same streaming trace replayed
against periodic replica removals done the r13 way (kill + resume) and
the drain-aware way (ACTIVE -> DRAINING -> teardown) — zero masked
resumes, replayed-token count and scale-down-window p99 compared in the
same run.  The r13 acceptance contract (kept):

  * >= 64 total decode slots across replicas at peak under the
    replayed bursty load (autoscaler must actually fan the fleet out);
  * an autoscaling trace: replica count responding to occupancy;
  * p99 for ADMITTED interactive requests held under the declared SLO
    at nominal load;
  * zero silently-dropped requests: every offered request ends in
    exactly one of {completed, shed (429), clean error} — client-side
    and fleet-side counts must both add up;
  * same-run A/B vs the r10 single-engine path (one replica, no
    fleet): the same nominal trace replayed against both, plus the
    overload level where the unprotected path degrades unboundedly
    while the fleet sheds to hold p99.

Arrival processes are non-homogeneous Poisson (thinning): ``bursty``
(square-wave rate: quiet base / duty-cycle peaks) and ``diurnal``
(sinusoidal day curve compressed to seconds).  Request mix: 70%
interactive / 30% batch priority classes, 15% on a second model
variant (exercises multiplexed routing).

loadavg is recorded per phase (PERF.md box-variance caveat: only the
in-run A/B ratio is portable across days, never the absolutes).

Round 18 adds ``--prefix-cluster`` → SERVE_r18.json: the cluster
prefix plane's proof harness.  Same-run A/B (cluster_prefix on vs
off): a COLD replica joins mid-storm while traffic sharing long prompt
prefixes replays — with the plane on it adopts the holders' published
blocks and its first-token latency lands within 1.3x of a warm
replica's; with the plane off it pays full prefill.  A chaos pass then
kills one holder and drains another mid-fetch: every request still
completes token-exact against the full-recompute oracle.

Run:  JAX_PLATFORMS=cpu python benchmarks/trace_replay.py [--quick]
      JAX_PLATFORMS=cpu python benchmarks/trace_replay.py --prefix-cluster
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import threading
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SLO_INTERACTIVE_P99_S = 3.0      # declared: admitted interactive, nominal


def _pct(xs, p):
    xs = sorted(xs)
    if not xs:
        return 0.0
    i = min(len(xs) - 1, max(0, int(round(p / 100 * (len(xs) - 1)))))
    return xs[i]


# ------------------------------------------------------------- arrivals


def bursty_arrivals(rng, *, base, peak, period, duty, duration):
    """Square-wave rate: ``peak`` for the first ``duty`` fraction of
    every ``period``, ``base`` otherwise (thinned Poisson)."""
    def rate(t):
        return peak if (t % period) < duty * period else base
    return _thin(rng, rate, max(base, peak), duration)


def diurnal_arrivals(rng, *, trough, peak, period, duration):
    """Sinusoidal "day" compressed to seconds."""
    def rate(t):
        return trough + (peak - trough) * 0.5 * (
            1 - math.cos(2 * math.pi * t / period))
    return _thin(rng, rate, peak, duration)


def _thin(rng, rate_fn, rate_max, duration):
    out, t = [], 0.0
    while True:
        t += rng.exponential(1.0 / rate_max)
        if t >= duration:
            return out
        if rng.random() < rate_fn(t) / rate_max:
            out.append(t)


# --------------------------------------------------------------- driving


def _post(addr, payload, timeout):
    rq = urllib.request.Request(
        addr + "/v1/generate", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(rq, timeout=timeout) as resp:
        return json.loads(resp.read())


def _post_stream(addr, payload, timeout):
    """Streamed /v1/generate: returns (n_tokens, clean).  urllib strips
    the chunked framing, so the body is concatenated JSON documents —
    decode them in sequence; ``clean`` means the terminal done-chunk
    arrived (a mid-stream replica kill without resume truncates)."""
    rq = urllib.request.Request(
        addr + "/v1/generate", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(rq, timeout=timeout) as resp:
        raw = resp.read().decode("utf-8", "replace")
    dec = json.JSONDecoder()
    i, n, clean = 0, 0, False
    while i < len(raw):
        while i < len(raw) and raw[i] in " \r\n":
            i += 1
        if i >= len(raw):
            break
        obj, i = dec.raw_decode(raw, i)
        if "token" in obj:
            n += 1
        if obj.get("done"):
            clean = True
    return n, clean


def replay_streams(addr, arrivals, reqs, *, timeout=60.0, pool=None):
    """Like replay() but over STREAMING requests: latency is measured
    to the END of the stream, and each completion records its wall
    offset so tail latency can be windowed around scale-down events."""
    from concurrent.futures import ThreadPoolExecutor
    outcomes = [None] * len(arrivals)
    t_start = [0.0]

    def fire(i, payload):
        t0 = time.perf_counter()
        rec = {"class": payload.get("priority", "batch")}
        try:
            n, clean = _post_stream(addr, payload, timeout)
            rec.update(outcome="completed" if clean else "truncated",
                       latency_s=time.perf_counter() - t0,
                       done_at_s=time.perf_counter() - t_start[0],
                       n_tokens=n)
        except urllib.error.HTTPError as e:
            e.read()
            rec.update(outcome="shed" if e.code == 429 else "error",
                       code=e.code)
        except Exception as e:   # noqa: BLE001 — clean client error
            rec.update(outcome="error", detail=str(e)[:120])
        outcomes[i] = rec

    own_pool = pool is None
    if own_pool:
        pool = ThreadPoolExecutor(max_workers=512)
    lag = 0.0
    try:
        futs = []
        t_start[0] = time.perf_counter()
        for i, (at, payload) in enumerate(zip(arrivals, reqs)):
            delay = t_start[0] + at - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            else:
                lag = max(lag, -delay)
            futs.append(pool.submit(fire, i, payload))
        for fu in futs:
            fu.result(timeout=timeout + 30)
        wall = time.perf_counter() - t_start[0]
    finally:
        if own_pool:
            pool.shutdown(wait=False)
    assert all(o is not None for o in outcomes), "silently dropped!"
    return outcomes, wall, lag, t_start[0]


class ScaleDownStorm(threading.Thread):
    """Periodic replica removal while traffic replays: the r14 A/B
    lever.  ``drain=True`` goes through the drain protocol (ACTIVE ->
    DRAINING -> teardown once idle / at the deadline); ``drain=False``
    is the r13 path — scale_to kills a replica with requests in
    flight.  Each pulse restores the fleet to ``n`` replicas so every
    pulse starts from the same shape."""

    def __init__(self, state, drain: bool, *, period: float,
                 deadline_s: float, n: int, t0: float):
        super().__init__(daemon=True)
        self.st, self.drain = state, drain
        self.period, self.deadline_s, self.n = period, deadline_s, n
        self.t0 = t0
        self.pulses = []          # wall offsets of each scale-down
        self._halt = threading.Event()

    def run(self):
        while not self._halt.wait(self.period):
            self.pulses.append(round(time.perf_counter() - self.t0, 2))
            if self.drain:
                self.st.drain_replicas(1, self.deadline_s)
            else:
                with self.st._lock:
                    cur = len(self.st.replicas)
                self.st.scale_to(max(1, cur - 1))
            if self._halt.is_set():
                return
            # surge replacement IMMEDIATELY in both arms (the rolling-
            # restart shape): capacity dips identically — only the
            # treatment of the removed replica's in-flight work differs,
            # which is exactly what the A/B measures.  The drained
            # victim finishes in the background; drain_tick retires it.
            self.st.scale_to(self.n)

    def stop(self):
        self._halt.set()


def window_p99(outcomes, pulses, window_s=3.0):
    """p99 stream latency over completions landing within ``window_s``
    after any scale-down pulse — the tail the removal actually hurt."""
    lat = [o["latency_s"] for o in outcomes
           if o.get("outcome") == "completed"
           and any(p <= o.get("done_at_s", -1) <= p + window_s
                   for p in pulses)]
    return _pct(lat, 99), len(lat)


def replay(addr, arrivals, reqs, *, timeout=60.0, pool=None):
    """Fire each request at its arrival offset (pre-spawned worker
    pool, so arrival pacing never stalls on thread creation); returns
    (outcomes, wall, pacing_lag_s) — every offered request is accounted
    exactly once, and the recorded lag proves the client actually
    offered the intended rate."""
    from concurrent.futures import ThreadPoolExecutor
    outcomes = [None] * len(arrivals)

    def fire(i, payload):
        t0 = time.perf_counter()
        rec = {"class": payload.get("priority", "batch"),
               "model": payload.get("model")}
        try:
            out = _post(addr, payload, timeout)["result"]
            rec.update(outcome="completed", latency_s=time.perf_counter()
                       - t0, n_tokens=out["n"])
        except urllib.error.HTTPError as e:
            body = e.read().decode("utf-8", "replace")
            if e.code == 429:
                rec.update(outcome="shed",
                           retry_after=e.headers.get("Retry-After"))
            else:
                rec.update(outcome="error", code=e.code,
                           detail=body[:120])
        except Exception as e:   # noqa: BLE001 — clean client error
            rec.update(outcome="error", detail=str(e)[:120])
        outcomes[i] = rec

    own_pool = pool is None
    if own_pool:
        pool = ThreadPoolExecutor(max_workers=512)
    lag = 0.0
    try:
        futs = []
        t_start = time.perf_counter()
        for i, (at, payload) in enumerate(zip(arrivals, reqs)):
            delay = t_start + at - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            else:
                lag = max(lag, -delay)
            futs.append(pool.submit(fire, i, payload))
        for f in futs:
            f.result(timeout=timeout + 30)
        wall = time.perf_counter() - t_start
    finally:
        if own_pool:
            pool.shutdown(wait=False)
    assert all(o is not None for o in outcomes), "silently dropped!"
    return outcomes, wall, lag


def summarize(outcomes, wall, lag=0.0):
    lat_all = [o["latency_s"] for o in outcomes
               if o["outcome"] == "completed"]
    lat_int = [o["latency_s"] for o in outcomes
               if o["outcome"] == "completed"
               and o["class"] == "interactive"]
    counts = {}
    for o in outcomes:
        counts[o["outcome"]] = counts.get(o["outcome"], 0) + 1
    return {
        "offered": len(outcomes),
        "completed": counts.get("completed", 0),
        "shed": counts.get("shed", 0),
        "errors": counts.get("error", 0),
        "wall_s": round(wall, 2),
        "goodput_req_s": round(counts.get("completed", 0) / wall, 2),
        "p50_s": round(_pct(lat_all, 50), 4),
        "p99_s": round(_pct(lat_all, 99), 4),
        "interactive_p99_s": round(_pct(lat_int, 99), 4),
        "shed_fraction": round(counts.get("shed", 0)
                               / max(1, len(outcomes)), 3),
        "pacing_lag_s": round(lag, 3),
    }


def make_requests(rng, n, *, vocab, interactive_frac=0.7,
                  alt_model_frac=0.15):
    reqs = []
    for _ in range(n):
        pl = int(rng.integers(6, 13))
        req = {"prompt": rng.integers(0, vocab, pl).tolist(),
               "max_tokens": int(rng.integers(12, 25)),
               "priority": ("interactive"
                            if rng.random() < interactive_frac
                            else "batch")}
        if rng.random() < alt_model_frac:
            req["model"] = "alt"
        else:
            req["model"] = "base"
        reqs.append(req)
    return reqs


class FleetSampler(threading.Thread):
    """The autoscaling trace: replica count / slots / occupancy /
    ingress queue sampled on a fixed cadence while traffic replays."""

    def __init__(self, fleet, state, period=0.25):
        super().__init__(daemon=True)
        self.fleet, self.state, self.period = fleet, state, period
        self.rows = []
        self._halt = threading.Event()   # NB: Thread owns _stop
        self._t0 = time.perf_counter()
        self.marks = []      # (t, label) phase boundaries

    def mark(self, label):
        self.marks.append((round(time.perf_counter() - self._t0, 2),
                           label))

    def run(self):
        while not self._halt.wait(self.period):
            snap = self.fleet.fleet_snapshot()
            self.rows.append({
                "t": round(time.perf_counter() - self._t0, 2),
                "replicas": snap["replicas"],
                "total_slots": snap["total_slots"],
                "occupancy": round(snap["occupancy"], 3),
                "ingress_queued": snap["ingress_queued"],
                "engine_waiting": snap["engine_waiting"],
            })

    def stop(self):
        self._halt.set()


# ----------------------------------------------- prefix-cluster arm (r18)


class PrefixStorm(threading.Thread):
    """Background prefix-sharing traffic: the storm the cold replica
    joins into.  Fires fleet.remote at a steady Poisson rate until
    stopped; every outcome is accounted (completed or recorded error)."""

    def __init__(self, f, prefixes, mk_req, *, rate, seed):
        super().__init__(daemon=True)
        self.f, self.prefixes, self.mk_req = f, prefixes, mk_req
        self.rate, self.seed = rate, seed
        self.offered = 0
        self.completed = 0
        self.errors = []
        self._lock = threading.Lock()
        self._halt = threading.Event()

    def _fire(self, req):
        try:
            self.f.remote((req,), {}).result(timeout=120)
            with self._lock:
                self.completed += 1
        except Exception as e:   # noqa: BLE001 — accounted, not raised
            with self._lock:
                self.errors.append(str(e)[:120])

    def run(self):
        import numpy as np
        from concurrent.futures import ThreadPoolExecutor
        r = np.random.default_rng(self.seed)
        pool = ThreadPoolExecutor(max_workers=64)
        futs = []
        try:
            while not self._halt.wait(float(r.exponential(
                    1.0 / self.rate))):
                pfx = self.prefixes[int(r.integers(0, len(self.prefixes)))]
                with self._lock:
                    self.offered += 1
                futs.append(pool.submit(self._fire, self.mk_req(r, pfx)))
            for fu in futs:
                fu.result(timeout=150)
        finally:
            pool.shutdown(wait=False)

    def stop(self):
        self._halt.set()


def _leak_audit(f):
    """Blocks-vs-trie audit over every LIVE engine: with nothing in
    flight, a used block unaccounted to the radix trie is a refcount
    leaked by some fetch/install/fallback path."""
    out = []
    for rep in list(f.state.replicas):
        try:
            eng = rep.impl._user.engine
        except Exception:
            continue
        if getattr(eng, "_stopped", False):
            continue
        stats = eng.pool.stats()
        if stats["blocks_used"] != eng.trie.cached_blocks:
            out.append(f"{rep.tag}: used={stats['blocks_used']} "
                       f"trie={eng.trie.cached_blocks}")
    return out


def prefix_cluster_main(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu import serve
    from ray_tpu.core import fault_injection as fi
    from ray_tpu.inference import EngineConfig, build_gpt_deployment
    from ray_tpu.models import gpt
    from ray_tpu.serve import fleet as fleet_mod

    out_path = args.out or "SERVE_r18.json"
    # long-prefix regime: prefill is the cost a cold replica pays, so
    # prompts carry a 448-token shared prefix (28 blocks of 16) and a
    # short random suffix — adoption moves the 28 blocks, the suffix
    # still prefills locally on every replica.  The model is decode-
    # heavy on purpose (wide FFN): TTFT must be dominated by model
    # compute, not by the engine's fixed round-trip, or the adoption-
    # vs-warm ratio measures dispatch overhead instead of the plane
    cfg = gpt.GPTConfig(vocab_size=512, max_seq=512, d_model=384,
                        n_heads=8, n_layers=6, d_ff=4096, remat=False,
                        dtype=jnp.float32)
    ecfg = EngineConfig(max_slots=8, kv_block_size=16, n_blocks=512,
                        default_max_new=8)
    n_prefixes = 4 if args.quick else 6
    prefix_tokens = 448
    # the storm must keep the holders WARM, not saturated: a prefix
    # fetch runs on the holder's loop thread, so a holder pinned at
    # 100% decode makes every adoption wait out a full iteration —
    # that measures queueing, not the plane.  Short generations at a
    # rate the box can absorb leave the loop idle between requests
    storm_rate = 1.5
    storm_max_new = 2
    corpus_rng = np.random.default_rng(1800)
    prefixes = [corpus_rng.integers(0, cfg.vocab_size,
                                    prefix_tokens).tolist()
                for _ in range(n_prefixes)]

    def loadavg():
        return round(os.getloadavg()[0], 2)

    def mk_req(r, pfx, max_new=4):
        sfx = r.integers(0, cfg.vocab_size,
                         int(r.integers(4, 9))).tolist()
        return {"prompt": pfx + sfx, "max_tokens": max_new,
                "temperature": 0.0, "priority": "interactive"}

    def probe_req(r, pfx):
        # TTFT proxy: a 1-token greedy request's full latency is
        # prefill (or adoption) + one decode step — the first token
        sfx = r.integers(0, cfg.vocab_size, 6).tolist()
        return {"prompt": pfx + sfx, "max_tokens": 1,
                "temperature": 0.0}

    # ---- A/B arms: plane on vs plane off, identical seeds -------------
    def arm(enabled: bool):
        la0 = loadavg()
        dep = build_gpt_deployment(cfg=cfg, engine_cfg=ecfg, seed=0,
                                   num_replicas=2, warm_on_init=True)
        serve.run(dep, use_actors=False, http=False)
        f = fleet_mod.enable("v1", fleet_mod.FleetConfig(
            rate=500, burst=64, seed=18, cluster_prefix=enabled))
        st = f.state
        rw = np.random.default_rng(1801)
        # warm every prefix on EVERY starting replica (direct _call:
        # the probe baseline must be a true local hit on whichever
        # warm body we probe — with the plane on the second body
        # adopts remotely; with it off each pays its own prefill,
        # exactly the current behavior)
        for pfx in prefixes:
            for rep in list(st.replicas):
                f._call(rep, (mk_req(rw, pfx),), {}, "__call__")
        if f.prefix is not None:
            # direct _call skips the post-call publish drain the
            # f.remote path does — drain explicitly so the storm's
            # route_hint sees the warm holders from its first request
            for rep in list(st.replicas):
                f.prefix.publish_from(rep)
        pre_join_hits = (f.prefix.counters()["prefix_remote_hits"]
                        if f.prefix is not None else 0)
        storm = PrefixStorm(
            f, prefixes,
            lambda r, pfx: mk_req(r, pfx, max_new=storm_max_new),
            rate=storm_rate, seed=1802)
        storm.start()
        time.sleep(1.5)                     # the storm is established…
        before = {x.tag for x in st.replicas}
        t0 = time.perf_counter()
        st.scale_to(3)                      # …and the COLD replica joins
        join_s = time.perf_counter() - t0
        cold = next(x for x in st.replicas if x.tag not in before)
        warms = [x for x in st.replicas if x.tag in before]
        rp = np.random.default_rng(1803)
        warm_ttft, cold_ttft = [], []
        for i, pfx in enumerate(prefixes):
            q = probe_req(rp, pfx)
            t1 = time.perf_counter()
            f._call(warms[i % len(warms)], (q,), {}, "__call__")
            warm_ttft.append(time.perf_counter() - t1)
        for pfx in prefixes:
            q = probe_req(rp, pfx)
            t1 = time.perf_counter()
            f._call(cold, (q,), {}, "__call__")
            cold_ttft.append(time.perf_counter() - t1)
        storm.stop()
        storm.join(timeout=180)
        snap = f.fleet_snapshot()
        events = f.events()
        adopt_events = {k: sum(1 for e in events if e["kind"] == k)
                        for k in ("adopt_begin", "adopt_complete",
                                  "adopt_fallback")}
        leaks = _leak_audit(f)
        serve.shutdown()
        ratio = _pct(cold_ttft, 50) / max(_pct(warm_ttft, 50), 1e-9)
        return {
            "plane": "on" if enabled else "off",
            "storm": {"offered": storm.offered,
                      "completed": storm.completed,
                      "errors": storm.errors,
                      "rate_req_s": storm_rate},
            "cold_join_s": round(join_s, 3),
            "warm_ttft_s": [round(x, 5) for x in warm_ttft],
            "cold_ttft_s": [round(x, 5) for x in cold_ttft],
            "warm_ttft_p50_s": round(_pct(warm_ttft, 50), 5),
            "cold_ttft_p50_s": round(_pct(cold_ttft, 50), 5),
            "cold_warm_ttft_p50_ratio": round(ratio, 3),
            "remote_hits_pre_join": pre_join_hits,
            # the PLANE's counters only (engines also report local
            # prefix_hit_* stats, plane or no plane — those are not
            # what absent-when-disabled is about)
            "counters": {k: snap[k] for k in (
                "prefix_remote_hits", "prefix_remote_fetch_failures",
                "prefix_fallback_recomputes",
                "prefix_directory_entries") if k in snap},
            "adopt_events": adopt_events,
            "block_leaks": leaks,
            "loadavg_1m": [la0, loadavg()],
        }

    print("prefix-cluster arm A: plane ON (adoption)")
    adopt = arm(enabled=True)
    print(f"  cold/warm TTFT p50 ratio "
          f"{adopt['cold_warm_ttft_p50_ratio']}  "
          f"remote_hits {adopt['counters'].get('prefix_remote_hits')}")
    print("prefix-cluster arm B: plane OFF (baseline)")
    base = arm(enabled=False)
    print(f"  cold/warm TTFT p50 ratio "
          f"{base['cold_warm_ttft_p50_ratio']}")

    # ---- chaos pass: holders killed / drained mid-fetch ---------------
    # prompt i pays prefill on replica i, so the three holders are
    # distinct by construction; the scripted fault then kills the
    # first holder and drains the second AT the prefix_fetch choke
    # point — both adoptions must silently downgrade to local
    # recompute and stay token-exact against the oracle
    def chaos_pass():
        la0 = loadavg()
        dep = build_gpt_deployment(cfg=cfg, engine_cfg=ecfg, seed=0,
                                   num_replicas=3, warm_on_init=True)
        serve.run(dep, use_actors=False, http=False)
        f = fleet_mod.enable("v1", fleet_mod.FleetConfig(
            rate=500, burst=64, seed=19, cluster_prefix=True))
        r = np.random.default_rng(1807)
        reqs = [mk_req(r, prefixes[i]) for i in range(3)]
        params = gpt.init_params(cfg, jax.random.PRNGKey(0))

        def oracle(q):
            out = gpt.generate(params, cfg,
                               jnp.asarray([q["prompt"]], jnp.int32),
                               max_new=q["max_tokens"], temperature=0.0)
            return np.asarray(out)[0, len(q["prompt"]):].tolist()

        refs = [oracle(q) for q in reqs]
        reps = list(f.state.replicas)
        parity, errors = [], []

        def serve_on(rep, q, ref, label):
            try:
                out = f._call(rep, (q,), {}, "__call__")
                parity.append(out["tokens"] == ref)
            except Exception as e:   # noqa: BLE001 — accounted
                errors.append(f"{label}: {str(e)[:120]}")

        for i, q in enumerate(reqs):                 # publish
            serve_on(reps[i], q, refs[i], f"publish#{i}")
            # direct _call skips the post-call publish drain that the
            # routed path runs — drain explicitly so the directory
            # knows holder i before the adoptions fire
            f.prefix.publish_from(reps[i])
        serve_on(reps[0], reqs[2], refs[2], "clean adopt")
        calls = {"n": 0}

        def chaos_fn(ctx):
            calls["n"] += 1
            if calls["n"] == 1:
                f.kill_replica(ctx["holder_replica"])
            else:
                f.state.drain_replicas(
                    1, deadline_s=10.0,
                    replicas=[ctx["holder_replica"]])
                raise RuntimeError("holder drained mid-adoption")

        plan = fi.FaultPlan()
        plan.add(fi.Rule("prefix_fetch", "script", fn=chaos_fn,
                         times=2))
        fi.install(plan)
        try:
            serve_on(reps[1], reqs[0], refs[0], "kill arm")
            serve_on(reps[2], reqs[1], refs[1], "drain arm")
        finally:
            fi.uninstall()
        counters = dict(f.prefix.counters())
        directory_entries = len(f.prefix.directory)
        leaks = _leak_audit(f)
        serve.shutdown()
        return {
            "requests": len(parity) + len(errors),
            "token_exact": sum(bool(p) for p in parity),
            "errors": errors,
            "counters": counters,
            "directory_entries_after": directory_entries,
            "block_leaks": leaks,
            "loadavg_1m": [la0, loadavg()],
        }

    print("prefix-cluster chaos pass: kill + drain mid-fetch")
    chaos = chaos_pass()
    print(f"  {chaos['token_exact']}/{chaos['requests']} token-exact, "
          f"errors={chaos['errors']}, counters={chaos['counters']}")

    ac, cc = adopt["counters"], chaos["counters"]
    gates = {
        # the cold replica actually adopted: remote hits moved past
        # what the second warm body's startup adoption already counted
        "adopt_remote_hits_positive":
            ac.get("prefix_remote_hits", 0)
            > adopt["remote_hits_pre_join"],
        "adopt_cold_ttft_within_1p3x_warm":
            adopt["cold_warm_ttft_p50_ratio"] <= 1.3,
        # fallback-total baseline: no plane, no keys, and the cold
        # replica pays full prefill (the gap adoption closes)
        "baseline_plane_absent": base["counters"] == {},
        "baseline_cold_pays_full_prefill":
            base["cold_warm_ttft_p50_ratio"]
            > adopt["cold_warm_ttft_p50_ratio"],
        "storm_zero_request_errors":
            adopt["storm"]["errors"] == [] and base["storm"]["errors"]
            == [] and adopt["storm"]["offered"]
            == adopt["storm"]["completed"],
        "no_block_leaks": (adopt["block_leaks"] == []
                           and base["block_leaks"] == []
                           and chaos["block_leaks"] == []),
        "chaos_all_token_exact":
            chaos["errors"] == []
            and chaos["token_exact"] == chaos["requests"],
        "chaos_failures_counted_and_recomputed": (
            cc.get("prefix_remote_fetch_failures", 0) >= 2
            and cc.get("prefix_fallback_recomputes", 0) >= 2
            and cc.get("prefix_remote_hits", 0) >= 1),
    }
    artifact = {
        "round": 18,
        "mode": "prefix_cluster",
        "quick": bool(args.quick),
        "_conditions": {
            "backend": jax.default_backend(),
            "physical_cores": os.cpu_count(),
            "note": "same-run A/B; only ratios are portable across "
                    "days (PERF.md box-variance caveat)",
        },
        "model": {"d_model": cfg.d_model, "n_layers": cfg.n_layers,
                  "n_heads": cfg.n_heads, "d_ff": cfg.d_ff,
                  "vocab": cfg.vocab_size, "max_seq": cfg.max_seq},
        "engine": {"max_slots": ecfg.max_slots,
                   "kv_block_size": ecfg.kv_block_size,
                   "n_blocks": ecfg.n_blocks},
        "corpus": {"n_prefixes": n_prefixes,
                   "prefix_tokens": prefix_tokens,
                   "suffix_tokens": "4-8 random per request",
                   "ttft_probe": "1-token greedy request latency "
                                 "(prefill/adoption + first decode)"},
        "adopt": adopt,
        "baseline": base,
        "chaos": chaos,
        "ab": {
            "cold_warm_ttft_p50_ratio": {
                "adopt": adopt["cold_warm_ttft_p50_ratio"],
                "baseline": base["cold_warm_ttft_p50_ratio"]},
            "remote_hits": {
                "adopt": ac.get("prefix_remote_hits", 0),
                "baseline": 0},
        },
        "acceptance": gates,
    }
    out = json.dumps(artifact, indent=1)
    print(out)
    with open(out_path, "w") as fo:
        fo.write(out + "\n")
    ok = all(gates.values())
    print("\nacceptance: " + ", ".join(
        f"{k}={'PASS' if v else 'FAIL'}" for k, v in gates.items()))
    return 0 if ok else 1


# ------------------------------------------------------------------ main


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--events-out", default=None,
                    help="Fleet.dump_events JSON (feed to `ray_tpu "
                         "timeline --serve-events`)")
    ap.add_argument("--prefix-cluster", action="store_true",
                    help="cluster prefix plane proof harness -> "
                         "SERVE_r18.json (cold-replica adoption A/B "
                         "+ kill/drain chaos pass)")
    args = ap.parse_args()
    from ray_tpu._compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.prefix_cluster:
        return prefix_cluster_main(args)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import ray_tpu.perf as perf
    from ray_tpu import serve
    from ray_tpu.inference import EngineConfig, build_gpt_deployment
    from ray_tpu.models import gpt
    from ray_tpu.serve import fleet as fleet_mod
    from ray_tpu.serve.deployment import AutoscalingConfig

    out_path = args.out or f"SERVE_r{perf.ROUND}.json"
    # a model size big enough that the ENGINE, not
    # the HTTP stack, is the bottleneck — otherwise offered load never
    # reaches the admission/occupancy machinery under test
    cfg = gpt.GPTConfig(vocab_size=512, max_seq=64, d_model=128,
                        n_heads=4, n_layers=4, d_ff=512, remat=False,
                        dtype=jnp.float32)
    slots = 16
    max_replicas = 6
    rng = np.random.default_rng(13)
    dur = 6.0 if args.quick else 12.0

    def loadavg():
        return round(os.getloadavg()[0], 2)

    phases = {}

    # ---- phase 0: the r10 single-engine path (baseline A arm) ----------
    # one replica, NO fleet layer: round-robin handle + unbounded-ish
    # engine queue — exactly what PR 5 shipped.
    load0 = loadavg()
    dep = build_gpt_deployment(
        cfg=cfg, engine_cfg=EngineConfig(max_slots=slots), seed=0,
        num_replicas=1, warm_on_init=True,
        variants={"base": 0, "alt": 1}, multiplex_capacity=2)
    serve.run(dep, use_actors=False, http=True)
    addr = serve.proxy_address()

    # calibrate: closed-loop burst for the single-engine capacity
    cal_reqs = make_requests(rng, 48, vocab=cfg.vocab_size)
    done, lock = [], threading.Lock()

    def closed_worker(it):
        while True:
            with lock:
                try:
                    payload = next(it)
                except StopIteration:
                    return
            t0 = time.perf_counter()
            try:
                _post(addr, payload, 60)
                with lock:
                    done.append(time.perf_counter() - t0)
            except Exception:
                pass

    _post(addr, {"prompt": [1, 2], "max_tokens": 2, "model": "base"}, 60)
    _post(addr, {"prompt": [1, 2], "max_tokens": 2, "model": "alt"}, 60)
    it = iter(cal_reqs)
    t0 = time.perf_counter()
    ws = [threading.Thread(target=closed_worker, args=(it,))
          for _ in range(16)]
    for w in ws:
        w.start()
    for w in ws:
        w.join()
    cal_wall = time.perf_counter() - t0
    capacity = len(done) / cal_wall
    # nominal ("1x") arrival rate: just under one engine's capacity,
    # capped so the client pool can hold 4x's in-flight population —
    # the ADMISSION layer, not the client, must be what says no
    nominal = max(4.0, min(capacity * 0.8, 25.0))
    print(f"calibrated single-engine capacity ~{capacity:.1f} req/s "
          f"-> nominal offered rate {nominal:.1f}/s")

    def bursty_trace(level, seed):
        r = np.random.default_rng(seed)
        lam = nominal * level
        arr = bursty_arrivals(r, base=lam * 0.4, peak=lam * 1.6,
                              period=4.0, duty=0.5, duration=dur)
        return arr, make_requests(r, len(arr), vocab=cfg.vocab_size)

    # baseline replays: nominal + overload (same traces the fleet gets)
    base_phases = {}
    for level in (1.0, 4.0):
        arr, reqs = bursty_trace(level, seed=int(level * 100))
        outcomes, wall, lag = replay(addr, arr, reqs, timeout=60)
        base_phases[f"{level}x"] = summarize(outcomes, wall, lag)
        print(f"baseline {level}x: {base_phases[f'{level}x']}")
    serve.shutdown()
    load1 = loadavg()
    phases["baseline_single_engine"] = {
        "calibration_req_s": round(capacity, 2),
        "levels": base_phases,
        "loadavg_1m": [load0, load1],
        "note": "r10 path: 1 replica, no fleet layer, round-robin "
                "handle, engine-side queueing only",
    }

    # ---- phase 1: the fleet (B arm) ------------------------------------
    load2 = loadavg()
    dep = build_gpt_deployment(
        cfg=cfg, engine_cfg=EngineConfig(max_slots=slots), seed=0,
        num_replicas=1, warm_on_init=True,
        variants={"base": 0, "alt": 1}, multiplex_capacity=2,
        max_concurrent_queries=4 * slots,
        autoscaling=AutoscalingConfig(min_replicas=1,
                                      max_replicas=max_replicas,
                                      target_ongoing_requests=6.0))
    serve.run(dep, use_actors=False, http=True)
    addr = serve.proxy_address()
    # admission contract: 2x nominal sustained (the fleet scales to
    # carry it), one nominal-second of burst absorbed, a bounded queue
    # — anything past that sheds EXPLICITLY instead of queueing
    f = fleet_mod.enable("v1", fleet_mod.FleetConfig(
        rate=nominal * 2.0, burst=nominal,
        max_queue_depth=int(nominal * 1.5),
        interactive_wait_s=2.0, batch_wait_s=8.0, seed=13))
    st = serve.get_handle("v1")._state
    _post(addr, {"prompt": [1, 2], "max_tokens": 2, "model": "base"}, 60)

    sampler = FleetSampler(f, st)
    sampler.start()
    fleet_phases = {}
    for level in (0.5, 1.0, 2.0, 4.0):
        sampler.mark(f"level_{level}x")
        arr, reqs = bursty_trace(level, seed=int(level * 100))
        outcomes, wall, lag = replay(addr, arr, reqs, timeout=60)
        fleet_phases[f"{level}x"] = summarize(outcomes, wall, lag)
        print(f"fleet {level}x: {fleet_phases[f'{level}x']}")
    # diurnal tail: rate sweeps trough->peak->trough (scale up AND down)
    sampler.mark("diurnal")
    r = np.random.default_rng(7)
    arr = diurnal_arrivals(r, trough=nominal * 0.2, peak=nominal * 2.0,
                           period=dur, duration=dur)
    reqs = make_requests(r, len(arr), vocab=cfg.vocab_size)
    outcomes, wall, lag = replay(addr, arr, reqs, timeout=60)
    fleet_phases["diurnal"] = summarize(outcomes, wall, lag)
    print(f"fleet diurnal: {fleet_phases['diurnal']}")
    sampler.mark("end")
    time.sleep(1.0)
    sampler.stop()
    sampler.join(timeout=5)

    snap = f.fleet_snapshot()
    events = f.events()
    if args.events_out:
        f.dump_events(args.events_out)
    event_kinds = {}
    for e in events:
        event_kinds[e["kind"]] = event_kinds.get(e["kind"], 0) + 1
    serve.shutdown()
    load3 = loadavg()

    # ---- phase 2: scale-down storm A/B (ISSUE 14 drain acceptance) -----
    # the SAME steady streaming trace replayed against periodic replica
    # removals, once the r13 way (kill + resume) and once drain-aware —
    # same run, so replayed-token count and scale-down-window p99 are
    # directly comparable.
    storm_replicas = 3
    storm_deadline = 8.0
    storm_dur = max(dur, 8.0)
    storm_period = storm_dur / 4.0
    # storm load targets MODERATE occupancy: busy slots, shallow
    # queues.  Too idle (the degradation-phase ``nominal``) and a
    # replica removal is free — the A/B measures scheduler noise; at
    # saturation BOTH arms drown in queueing and the dips dominate.
    # In between, a kill catches a replica's worth of mid-decode
    # streams whose replays are the visible tail — exactly the r13
    # damage the drain exists to avoid.
    storm_rate = min(nominal * 2.0, capacity * 0.6)

    def storm_requests(r, n):
        # LONG streams (vs the degradation-curve mix): a mid-stream
        # kill then costs a real replay — prefill plus up to ~45 tokens
        # — which is exactly the tail the drain protocol exists to
        # avoid; short streams would bury the A/B in scheduler noise
        reqs = []
        for _ in range(n):
            pl = int(r.integers(6, 13))
            reqs.append({"prompt": r.integers(0, cfg.vocab_size,
                                              pl).tolist(),
                         "max_tokens": int(r.integers(32, 50)),
                         "stream": True,
                         "priority": ("interactive"
                                      if r.random() < 0.7 else "batch")})
        return reqs

    def storm_arm(drain: bool):
        la = loadavg()
        dep2 = build_gpt_deployment(
            cfg=cfg, engine_cfg=EngineConfig(max_slots=slots), seed=0,
            num_replicas=storm_replicas, warm_on_init=True,
            max_concurrent_queries=4 * slots)
        serve.run(dep2, use_actors=False, http=True)
        addr2 = serve.proxy_address()
        f2 = fleet_mod.enable("v1", fleet_mod.FleetConfig(
            rate=storm_rate * 2.0, burst=storm_rate,
            max_queue_depth=int(storm_rate * 1.5),
            interactive_wait_s=4.0, batch_wait_s=10.0, seed=14,
            drain_deadline_s=storm_deadline))
        st2 = serve.get_handle("v1")._state
        _post(addr2, {"prompt": [1, 2], "max_tokens": 2}, 60)
        r = np.random.default_rng(1400)           # SAME trace both arms
        arr = _thin(r, lambda t: storm_rate, storm_rate, storm_dur)
        reqs = storm_requests(r, len(arr))
        t0 = time.perf_counter()
        storm = ScaleDownStorm(st2, drain, period=storm_period,
                               deadline_s=storm_deadline,
                               n=storm_replicas, t0=t0)
        storm.start()
        outcomes, wall, lag, _ = replay_streams(addr2, arr, reqs,
                                                timeout=60)
        storm.stop()
        storm.join(timeout=storm_deadline + 10)
        # settle any drain still open before reading the counters
        deadline = time.time() + storm_deadline + 5
        while st2.draining and time.time() < deadline:
            time.sleep(0.05)
        snap2 = f2.fleet_snapshot()
        wp99, wn = window_p99(outcomes, storm.pulses)
        counts = {}
        for o in outcomes:
            counts[o["outcome"]] = counts.get(o["outcome"], 0) + 1
        lat = [o["latency_s"] for o in outcomes
               if o["outcome"] == "completed"]
        serve.shutdown()
        return {
            "mode": "drain" if drain else "kill_resume",
            "offered": len(outcomes),
            "completed": counts.get("completed", 0),
            "truncated": counts.get("truncated", 0),
            "shed": counts.get("shed", 0),
            "errors": counts.get("error", 0),
            "wall_s": round(wall, 2),
            "pacing_lag_s": round(lag, 3),
            "scale_down_pulses": storm.pulses,
            "p50_s": round(_pct(lat, 50), 4),
            "p99_s": round(_pct(lat, 99), 4),
            "scale_down_window_p99_s": round(wp99, 4),
            "scale_down_window_n": wn,
            "counters": {k: v for k, v in snap2.items()
                         if isinstance(v, int)},
            "loadavg_1m": [la, loadavg()],
        }

    storm_kill = storm_arm(drain=False)
    print(f"storm kill+resume: {storm_kill}")
    storm_drain = storm_arm(drain=True)
    print(f"storm drain: {storm_drain}")

    # ---- assemble + acceptance gates -----------------------------------
    peak_slots = max((row["total_slots"] for row in sampler.rows),
                     default=0)
    peak_replicas = max((row["replicas"] for row in sampler.rows),
                       default=0)
    scale_events = [e for e in events if e["kind"] == "scale"]
    offered_total = sum(p["offered"] for p in fleet_phases.values())
    accounted = sum(p["completed"] + p["shed"] + p["errors"]
                    for p in fleet_phases.values())
    # fleet-side cross-check: everything admitted finished one way
    fleet_accounted = (snap["admitted"]
                       == snap["completed"] + snap["errored"]
                       + snap["cancelled"])
    nominal_p99 = fleet_phases["1.0x"]["interactive_p99_s"]
    kc, dc = storm_kill["counters"], storm_drain["counters"]
    n_pulses_drain = len(storm_drain["scale_down_pulses"])
    gates = {
        "total_slots_ge_64": peak_slots >= 64,
        "autoscaled": peak_replicas >= 4 and len(scale_events) >= 2,
        "interactive_p99_slo_met_at_nominal":
            nominal_p99 <= SLO_INTERACTIVE_P99_S,
        "zero_silently_dropped": offered_total == accounted,
        "fleet_accounting_consistent": fleet_accounted,
        # r14 drain acceptance: every scale-down accounted (drained /
        # drain_timeout / resumed_scale_down), failure-resumes ZERO in
        # both arms (no chaos ran), replay cost and scale-down-window
        # tail both improved by draining — same-run A/B
        "storm_zero_masked_resumes": (
            kc["resumed_failure"] == 0 and dc["resumed_failure"] == 0
            and dc["drained"] + dc["drain_timeout"] >= n_pulses_drain),
        "storm_replayed_tokens_improved":
            dc["replayed_tokens"] <= kc["replayed_tokens"],
        # both windows must actually contain completions: _pct([]) is
        # 0.0, and an empty window would pass (or fail) this vacuously
        "storm_window_p99_improved": (
            storm_kill["scale_down_window_n"] > 0
            and storm_drain["scale_down_window_n"] > 0
            and storm_drain["scale_down_window_p99_s"]
            <= storm_kill["scale_down_window_p99_s"]),
        "storm_no_truncated_streams":
            storm_kill["truncated"] == 0
            and storm_drain["truncated"] == 0,
    }
    artifact = {
        "round": perf.ROUND,
        "quick": bool(args.quick),
        "_conditions": {
            "loadavg_1m": {"baseline": [load0, load1],
                           "fleet": [load2, load3]},
            "backend": jax.default_backend(),
            "physical_cores": os.cpu_count(),
            "note": "same-run A/B; only ratios are portable across "
                    "days (PERF.md box-variance caveat)",
        },
        "model": {"d_model": cfg.d_model, "n_layers": cfg.n_layers,
                  "n_heads": cfg.n_heads, "d_ff": cfg.d_ff,
                  "vocab": cfg.vocab_size, "max_seq": cfg.max_seq},
        "fleet_config": {
            "slots_per_replica": slots, "max_replicas": max_replicas,
            "admission_rate_req_s": round(nominal * 2.0, 1),
            "queue_depth": int(nominal * 1.5),
            "variants": ["base", "alt"], "multiplex_capacity": 2,
            "declared_slo": {"interactive_p99_s": SLO_INTERACTIVE_P99_S,
                             "at_level": "1.0x"},
        },
        "arrival_processes": {
            "bursty": "square wave, 4s period, 50% duty, peak=1.6x "
                      "mean, base=0.4x mean",
            "diurnal": "sinusoid trough 0.2x -> peak 2x nominal over "
                       f"{dur}s",
            "nominal_rate_req_s": round(nominal, 1),
        },
        "baseline_single_engine": phases["baseline_single_engine"],
        "fleet": {
            "degradation_curve": fleet_phases,
            "peak_total_slots": peak_slots,
            "peak_replicas": peak_replicas,
            "scale_events": len(scale_events),
            "counters": snap,
            "ingress_event_counts": event_kinds,
        },
        "autoscale_trace": {"marks": sampler.marks,
                            "rows": sampler.rows},
        "ab_nominal": {
            "baseline_p99_s": base_phases["1.0x"]["p99_s"],
            "fleet_p99_s": fleet_phases["1.0x"]["p99_s"],
            "baseline_goodput": base_phases["1.0x"]["goodput_req_s"],
            "fleet_goodput": fleet_phases["1.0x"]["goodput_req_s"],
        },
        "scale_down_storm": {
            "config": {"replicas": storm_replicas,
                       "drain_deadline_s": storm_deadline,
                       "pulse_period_s": round(storm_period, 2),
                       "offered_rate_req_s": round(storm_rate, 1),
                       "trace": "steady Poisson, all streaming, "
                                "identical seed both arms"},
            "kill_resume": storm_kill,
            "drain": storm_drain,
            "ab": {
                "replayed_tokens": {
                    "kill_resume": kc["replayed_tokens"],
                    "drain": dc["replayed_tokens"]},
                "scale_down_window_p99_s": {
                    "kill_resume":
                        storm_kill["scale_down_window_p99_s"],
                    "drain": storm_drain["scale_down_window_p99_s"]},
                "resumes": {
                    "kill_resume": {
                        "scale_down": kc["resumed_scale_down"],
                        "failure": kc["resumed_failure"]},
                    "drain": {
                        "scale_down": dc["resumed_scale_down"],
                        "failure": dc["resumed_failure"],
                        "drained": dc["drained"],
                        "drain_timeout": dc["drain_timeout"]}},
            },
        },
        "ab_overload_4x": {
            "baseline_p99_s": base_phases["4.0x"]["p99_s"],
            "fleet_p99_s": fleet_phases["4.0x"]["p99_s"],
            "baseline_goodput": base_phases["4.0x"]["goodput_req_s"],
            "fleet_goodput": fleet_phases["4.0x"]["goodput_req_s"],
            "baseline_shed_fraction":
                base_phases["4.0x"]["shed_fraction"],
            "fleet_shed_fraction": fleet_phases["4.0x"]["shed_fraction"],
            "note": "overload: the unprotected path absorbs everything "
                    "into queueing latency; the fleet sheds the excess "
                    "(429 + Retry-After) and holds p99 for what it "
                    "admits",
        },
        "acceptance": gates,
    }
    out = json.dumps(artifact, indent=1)
    print(out)
    with open(out_path, "w") as fo:
        fo.write(out + "\n")
    ok = all(gates.values())
    print("\nacceptance: " + ", ".join(
        f"{k}={'PASS' if v else 'FAIL'}" for k, v in gates.items()))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
