"""Traffic kind ``open_loop_http_nemotron_h``: the served path of the
hybrid layer function in its ``nemotron_h`` layout (single-mixer layers:
Mamba-2 with several B/C groups, attention, sigmoid-routed relu^2
experts; ``ray_tpu/models/hybrid.py``) under a fixed offered rate.

The same run as ``open_loop_http_recurrent`` — ``serve.run(
build_gpt_deployment(...), use_actors=False, http=True)`` in this
process, streamed ``POST /v1/generate`` from the ``loadgen.py`` child,
greedy, ``eos`` off, the lead-in / window / drain of
``traffic_gen.chat_requests``, times taken at the client from when each
request was DUE, warm-up of both programs, the gauges polled once a
second under ``--trace 1``.  What this kind owns: ``model_config`` (the
``nemotron_h`` keys of the configuration file, this chip's share of the
experts and the vocabulary), the reference it checks against
(``chipbench/reference/nemotron_h.py``) and the statistic of the check
(a quantile of the checked tokens' margins, ``margin_quantile`` of the
mix, where the other kinds take the maximum: see ``run``), the label
table of its trace (``chipbench/nemotron_trace.py``) and the two
counters of experts touched, how the seeded weights are made
(``make_params``: the program's init, then the routers' selection bias
balanced as the published model's training balances it) and a watch on
the host (``HostWatch``: what stalled a run, if anything did).  The load
generator, the client reduction, the engine's counters, the sample and
the verdict are imported from the files that have them.

The model module is imported FIRST, and asked for the layout: a checkout
whose program lacks it stops here, at once, with a message, before any
weight or pool is allocated.
"""

from __future__ import annotations

from ray_tpu.models import hybrid          # noqa: E402  (first: see above)

if not hasattr(hybrid, "PATTERN_KINDS"):
    raise SystemExit("ray_tpu/models/hybrid.py of this checkout has no "
                     "nemotron_h layout (single-mixer layers): the cell "
                     "cannot run here")

import gc                                   # noqa: E402
import json                                 # noqa: E402
import os                                   # noqa: E402
import tempfile                             # noqa: E402
import time                                 # noqa: E402

from chipbench.traffic.open_loop_http import (COUNTERS, ROUTE,    # noqa: E402
                                              client_metrics,
                                              engine_counters, pick_checked,
                                              run_loadgen, verdict)
from chipbench.traffic.open_loop_http_recurrent import (     # noqa: E402
    EXPERT_COUNTERS)


def model_config(config: dict):
    """``chipbench/configs/<name>.json`` -> (``hybrid.HybridConfig`` of
    this chip's share, the published keys as the reference reads them,
    the held expert range)."""
    # the router keeps its published width; the file's own key counts
    # the experts HELD here
    published = {**config, "n_routed_experts":
                 config["published"]["n_routed_experts"]}
    held = (0, config["n_routed_experts"])
    cfg = hybrid.HybridConfig.from_published(
        published, vocab_size=config["vocab_size"], experts_held=held,
        max_seq=config["engine"]["max_seq"], **config.get("hybrid_config",
                                                          {}))
    return cfg, published, held


def balance_selection_bias(cfg, params, key, tokens: int,
                           rounds: int):
    """The tree with every experts sublayer's ``router_bias`` set so
    that, over ``tokens`` seeded ids run through the program's own layer
    function, the k largest of ``score + bias`` load all the router's
    experts alike.

    Why: the published model is trained with a selection bias whose one
    job is to keep the experts' load even.  Seeded N(0, 0.02) weights
    have no such thing: a relu^2 expert's output has a large part that
    is the same for every token, the next router sees it as a fixed
    offset an expert, and the tokens crowd onto a few experts, other ones
    a seed (at the published widths, PR 38: the busiest held expert
    2.4 x the mean at the first experts sublayer and 5 x at the fifth;
    55-63 % of the 64 held experts touched by a decode pass of 24-30
    rows where an even router touches 68-76 %).  A decode pass then reads
    a fifth fewer expert weights than the deployment's would, and how
    many depends on the seed: the pass's time moved 3.6 % between seeds
    (1.7 % balanced), and the cell's
    tails with it.  The bias is found the way the published recipe
    updates it (a step against the sign of each expert's excess load),
    sublayer by sublayer in order, each on the stream that the balanced
    sublayers before it leave."""
    import jax

    ids = jax.random.randint(key, (1, tokens), 0, cfg.vocab_size)
    block, bias_of = balance_programs(cfg, tokens, rounds)
    x = hybrid.embed(cfg, params, ids)
    layers = [dict(lp) for lp in params["layers"]]
    for i, kind in cfg.sublayers:
        name = "ffn" if kind == hybrid.EXPERTS else "mixer"
        if kind == hybrid.EXPERTS:
            layers[i][name] = {**layers[i][name],
                               "router_bias": bias_of(layers[i][name], x)}
        x = block[kind](layers[i][name], x)
    return {**params, "layers": layers}


def balance_programs(cfg, tokens: int, rounds: int):
    """-> ({kind: the program's sublayer of that kind on one window of
    ``tokens`` from zero state, ``(lp, x [1, tokens, d]) -> x``}, the
    balanced bias of an experts sublayer given its input stream, ``(lp,
    x) -> [E]``), jitted."""
    import jax
    import jax.numpy as jnp

    k, n = cfg.experts_per_token, cfg.n_experts
    n_valid = jnp.full((1,), tokens, jnp.int32)
    attend = hybrid.causal_attend(cfg)

    def past(kind):
        return {hybrid.MAMBA: hybrid.zero_state(cfg, 1),
                hybrid.ATTENTION: attend}.get(kind)

    @jax.jit
    def bias_of(lp, x):
        xf = x[0].astype(jnp.float32)
        h = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                               + cfg.rms_eps) * lp["norm"]
        scores = jax.nn.sigmoid(jnp.dot(
            h.astype(x.dtype), lp["router"].astype(x.dtype),
            preferred_element_type=jnp.float32))

        def step(i, bias):
            _, chosen = jax.lax.top_k(scores + bias, k)
            load = jnp.zeros((n,)).at[chosen.reshape(-1)].add(1.0)
            size = 0.02 * (1.0 - i / rounds) + 0.0005
            return bias + size * jnp.sign(tokens * k / n - load)
        return jax.lax.fori_loop(0, rounds, step, jnp.zeros((n,)))

    block = {kind: jax.jit(lambda lp, x, kind=kind: hybrid.block(
        cfg, kind, lp, x, past(kind), n_valid)[0])
        for kind in set(cfg.layer_types)}
    return block, bias_of


def make_params(cfg, config: dict, seed: int):
    """The cell's weights from the seed: ``hybrid.init_params``, then
    ``balance_selection_bias`` as the configuration's ``selection_bias``
    says."""
    import jax

    from chipbench.model import fold_seed
    params = jax.jit(lambda key: hybrid.init_params(cfg, key))(
        jax.random.PRNGKey(fold_seed(seed, 0)))
    return jax.block_until_ready(balance_selection_bias(
        cfg, params, jax.random.PRNGKey(fold_seed(seed, 5)),
        **config["selection_bias"]))


def judge(margins, mix: dict) -> dict:
    """The numbers the check compares, each beside its limit: two
    quantiles of the checked tokens' margins, the bulk
    (``margin_quantile`` within ``tie_tolerance``) and the tail
    (``tail_quantile`` within ``tail_tolerance``).  Not the maximum,
    which the other serving kinds take: the mix's
    ``tie_tolerance_reason`` has the readings."""
    import numpy as np
    out = {}
    for q, limit in ((mix["margin_quantile"], mix["tie_tolerance"]),
                     (mix["tail_quantile"], mix["tail_tolerance"])):
        out[f"margin_p{q}"] = {
            "value": float(np.quantile(margins, q / 100.0))
            if len(margins) else 0.0, "limit": limit}
    return out


def expert_counters(handle) -> dict:
    st = handle.options(method_name="engine_stats").remote().result(
        timeout=30)
    out = {k: st[k] for k in EXPERT_COUNTERS}
    out["state_rows_in_use"] = st["state_rows_in_use"]
    out["state_bytes"] = st["state_bytes"]
    return out


def run(ctx) -> dict:
    import jax
    import numpy as np

    from ray_tpu import serve
    from ray_tpu._compile_cache import compile_cache_stats
    from ray_tpu.inference import EngineConfig, build_gpt_deployment

    from chipbench import nemotron_trace, spans, stats, trace_reduce
    from chipbench.host_watch import HostWatch
    from chipbench.model import device_memory_peak
    from chipbench.reference import nemotron_h as ref
    from chipbench.traffic_gen import chat_requests

    mix, config = ctx.mix, ctx.config
    if ctx.rehearse:
        # run.py's fixture knows the GPT keys only; this kind's CPU
        # sizes are a fixture of its own
        with open(os.path.join(ctx.root, "chipbench", "tests",
                               "rehearse_nemotron_h.json")) as f:
            own = json.load(f)
        config = {**config, **own["config"]}
        mix = {**mix, **own["traffic"]}
    cfg, published, held = model_config(config)
    engine_cfg = EngineConfig(**config["engine"])
    stamps = stats.Stamps(ctx.t_start)
    stamps.mark("import_s")
    params = make_params(cfg, config, ctx.seed)
    stamps.mark("weights_s")
    ctx.log(f"weights on the device: {hybrid.num_params(params) / 1e6:.0f} M "
            f"parameters")
    handle = serve.run(
        build_gpt_deployment(name=ROUTE, cfg=cfg, engine_cfg=engine_cfg,
                             params=params, **config["deployment_args"]),
        use_actors=False, http=True)
    addr = serve.proxy_address()
    host, port = addr[len("http://"):].split(":")
    port = int(port)
    stamps.mark("programs_s")
    stamps.cache("after_programs", compile_cache_stats())
    ctx.log(f"deployment up at {addr}")
    trace = {"polls": []}
    try:
        # ---- warm-up (set-up): two chunks, the second partial; decode
        rng = np.random.default_rng([int(ctx.seed), 9])
        warm = rng.integers(0, cfg.vocab_size,
                            engine_cfg.prefill_chunk + 44).tolist()
        got = run_loadgen(ctx, host, port, [
            {"id": 0, "due_s": 0.0, "prompt": warm, "max_tokens": 4}],
            time.monotonic(), 600.0)[0]
        if got["ended"] != "done":
            raise RuntimeError(f"warm-up request failed: {got}")
        stamps.mark("warmup_s")
        ctx.log("warm-up done")

        # ---- the window
        requests = chat_requests(mix, ctx.seconds, ctx.seed, cfg.vocab_size)
        sent = [{k: r[k] for k in ("id", "due_s", "prompt", "max_tokens")}
                for r in requests]
        lead_s = max([0.0] + [-r["due_s"] for r in requests])
        t0 = time.monotonic() + 1.0 + lead_s
        setup_s = t0 - ctx.t_start
        stamps.mark("lead_in_s", at=t0)

        def counters():
            return {**engine_counters(handle), **expert_counters(handle)}

        def sleep_until(t):
            """Sleep to ``t``; with a trace asked for, poll the gauges
            once a second on the way."""
            while True:
                left = t - time.monotonic()
                if left <= 0:
                    return
                if ctx.trace and "at_window_start" in trace and left > 1.0:
                    time.sleep(1.0)
                    trace["polls"].append(expert_counters(handle))
                else:
                    time.sleep(left)

        def mid():
            """Runs here while the child offers the load."""
            sleep_until(t0)
            trace["at_window_start"] = counters()
            trace["compiles_at_start"] = compile_cache_stats()
            if ctx.trace:
                sleep_until(t0 + 0.45 * ctx.seconds)
                trace["dir"] = tempfile.mkdtemp(prefix="chipbench_trace_")
                jax.profiler.start_trace(trace["dir"])
                time.sleep(min(mix["trace_s"], 0.4 * ctx.seconds))
                jax.profiler.stop_trace()
            sleep_until(t0 + ctx.seconds)
            trace["at_window_end"] = counters()
            trace["compiles_at_end"] = compile_cache_stats()

        watch = HostWatch()
        watch.start(t0)
        recs = run_loadgen(ctx, host, port, sent, t0,
                           ctx.seconds + mix["drain_s"], mid=mid)
        host_watch = watch.report()
    finally:
        serve.shutdown()
    before, c0 = trace["at_window_start"], trace["compiles_at_start"]
    after, c1 = trace["at_window_end"], trace["compiles_at_end"]
    compiles_in_window = (c1["hits"] + c1["misses"]
                          - c0["hits"] - c0["misses"])
    stamps.cache("at_window_start", c0)
    del handle
    gc.collect()
    # the window's peak, read before the reference puts anything on the chip
    memory_peak = device_memory_peak(jax.devices())

    # ---- reduction (client side)
    by_id = {r["id"]: r for r in recs}
    client = client_metrics(requests, by_id, ctx.seconds)
    for r_id, why in client["failures"]:
        ctx.log(f"request {r_id} failed: {why}")
    ttft, gaps, late, failed = (client["ttft"], client["gaps"],
                                client["late"], len(client["failures"]))
    end_to_end = {}
    if ttft and gaps:
        end_to_end = {
            "ttft_p90_ms": 1e3 * stats.percentile(ttft, 90),
            "itl_p95_ms": 1e3 * stats.percentile(gaps, 95),
            "serve_tokens_per_s": client["tokens_in_window"] / ctx.seconds,
        }

    # ---- correctness: a seeded sample through the plain reference,
    # now that the engine's pools are freed
    done = [r for r in requests if by_id.get(r["id"], {}).get("ended")
            == "done"]
    t_ref = time.monotonic()
    picks = pick_checked(done, ctx.seed, mix["checked_requests"])
    margins, disagreed = [np.zeros(0)], 0
    for r in picks:
        emitted = by_id[r["id"]]["tokens"]
        m, best = ref.margins(params, r["prompt"], emitted, published, held,
                              engine_cfg.max_seq)
        margins.append(m)
        disagreed += int((best != np.asarray(emitted)).sum())
    stamps.notes["reference_check_s"] = time.monotonic() - t_ref
    # The numbers compared are two QUANTILES of the checked tokens'
    # margins, not their maximum (``judge``): with this family's
    # unit-size logits a router's near-tie flipped by a rounding moves a
    # logit by ~1, in the served path and in the float8 control alike,
    # so the maximum over thousands of tokens does not tell the two
    # apart; the bulk and the tail do.
    margins = np.concatenate(margins)
    checked_tokens = len(margins)
    judged = judge(margins, mix)
    worst = float(margins.max()) if checked_tokens else 0.0
    ctx.log("reference: " + ", ".join(
        f"{k} {v['value']:.6f} (limit {v['limit']})"
        for k, v in judged.items())
        + f", worst {worst:.6f} over {len(picks)} requests; {disagreed} of "
        f"{checked_tokens} tokens are not the reference's argmax")
    correct, checks = verdict(failed, compiles_in_window, 0.0, 0.0,
                              len(picks), bool(end_to_end))
    del checks["worst_margin"]
    checks.update(judged)
    correct = correct and all(v["value"] <= v["limit"]
                              for v in judged.values())

    counters = {k: after[k] - before[k]
                for k in COUNTERS + EXPERT_COUNTERS}
    counters["occupancy_sum"] = after["occupancy_sum"] \
        - before["occupancy_sum"]
    polls = trace["polls"] or [before, after]
    obs = {"window_s": ctx.seconds, "counters": counters,
           "state_rows_mean": sum(p["state_rows_in_use"] for p in polls)
           / len(polls),
           "max_slots": engine_cfg.max_slots, "published": published,
           "held": held, "expert_layers": cfg.layer_types.count(
               hybrid.EXPERTS)}
    if ctx.trace and "dir" in trace:
        import shutil
        path = trace_reduce.find_xplane(trace["dir"])
        obs["trace"] = trace_reduce.summarize(trace_reduce.load_events(path))
        obs["scoped"] = nemotron_trace.summarize(nemotron_trace.load_events(
            path, nemotron_trace.marks_of(published, engine_cfg.max_slots,
                                          engine_cfg.prefill_chunk)))
        shutil.rmtree(trace["dir"], ignore_errors=True)
    notes = {
        "requests": len(requests), "rate_per_s": mix["rate_per_s"],
        "lead_in_requests": sum(1 for r in requests if r["lead"]),
        "in_flight_at_window_start": before["active_slots"]
        + before["waiting_requests"],
        "offered_tokens_per_s": sum(r["max_tokens"] for r in requests
                                    if not r["lead"]) / ctx.seconds,
        "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50) if ttft else None,
        "itl_p50_ms": 1e3 * stats.percentile(gaps, 50) if gaps else None,
        "ttft_samples": len(ttft), "itl_samples": len(gaps),
        "lateness_p99_ms": 1e3 * stats.percentile(late, 99) if late else None,
        "in_flight_at_window_end": after["active_slots"]
        + after["waiting_requests"],
        "waiting_at_window_end": after["waiting_requests"],
        "blocks_free_at_window_end": after["blocks_free"],
        "cache_bytes": after["cache_bytes"],
        "state_bytes": after["state_bytes"],
        "counters": counters, "compiles_in_window": compiles_in_window,
        "worst_margin": worst,
        **{k: v["value"] for k, v in judged.items()},
        "margin_p50": float(np.median(margins)) if checked_tokens else 0.0,
        "tie_tolerance": mix["tie_tolerance"],
        "tail_tolerance": mix["tail_tolerance"],
        "checked_requests": len(picks), "checked_tokens": checked_tokens,
        "tokens_not_reference_argmax": disagreed,
        "setup_stamps": stamps.notes, "host_watch": host_watch,
    }
    if ctx.trace:
        # how often the engine's prefill policy has anything to decide
        # (it differs by the rows' occupancy only where two or more rows
        # prefill at once), over the traced passes
        seen = [p["attributes"] for p, _ in spans.whole_passes(
            {**obs, "end_to_end": {"setup_s": setup_s}})]
        two = [a for a in seen if a.get("prefilling", 0) >= 2]
        notes["traced_passes"] = {
            "n": len(seen), "two_or_more_prefilling": len(two),
            "of_those_at_half_occupancy_or_over": sum(
                1 for a in two if 2 * a["active"] >= engine_cfg.max_slots)}
    return {"correct": correct, "attempted": len(requests),
            "failed": failed, "setup_s": setup_s,
            "end_to_end": end_to_end, "obs": obs, "notes": notes,
            "checks": checks, "memory_peak_bytes": memory_peak}
