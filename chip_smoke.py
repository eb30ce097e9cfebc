"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, at the
full width of GPT-2 124M (seeded random weights), in ONE process that
holds the chip:

  default (one chip)
    workers  ray_tpu.init() starts CPU-pinned worker processes while this
             process holds the chip; none of them may map the TPU library
    train    JaxTrainer, gpt2_124m(remat dots), batch 16 x 1025, 6 steps
             on a repeated seeded batch: finite falling loss, the flash
             kernel in the compiled loss-and-gradient program, and a
             second fit in this process served by the persistent cache
    serve    serve.run(build_gpt_deployment(...), http=True): one-shot,
             streamed and shared-prefix requests over real HTTP, greedy
             tokens checked against gpt.generate / gpt.forward on the
             same params, prefix_hit_rate > 0
  --chips 4 (run by the builder; the driver never passes it)
    train_mesh  the train phase on mesh {"dp": 2, "tp": 2} against the
                same seed and batch on one device, step by step
    serve_tp2   a tp=2 engine against the unmeshed engine, same prompts
    and no other phase.

Each phase prints one JSON line on stdout as it finishes; everything
else (logs, warnings, worker output) goes to stderr.  The LAST stdout
line is {"ok": true, "device": {"platform", "kind", "count"}} — written
after every shutdown has run.  Any failed phase: non-zero exit and
"ok": false.  No TPU (and no --rehearse): non-zero exit, nothing on
stdout.  --rehearse is the CPU dress rehearsal at a tiny size with the
kernels interpreted; it refuses to run on a TPU.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import traceback
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

# a greedy token is accepted when its teacher-forced reference logit is
# within 4 bf16 ulps of that step's maximum at the logit magnitude the
# seeded model produces (|logit| in [2, 4) -> ulp 2^-6): two correct
# bf16 programs may break a near-tie differently, not more than that
TIE_TOL = 2.0 ** -4
# meshed vs single-device loss: bf16 activations (eps 2^-8 ~ 0.4% of a
# loss near 10.8) summed in a different order by tp
LOSS_TOL = 0.05
SEED = 0      # weights, batch and prompts all derive from it
STEPS = 6

_out = None   # the real stdout; fd 1 itself is pointed at stderr


def emit(obj: dict) -> None:
    _out.write(json.dumps(obj) + "\n")
    _out.flush()


def log(msg: str) -> None:
    sys.stderr.write(f"[chip_smoke] {msg}\n")
    sys.stderr.flush()


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ------------------------------------------------------------------ set-up

def parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU dress rehearsal: tiny model, interpreted "
                         "kernels; refused on a TPU")
    return ap.parse_args()


def descendants() -> list[int]:
    """Live processes below this one (the workers and the fork server
    start in their own sessions but stay our children)."""
    parent = {}
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat") as f:
                stat = f.read()
        except OSError:
            continue
        fields = stat.rsplit(")", 1)[1].split()
        if fields[0] != "Z":
            parent[int(name)] = int(fields[1])
    me, out = os.getpid(), []
    for pid in parent:
        p = pid
        while p in parent and p != me:
            p = parent[p]
        if p == me and pid != me:
            out.append(pid)
    return out


def maps_libtpu(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/maps") as f:
            return "libtpu" in f.read()
    except OSError:
        return False   # exited between the walk and the read


def build_native() -> dict:
    """Rebuild the C++ store from native/src so no stale library rides
    along in a copied tree (the .so files are git-ignored)."""
    t0 = time.time()
    subprocess.run(["make", "-s", "-B", "-C", os.path.join(HERE, "native"),
                    "all"], check=True, stdout=sys.stderr, stderr=sys.stderr)
    from ray_tpu import native
    native.load_library()
    return {"native_store": "rebuilt from native/src",
            "native_build_s": round(time.time() - t0, 1)}


def model_configs(args):
    from ray_tpu.inference import EngineConfig
    from ray_tpu.models import gpt
    if not args.rehearse:
        return (gpt.GPTConfig.gpt2_124m(remat=True, remat_policy="dots"),
                gpt.GPTConfig.gpt2_124m(), 16,
                EngineConfig(max_slots=8))
    # rehearsal: same code paths, toy sizes; head_dim 64 and seq 128
    # keep the flash kernel's tiling rule satisfied so it is the
    # interpreted KERNEL that runs, not the reference
    tiny = dict(vocab_size=512, max_seq=128, d_model=128, n_heads=2,
                n_layers=2, d_ff=256)
    return (gpt.GPTConfig(**tiny, remat=True, remat_policy="dots",
                          attn_impl="flash"),
            gpt.GPTConfig(**tiny), 4, EngineConfig(max_slots=8))


def seeded_batch(cfg, batch: int) -> dict:
    import numpy as np
    rng = np.random.default_rng(SEED)
    return {"tokens": rng.integers(0, cfg.vocab_size,
                                   (batch, cfg.max_seq + 1), dtype=np.int32)}


# ------------------------------------------------------------------- train

def fit(cfg, batch: dict, mesh_axes: dict, steps: int, run_dir: str,
        name: str):
    import optax

    from ray_tpu.models import gpt
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    def repeated():
        while True:
            yield batch

    trainer = JaxTrainer(
        loss_fn=lambda p, b, mesh=None, rules=None: gpt.loss_fn(
            p, b, cfg, mesh=mesh, rules=rules),
        init_params=lambda rng: gpt.init_params(cfg, rng),
        optimizer=optax.adamw(3e-4, weight_decay=0.1),
        train_data=repeated(), num_steps=steps,
        params_logical=gpt.param_logical_axes(cfg),
        report_every=1, seed=SEED,
        scaling_config=ScalingConfig(mesh=mesh_axes),
        run_config=RunConfig(name=name, storage_path=run_dir))
    t0 = time.time()
    result = trainer.fit()
    secs = time.time() - t0
    hist = result.metrics_history
    check(len(hist) == steps, f"{len(hist)} reports for {steps} steps")
    losses = [float(m["loss"]) for m in hist]
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    return trainer, losses, secs


def n_devices_of(tree) -> list[int]:
    import jax
    return sorted({len(x.sharding.device_set)
                   for x in jax.tree.leaves(tree)
                   if hasattr(x, "sharding") and x.ndim > 0})


def loss_grad_program(cfg, trainer, batch: dict):
    """The loss-and-gradient program inside the step ``trainer`` just
    ran — same loss_fn, mesh, rules, parameter shardings and batch —
    compiled on its own so that its text can be read."""
    import jax

    from ray_tpu.models import gpt
    from ray_tpu.parallel.sharding import DEFAULT_LLM_RULES
    from ray_tpu.train.step import shard_batch

    mesh = trainer.gang.mesh
    return jax.jit(jax.value_and_grad(
        lambda p, b: gpt.loss_fn(p, b, cfg, mesh=mesh,
                                 rules=DEFAULT_LLM_RULES))).lower(
        trainer.final_state.params, shard_batch(batch, mesh)).compile()


def phase_train(args, run_dir: str) -> dict:
    import jax

    from ray_tpu._compile_cache import compile_cache_stats

    cfg, _, bsz, _ = model_configs(args)
    batch = seeded_batch(cfg, bsz)
    before = compile_cache_stats()
    trainer, losses, secs = fit(cfg, batch, {"dp": -1}, STEPS, run_dir,
                                "smoke")
    first = compile_cache_stats()
    ln_v = math.log(cfg.vocab_size)
    check(abs(losses[0] - ln_v) < 1.0,
          f"first loss {losses[0]} not within 1.0 of ln(vocab)={ln_v:.3f}")
    check(losses[-1] < losses[0],
          f"loss did not fall on a repeated batch: {losses}")

    compiled = loss_grad_program(cfg, trainer, batch)
    has_kernel = "tpu_custom_call" in compiled.as_text()
    if not args.rehearse:
        check(has_kernel, "no tpu_custom_call in the compiled loss and "
                          "gradient: attention ran without the flash kernel")
    mem = compiled.memory_analysis()
    mesh_shape = dict(trainer.gang.mesh.shape)
    del trainer, compiled
    gc.collect()
    jax.clear_caches()

    # the same step compiled a second time in this process, by the same
    # entry point: every program must come from the persistent cache
    mid = compile_cache_stats()
    trainer, again, secs2 = fit(cfg, batch, {"dp": -1}, 1, run_dir, "again")
    after = compile_cache_stats()
    hits2, misses2 = after["hits"] - mid["hits"], after["misses"] - mid["misses"]
    check(hits2 > 0 and misses2 == 0,
          f"second fit in this process: {hits2} persistent-cache hits, "
          f"{misses2} misses (expected every program to hit)")
    check(abs(again[0] - losses[0]) < 1e-3,
          f"same seed, same batch, another first loss: {again[0]} vs "
          f"{losses[0]}")
    del trainer
    gc.collect()
    jax.clear_caches()
    return {
        "phase": "train", "ok": True, "model": "gpt2_124m"
        if not args.rehearse else "rehearsal-tiny",
        "batch": [bsz, cfg.max_seq + 1], "steps": STEPS,
        "losses": [round(x, 4) for x in losses],
        "ln_vocab": round(ln_v, 4),
        "attention": ("pallas flash (tpu_custom_call in loss+grad)"
                      if has_kernel else "pallas flash, interpreted"),
        "fit_s_incl_compile": round(secs, 1),
        "cache_hits_first_fit": first["hits"] - before["hits"],
        "cache_misses_first_fit": first["misses"] - before["misses"],
        "second_fit_s_incl_compile": round(secs2, 1),
        "cache_hits_second_fit": hits2,
        "cache_misses_second_fit": misses2,
        "loss_grad_temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "mesh": mesh_shape,
    }


def phase_train_mesh(args, run_dir: str) -> dict:
    import jax

    from ray_tpu.train.step import shard_batch

    cfg, _, bsz, _ = model_configs(args)
    batch = seeded_batch(cfg, bsz)
    trainer, meshed, secs_m = fit(cfg, batch, {"dp": 2, "tp": 2}, STEPS,
                                  run_dir, "mesh")
    mesh = trainer.gang.mesh
    st = trainer.final_state
    spread = {"params": n_devices_of(st.params),
              "opt_state": n_devices_of(st.opt_state),
              "batch": n_devices_of(shard_batch(batch, mesh))}
    check(all(v == [4] for v in spread.values()),
          f"state not spread over 4 devices: {spread}")
    has_kernel = ("tpu_custom_call"
                  in loss_grad_program(cfg, trainer, batch).as_text())
    if not args.rehearse:
        check(has_kernel, "no tpu_custom_call in the meshed loss and "
                          "gradient")
    del trainer, st
    gc.collect()
    jax.clear_caches()

    # {"dp": 1} is a one-device mesh on jax.devices()[:1]
    trainer, single, secs_s = fit(cfg, batch, {"dp": 1}, STEPS, run_dir,
                                  "single")
    check(trainer.gang.mesh.size == 1, "reference run is not one device")
    del trainer
    gc.collect()
    jax.clear_caches()
    diffs = [abs(a - b) for a, b in zip(meshed, single)]
    check(max(diffs) <= LOSS_TOL,
          f"meshed vs single-device loss differ by {max(diffs)} > "
          f"{LOSS_TOL}: {meshed} vs {single}")
    check(meshed[-1] < meshed[0], f"meshed loss did not fall: {meshed}")
    return {"phase": "train_mesh", "ok": True,
            "mesh": {"dp": 2, "tp": 2}, "steps": STEPS,
            "losses_mesh": [round(x, 4) for x in meshed],
            "losses_one_device": [round(x, 4) for x in single],
            "max_abs_loss_diff": round(max(diffs), 5), "tol": LOSS_TOL,
            "devices_per_leaf": spread,
            "kernel_in_meshed_loss_grad": has_kernel,
            "fit_s_mesh": round(secs_m, 1), "fit_s_single": round(secs_s, 1)}


# ------------------------------------------------------------------- serve

def post_json(addr: str, route: str, body: dict) -> dict:
    req = urllib.request.Request(
        f"{addr}/{route}/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    # a failed engine step surfaces as a 500 here -> HTTPError -> the
    # phase fails
    with urllib.request.urlopen(req, timeout=600) as resp:
        check(resp.status == 200, f"HTTP {resp.status}")
        return json.loads(resp.read())["result"]


def post_stream(addr: str, route: str, body: dict) -> list[int]:
    from ray_tpu.inference import parse_stream_chunks
    host, port = addr[len("http://"):].split(":")
    data = json.dumps(body).encode()
    with socket.create_connection((host, int(port)), timeout=600) as s:
        s.sendall(f"POST /{route}/generate HTTP/1.1\r\nHost: x\r\n"
                  "Content-Type: application/json\r\n"
                  f"Content-Length: {len(data)}\r\n\r\n".encode() + data)
        buf = b""
        while b"0\r\n\r\n" not in buf:
            got = s.recv(65536)
            if not got:
                break
            buf += got
    head, _, payload = buf.partition(b"\r\n\r\n")
    check(head.split(b"\r\n")[0].split()[1] == b"200",
          f"stream status line {head[:40]!r}")
    chunks = parse_stream_chunks(payload)
    # the server signals a mid-stream failure by closing without the
    # terminal chunk: its absence fails the phase
    check(chunks and chunks[-1].get("done") is True,
          f"stream ended without its done chunk: {chunks[-2:]}")
    toks = [c["token"] for c in chunks[:-1]]
    check(chunks[-1]["n"] == len(toks), "stream token count mismatch")
    return toks


MAX_NEW = 8


def serve_inputs(args, head_len: int = 32, tail_len: int = 8):
    """(cfg, engine_cfg, prompts): five prompts of one length (one
    reference compile) — a one-shot, a streamed, and three sharing a
    ``head_len``-token head."""
    import numpy as np
    _, cfg, _, engine_cfg = model_configs(args)
    rng = np.random.default_rng(SEED + 1)
    n = head_len + tail_len
    shared = rng.integers(0, cfg.vocab_size, head_len).tolist()
    prompts = (
        [rng.integers(0, cfg.vocab_size, n).tolist() for _ in range(2)]
        + [shared + rng.integers(0, cfg.vocab_size, tail_len).tolist()
           for _ in range(3)])
    return cfg, engine_cfg, prompts


def deploy(cfg, engine_cfg, name: str, mesh=None) -> str:
    """The README quick start: serve.run(build_gpt_deployment(...));
    returns the proxy address."""
    from ray_tpu import serve
    from ray_tpu.inference import build_gpt_deployment
    serve.run(build_gpt_deployment(name=name, cfg=cfg, engine_cfg=engine_cfg,
                                   seed=SEED, mesh=mesh),
              use_actors=False, http=True)
    return serve.proxy_address()


def drive_http(addr: str, route: str, prompts):
    """one-shot, streamed, then the three shared-head requests."""
    outs = [post_json(addr, route, {"prompt": prompts[0],
                                    "max_tokens": MAX_NEW})["tokens"],
            post_stream(addr, route, {"prompt": prompts[1],
                                      "max_tokens": MAX_NEW,
                                      "stream": True})]
    outs += [post_json(addr, route, {"prompt": p,
                                     "max_tokens": MAX_NEW})["tokens"]
             for p in prompts[2:]]
    for o in outs:
        check(len(o) == MAX_NEW, f"{len(o)} tokens for max_tokens={MAX_NEW}")
    return outs


def reference_check(cfg, prompts, outs) -> dict:
    """Engine tokens vs the repo's oracle on the same params, in this
    process: exact equality with gpt.generate, and the teacher-forced
    form that is sound under bf16 near-ties (see TIE_TOL)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import gpt

    max_new = MAX_NEW
    # the replica derives its params from the seed the same way
    params = gpt.init_params(cfg, jax.random.PRNGKey(SEED))
    prompt = jnp.asarray(prompts, jnp.int32)
    s0 = prompt.shape[1]
    ref = np.asarray(jax.jit(
        lambda p, t: gpt.generate(p, cfg, t, max_new, temperature=0.0)
    )(params, prompt))[:, s0:]
    got = np.asarray(outs, np.int32)
    exact_tokens = float((ref == got).mean())
    exact_requests = float((ref == got).all(axis=1).mean())

    full = jnp.concatenate([prompt, jnp.asarray(got)], axis=1)
    logits = np.asarray(jax.jit(
        lambda p, t: gpt.forward(p, t, cfg))(params, full))
    check(np.isfinite(logits).all(), "non-finite reference logits")
    step_logits = logits[:, s0 - 1:-1, :]               # [b, max_new, V]
    chosen = np.take_along_axis(step_logits, got[..., None], -1)[..., 0]
    margin = step_logits.max(-1) - chosen
    check(float(margin.max()) <= TIE_TOL,
          f"an emitted token's reference logit is {margin.max():.4f} below "
          f"that step's maximum (tol {TIE_TOL}); exact-match rate "
          f"{exact_tokens:.3f}")
    return {"exact_token_rate_vs_generate": round(exact_tokens, 4),
            "exact_request_rate_vs_generate": round(exact_requests, 4),
            "max_teacher_forced_margin": round(float(margin.max()), 5),
            "tie_tol": TIE_TOL}


def engine_gauge(name: str) -> dict:
    from ray_tpu import inference
    for metric, _kind, _doc, series in inference.metrics_snapshot():
        if metric == name:
            return {dict(k).get("engine", "?"): v for k, v in series.items()}
    raise KeyError(name)


def phase_serve(args) -> dict:
    cfg, engine_cfg, prompts = serve_inputs(args)
    t0 = time.time()
    addr = deploy(cfg, engine_cfg, "v1")
    outs = drive_http(addr, "v1", prompts)
    serve_s = time.time() - t0
    hit = max(engine_gauge("ray_tpu_inference_prefix_hit_rate").values())
    check(hit > 0, "prefix_hit_rate is 0 after three shared-head requests")
    return {"phase": "serve", "ok": True, "requests": len(prompts),
            "prompt_len": len(prompts[0]), "max_tokens": MAX_NEW,
            "http": addr, "prefix_hit_rate": round(hit, 4),
            **reference_check(cfg, prompts, outs),
            "serve_s_incl_compile": round(serve_s, 1)}


def phase_serve_tp2(args) -> dict:
    import jax
    import numpy as np

    from ray_tpu.parallel.mesh import create_mesh

    cfg, engine_cfg, prompts = serve_inputs(args)
    deploy(cfg, engine_cfg, "v1")
    addr = deploy(cfg, engine_cfg, "tp2",
                  mesh=create_mesh({"tp": 2}, devices=jax.devices()[:2]))
    plain = drive_http(addr, "v1", prompts)
    tp2 = drive_http(addr, "tp2", prompts)
    shards = engine_gauge("ray_tpu_inference_tp_shards")
    check(sorted(shards.values()) == [1.0, 2.0],
          f"expected one unmeshed and one tp=2 engine, got {shards}")
    exact = float((np.asarray(plain) == np.asarray(tp2)).mean())
    ref = reference_check(cfg, prompts, tp2)
    return {"phase": "serve_tp2", "ok": True, "requests": len(prompts),
            "tp_shards": shards,
            "exact_token_rate_tp2_vs_unmeshed": round(exact, 4),
            **{f"tp2_{k}": v for k, v in ref.items()}}


# -------------------------------------------------------------------- main

def phase_workers() -> dict:
    """One process for each chip: the parent holds it, the workers it
    starts are CPU-pinned and must never map the TPU library."""
    import ray_tpu

    ray_tpu.init(num_cpus=2)

    @ray_tpu.remote
    def probe():
        import jax
        return {"pid": os.getpid(), "backend": jax.default_backend(),
                "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS")}

    got = ray_tpu.get(probe.remote(), timeout=300)
    check(got["pid"] != os.getpid(), "probe ran in the driver")
    check(got["backend"] == "cpu", f"worker backend {got['backend']}")
    kids = descendants()
    check(got["pid"] in kids, "worker is not a child of this process")
    offenders = [p for p in kids if maps_libtpu(p)]
    check(not offenders, f"child processes mapped libtpu: {offenders}")
    return {"phase": "workers", "ok": True, "children": len(kids),
            "worker_backend": got["backend"],
            "children_mapping_libtpu": 0,
            "parent_maps_libtpu": maps_libtpu(os.getpid())}


def shutdown_all() -> int:
    """serve, runtime, then every process this script started."""
    from ray_tpu import serve
    import ray_tpu
    serve.shutdown()
    ray_tpu.shutdown()
    deadline = time.time() + 10
    while descendants() and time.time() < deadline:
        time.sleep(0.2)
    left = descendants()
    for pid in left:
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass
    return len(left)


def main() -> int:
    global _out
    args = parse_args()
    # fd 1 -> stderr: nothing a library, a logging handler or a child
    # process prints can land on stdout; only emit() writes there
    sys.stdout.flush()
    _out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    sys.stdout = sys.stderr

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if (args.chips == 4
                and "xla_force_host_platform_device_count" not in flags):
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4").strip()

    import jax
    import jaxlib

    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    device = {"platform": platform, "kind": kind, "count": len(devs)}
    if args.rehearse:
        if platform != "cpu":
            log("--rehearse is the CPU rehearsal; refusing on " + platform)
            return 2
    elif platform != "tpu":
        log(f"no TPU: jax found {platform!r} ({kind}); "
            "pass --rehearse for the CPU dress rehearsal")
        return 2
    if len(devs) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} devices, "
            f"jax found {len(devs)}")
        return 2
    if args.chips == 1 and len(devs) != 1 and not args.rehearse:
        log(f"default run is the one-chip run; jax found {len(devs)} "
            "devices (pass --chips 4)")
        return 2

    from ray_tpu._compile_cache import (compile_cache_stats,
                                        enable_compile_cache)
    cache_dir = enable_compile_cache()
    run_dir = tempfile.mkdtemp(prefix="chip_smoke_")

    def phase_env() -> dict:
        try:
            import libtpu
            libtpu_version = getattr(libtpu, "__version__", "?")
        except ImportError:
            libtpu_version = None
        return {"phase": "env", "ok": True, "jax": jax.__version__,
                "jaxlib": jaxlib.__version__, "libtpu": libtpu_version,
                "device": device, "rehearsal": args.rehearse,
                "compile_cache_dir": cache_dir,
                "compile_cache_entries_at_start":
                    len(os.listdir(cache_dir))
                    if os.path.isdir(cache_dir) else 0,
                **build_native()}

    if args.chips == 4:
        phases = [("env", phase_env),
                  ("train_mesh", lambda: phase_train_mesh(args, run_dir)),
                  ("serve_tp2", lambda: phase_serve_tp2(args))]
    else:
        phases = [("env", phase_env),
                  ("workers", phase_workers),
                  ("train", lambda: phase_train(args, run_dir)),
                  ("serve", lambda: phase_serve(args))]
    failed = None
    try:
        for name, run in phases:
            log(f"phase {name} ...")
            t0 = time.time()
            try:
                emit({**run(), "phase_s": round(time.time() - t0, 1)})
            except Exception as e:   # reported, then the run FAILS
                traceback.print_exc(file=sys.stderr)
                emit({"phase": name, "ok": False,
                      "error": f"{type(e).__name__}: {e}"[:2000]})
                failed = name
                break
    finally:
        left = shutdown_all()
        shutil.rmtree(run_dir, ignore_errors=True)
    if left and failed is None:
        emit({"phase": "shutdown", "ok": False,
              "error": f"{left} child processes outlived shutdown"})
        failed = "shutdown"
    emit({"phase": "summary", "ok": failed is None,
          "compile_cache": compile_cache_stats(),
          "compile_cache_dir": cache_dir})
    emit({"ok": failed is None, "device": device})
    return 0 if failed is None else 1


if __name__ == "__main__":
    code = main()
    sys.stderr.flush()
    # every shutdown has run and the last line is out: leave without
    # giving interpreter teardown a chance to print or to hang
    os._exit(code)
