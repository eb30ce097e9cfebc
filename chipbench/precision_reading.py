"""The second reading behind ``tie_tolerance`` of ``traffic/chat2k-r80.json``.

    python3 chipbench/precision_reading.py [seed ...]      (on the chip)

The plain reference computed in the nearest precision BELOW the one the
configuration states (float8 e4m3 inputs to every product; bfloat16 too,
for scale), judged by the float32 reference the way a served stream is:
how far below the float32 maximum its own argmax tokens lie, over the
last 256 positions of seeded sequences of four lengths.  The serving
check's limit has to lie under the float8 reading (8-bit arithmetic must
come out not correct) and over what the served path reads in the cell's
own runs (``notes.worst_margin``).  Prints one JSON line a sequence; not
run by ``run.py``.
"""
import json, os, sys, time
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import jax, jax.numpy as jnp, numpy as np
from ray_tpu.models import hybrid
from chipbench.model import fold_seed
from chipbench.reference import hybrid_ssm_moe as ref
from chipbench.traffic.open_loop_http_recurrent import model_config

config = json.load(open(os.path.join(
    ROOT, "chipbench/configs/granite-4.0-h-small-10L-e36.json")))
cfg, published, held = model_config(config)
for seed in [int(s) for s in sys.argv[1:]] or [3000002201]:
    params = jax.jit(lambda k: hybrid.init_params(cfg, k))(
        jax.random.PRNGKey(fold_seed(seed, 0)))
    rng = np.random.default_rng([seed, 7])
    for n in (300, 700, 1500, 2304):
        seq = rng.integers(0, cfg.vocab_size, n)
        width = config["engine"]["max_seq"]
        padded = np.zeros(width, np.int32)
        padded[:n] = seq
        rows = np.arange(n - 256, n)
        t = time.time()
        full = np.asarray(ref.logits(params, padded, published, held,
                                     rows=rows))
        srt = np.sort(full, -1)
        rec = {"seed": seed, "n": n, "logit_std": float(full.std()),
               "top2_gap_median": float(np.median(srt[:, -1] - srt[:, -2]))}
        for name, dt in (("float8_e4m3fn", jnp.float8_e4m3fn),
                         ("bfloat16", jnp.bfloat16)):
            low = np.asarray(ref.logits(params, padded, published, held,
                                        rows=rows, round_to=dt))
            pick = low.argmax(-1)
            margin = full.max(-1) - full[np.arange(len(pick)), pick]
            rec[name] = {
                "worst_margin": float(margin.max()),
                "p50": float(np.median(margin)),
                "p90": float(np.quantile(margin, 0.9)),
                "share_not_argmax": float(
                    (pick != full.argmax(-1)).mean()),
                "max_abs_logit_err": float(np.abs(low - full).max())}
        rec["seconds"] = time.time() - t
        print(json.dumps(rec), flush=True)
    del params
