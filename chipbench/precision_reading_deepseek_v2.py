"""The second reading behind ``docqa8k-r80``'s two limits: the controls.

    python3 chipbench/precision_reading_deepseek_v2.py \\
        deepseek-v2-7L-e20 [seed ...]                       (on the chip)

As ``precision_reading_nemotron_h.py`` reads that cell's control, for a
configuration of the ``deepseek_v2`` layout and judged as ITS check
judges a served stream (``judge``: two quantiles of the margins).  A
seed: the cell's own weights (``make_params``), seeded sequences of four
lengths at the cell's widths, the last 256 positions each.  The float32
reference gives the maxima; each lower precision picks its own argmax
tokens, and a margin is how far below the float32 maximum a picked
token's float32 logit lies:

  * ``float8_e4m3fn``: float8 e4m3 inputs to EVERY product, the nearest
    precision below the stated one — the control, which must come out
    NOT correct;
  * ``bfloat16``: the stated precision, which must come out correct;
  * ``cache_float8``: float32 products, only what the latent pool holds
    (the normed ``c_kv``, the rotated ``k_rope``) rounded to float8 e4m3
    — the second control, read and reported: what a quantised latent
    pool would cost;
  * ``float8.forced`` / ``bfloat16.forced``: routed to the experts the
    float32 run chose, which takes away what a router's tie, flipped by
    a rounding, adds.

One JSON line a sequence, and one a seed and precision (``"sample"``):
the seed's four sequences together, a run-sized sample, through
``judge`` with the mix's limits.  Not run by ``run.py``.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

LENGTHS, LAST = (1500, 4200, 7000), 256       # and the cell's max_seq


def main() -> None:
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import deepseek_v2 as ref
    from chipbench.traffic.open_loop_http_deepseek_v2 import (judge,
                                                              make_params,
                                                              model_config)
    name = sys.argv[1]
    with open(os.path.join(ROOT, "chipbench", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           "docqa8k-r80.json")) as f:
        mix = json.load(f)
    cfg, published, held = model_config(config)
    width = config["engine"]["max_seq"]
    lengths = [n for n in LENGTHS if n < width] + [width]
    last = min(LAST, min(lengths))
    f8, bf = jnp.float8_e4m3fn, jnp.bfloat16
    lower = (("float8_e4m3fn", {"round_to": f8}, False),
             ("float8_e4m3fn.forced", {"round_to": f8}, True),
             ("bfloat16", {"round_to": bf}, False),
             ("bfloat16.forced", {"round_to": bf}, True),
             ("cache_float8", {"round_cache_to": f8}, False))
    for seed in [int(s) for s in sys.argv[2:]] or [3000042020]:
        params = make_params(cfg, config, seed)
        rng = np.random.default_rng([seed, 7])
        sample = {}
        for n in lengths:
            padded = np.zeros(width, np.int32)
            padded[:n] = rng.integers(0, cfg.vocab_size, n)
            rows = np.arange(n - last, n)
            t = time.time()
            chosen = []
            full = np.asarray(ref.logits(params, padded, published, held,
                                         rows=rows, chosen=chosen))
            rec = {"config": name, "seed": seed, "n": n,
                   "logit_std": float(full.std()),
                   "top2_gap_p50": float(np.median(
                       np.diff(np.sort(full, -1)[:, -2:], axis=-1)))}
            for label, kw, forced in lower:
                low = np.asarray(ref.logits(
                    params, padded, published, held, rows=rows,
                    forced=chosen if forced else None, **kw))
                pick = low.argmax(-1)
                margin = full.max(-1) - full[np.arange(last), pick]
                sample.setdefault(label, []).append(margin)
                rec[label] = {
                    "worst": float(margin.max()),
                    **{f"p{q}": float(np.quantile(margin, q / 100))
                       for q in (50, 90, 99)},
                    "share_not_argmax": float(
                        (pick != full.argmax(-1)).mean())}
            rec["seconds"] = time.time() - t
            print(json.dumps(rec), flush=True)
        for label, margins in sample.items():
            judged = judge(np.concatenate(margins), mix)
            print(json.dumps({
                "config": name, "seed": seed, "sample": label,
                "checks": judged, "correct": all(
                    v["value"] <= v["limit"] for v in judged.values())}),
                flush=True)
        del params


if __name__ == "__main__":
    main()
