"""The second reading behind ``doc3k-r80``'s ``tie_tolerance``: the
controls.

    python3 chipbench/precision_reading_olmo_hybrid.py \\
        olmo-hybrid-7b-16L [seed ...]                       (on the chip)

As ``precision_reading_deepseek_v2.py`` reads that cell's controls, for
a configuration of the ``olmo_hybrid`` layout and judged as ITS check
judges a served stream (the MAXIMUM margin over the checked tokens).  A
seed: the cell's own weights (``make_params``), seeded sequences of four
lengths at the cell's widths, the last 256 positions each (four
sequences are a run-sized sample: the cell checks 4 requests of 64-384
emitted tokens).  The float32 reference gives the maxima; each lower
precision picks its own argmax tokens, and a margin is how far below
the float32 maximum a picked token's float32 logit lies:

  * ``float8_e4m3fn``: float8 e4m3 inputs to EVERY product, the nearest
    precision below the stated one — the control, which must come out
    NOT correct;
  * ``bfloat16``: the stated precision, which must come out correct;
  * ``state_bfloat16``: float32 products, only the matrix state rounded
    to bfloat16 after every token where the configuration states
    float32 — the second control, read and reported.

One JSON line a sequence, and one a seed and precision (``"sample"``):
the worst margin of the seed's four sequences beside the mix's limit.
Not run by ``run.py``.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

LENGTHS, LAST = (1500, 3300, 6000), 256       # and the cell's max_seq


def main() -> None:
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import olmo_hybrid as ref
    from chipbench.traffic.open_loop_http_olmo_hybrid import (make_params,
                                                              model_config)
    name = sys.argv[1]
    with open(os.path.join(ROOT, "chipbench", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           "doc3k-r80.json")) as f:
        limit = json.load(f)["tie_tolerance"]
    cfg, published = model_config(config)
    width = config["engine"]["max_seq"]
    lengths = [n for n in LENGTHS if n < width] + [width]
    last = min(LAST, min(lengths))
    lower = (("float8_e4m3fn", {"round_to": jnp.float8_e4m3fn}),
             ("bfloat16", {"round_to": jnp.bfloat16}),
             ("state_bfloat16", {"state_round_to": jnp.bfloat16}))
    for seed in [int(s) for s in sys.argv[2:]] or [3000044020]:
        params = make_params(cfg, seed)
        rng = np.random.default_rng([seed, 7])
        sample = {}
        for n in lengths:
            padded = np.zeros(width, np.int32)
            padded[:n] = rng.integers(0, cfg.vocab_size, n)
            rows = np.arange(n - last, n)
            t = time.time()
            full = np.asarray(ref.logits(params, padded, published,
                                         rows=rows))
            rec = {"config": name, "seed": seed, "n": n,
                   "logit_std": float(full.std()),
                   "top2_gap_p50": float(np.median(
                       np.diff(np.sort(full, -1)[:, -2:], axis=-1)))}
            for label, kw in lower:
                low = np.asarray(ref.logits(params, padded, published,
                                            rows=rows, **kw))
                pick = low.argmax(-1)
                margin = full.max(-1) - full[np.arange(last), pick]
                sample.setdefault(label, []).append(margin)
                rec[label] = {
                    "worst": float(margin.max()),
                    **{f"p{q}": float(np.quantile(margin, q / 100))
                       for q in (50, 90, 99)},
                    "share_not_argmax": float(
                        (pick != full.argmax(-1)).mean()),
                    "max_abs_logit_error": float(np.abs(low - full).max())}
            rec["seconds"] = time.time() - t
            print(json.dumps(rec), flush=True)
        for label, margins in sample.items():
            worst = float(np.concatenate(margins).max())
            print(json.dumps({
                "config": name, "seed": seed, "sample": label,
                "worst_margin": worst, "limit": limit,
                "correct": worst <= limit}), flush=True)
        del params


if __name__ == "__main__":
    main()
