"""The second reading behind ``agent4k-r80``'s two limits: the controls.

    python3 chipbench/precision_reading_lfm2.py \\
        lfm2-8b-a1b-12L [seed ...]                          (on the chip)

As ``precision_reading_afmoe.py`` reads the ``afmoe`` cell's control,
for a configuration of the ``lfm2_moe`` layout and judged as ITS check
judges a served stream (``open_loop_http_nemotron_h.judge``: two
quantiles of the margins).  A seed: the cell's own weights
(``make_params``), seeded sequences of four lengths at the cell's widths
— a hit's own part, a cold prompt's lower and upper end and the cell's
``max_seq`` — the last 256 positions each.  The float32 reference gives
the maxima; the same reference in a lower precision picks its own argmax
tokens, and a margin is how far below the float32 maximum a picked
token's float32 logit lies.  Three lower precisions:

  * ``float8_e4m3fn``: float8 e4m3 inputs to every product, the nearest
    precision below the stated one (saturated at 448: e4m3 has no
    infinity): the control, which the check must refuse;
  * ``bfloat16``: the stated one, which it must pass;
  * ``state_float8``: float32 products, only what a cache KEEPS of a
    convolution layer (the past inputs its taps read: the rows' state
    and every snapshot) rounded to float8 e4m3: the second control.

The first two are read twice: routing by their OWN scores, and FORCED
onto the experts the float32 run chose (``forced``).

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

LENGTHS, LAST = (600, 2200, 4000), 256         # and the cell's max_seq


def main() -> None:
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import lfm2 as ref
    from chipbench.traffic.open_loop_http_lfm2 import (make_params,
                                                       model_config)
    from chipbench.traffic.open_loop_http_nemotron_h import judge
    name = sys.argv[1]
    with open(os.path.join(ROOT, "chipbench", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           "agent4k-r80.json")) as f:
        mix = json.load(f)
    cfg, published, held = model_config(config)
    width = config["engine"]["max_seq"]
    lengths = [n for n in LENGTHS if n < width] + [width]
    last = min(LAST, min(lengths))
    f8 = jnp.float8_e4m3fn
    lower = (("float8_e4m3fn", dict(round_to=f8), True),
             ("bfloat16", dict(round_to=jnp.bfloat16), True),
             ("state_float8", dict(round_state=f8), False))
    for seed in [int(s) for s in sys.argv[2:]] or [3000052301]:
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
                   "top2_gap_median": float(np.median(
                       np.diff(np.sort(full, -1)[:, -2:], axis=-1)))}
            for label, how_low, both in lower:
                for how, forced in (("", None), (".forced", chosen))[
                        :2 if both else 1]:
                    low = np.asarray(ref.logits(
                        params, padded, published, held, rows=rows,
                        forced=forced, **how_low))
                    pick = low.argmax(-1)
                    margin = full.max(-1) - full[np.arange(last), pick]
                    sample.setdefault(label + how, []).append(margin)
                    rec[label + how] = {
                        "worst": float(margin.max()),
                        **{f"p{q}": float(np.quantile(margin, q / 100))
                           for q in (50, 90, 99)},
                        "share_not_argmax": float(
                            (pick != full.argmax(-1)).mean()),
                        "finite": bool(np.isfinite(low).all())}
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
