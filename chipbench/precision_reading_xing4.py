"""The second reading behind ``code4k``'s two limits: the controls.

    python3 chipbench/precision_reading_xing4.py \\
        xing4.0-29b-a4b-6L [seed ...]                       (on the chip)

As ``precision_reading_deepseek_v2.py`` reads that cell's controls, for
a configuration of the ``xing4_0`` layout and judged as ITS check judges
a served stream (``judge``: two quantiles of the margins).  A seed: the
cell's own weights (``make_params``), seeded sequences of four lengths
at the cell's widths, the last 256 positions each.  The float32
reference gives the maxima; each lower precision picks its own argmax
tokens, and a margin is how far below the float32 maximum a picked
token's float32 logit lies:

  * ``float8_e4m3fn``: float8 e4m3 inputs to EVERY product (the maps'
    product with ``Phi`` among them), the nearest precision below the
    stated one — the control, which must come out NOT correct;
  * ``bfloat16``: the stated precision, which must come out correct;
  * ``maps_bfloat16``: float32 products, only the three maps'
    arithmetic (the norm over 14,336 lanes, the product with ``Phi``,
    the exponentials, every Sinkhorn-Knopp iteration) computed in
    bfloat16 — the second control, read and reported: whether this
    check would tell a program that computed the maps in the
    activations' type;
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

LENGTHS, LAST = (1500, 4200, 9000), 256       # and the cell's max_seq


def main() -> None:
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import xing4 as ref
    from chipbench.traffic.open_loop_http_xing4 import (judge, make_params,
                                                        model_config)
    name = sys.argv[1]
    with open(os.path.join(ROOT, "chipbench", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           "code4k.json")) as f:
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
             ("maps_bfloat16", {"round_maps_to": bf}, False))
    for seed in [int(s) for s in sys.argv[2:]] or [3000061020]:
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
