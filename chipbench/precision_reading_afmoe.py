"""The second reading behind ``mixlen32k-r80``'s two limits: the control.

    python3 chipbench/precision_reading_afmoe.py \\
        trinity-large-preview-5L-e32 [seed ...]             (on the chip)

As ``precision_reading_nemotron_h.py`` reads the ``nemotron_h`` cell's
control, for a configuration of the ``afmoe`` layout and judged as ITS
check judges a served stream (``open_loop_http_nemotron_h.judge``: two
quantiles of the margins).  A seed: the cell's own weights
(``make_params``), seeded sequences of four lengths at the cell's widths
— inside the window, just past it, past two windows and the cell's
``max_seq`` — the last 256 positions each.  The float32 reference gives
the maxima; the same reference with float8 e4m3 inputs to every product
(the nearest precision below the stated one; saturated at 448, e4m3 has
no infinity) and with bfloat16 inputs (the stated one) picks its own
argmax tokens, and a margin is how far below the float32 maximum a
picked token's float32 logit lies.

Each lower precision is read twice: routing by its OWN scores, and
FORCED onto the experts the float32 run chose (``forced``).

One JSON line a sequence, and one a seed (``"sample"``): the seed's four
sequences together, a run-sized sample, through ``judge`` with the mix's
limits -- ``correct`` must read false for float8 and true for bfloat16.
Not run by ``run.py``.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

LENGTHS, LAST = (2000, 6000, 12000), 256       # and the cell's max_seq


def main() -> None:
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import afmoe as ref
    from chipbench.traffic.open_loop_http_afmoe import (make_params,
                                                        model_config)
    from chipbench.traffic.open_loop_http_nemotron_h import judge
    name = sys.argv[1]
    with open(os.path.join(ROOT, "chipbench", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           "mixlen32k-r80.json")) as f:
        mix = json.load(f)
    cfg, published, held = model_config(config)
    width = config["engine"]["max_seq"]
    lengths = [n for n in LENGTHS if n < width] + [width]
    last = min(LAST, min(lengths))
    lower = (("float8_e4m3fn", jnp.float8_e4m3fn),
             ("bfloat16", jnp.bfloat16))
    for seed in [int(s) for s in sys.argv[2:]] or [3000048020]:
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
                   "logit_std": float(full.std())}
            for label, dt in lower:
                for how, forced in (("", None), (".forced", chosen)):
                    low = np.asarray(ref.logits(
                        params, padded, published, held, rows=rows,
                        round_to=dt, forced=forced))
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
