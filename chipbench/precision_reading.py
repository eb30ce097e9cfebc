"""The second reading behind a serving mix's ``tie_tolerance``: the control.

    python3 chipbench/precision_reading.py <config> [seed ...]   (on the chip)

``<config>`` is ``granite-4.0-h-small-10L-e36`` (mix ``chat2k-r50``),
``gpt2-xl`` (mix ``chat-r80-v2``) or ``gpt2-124m`` (the training cell:
the loss on its first batch, see ``loss_readings``).  The plain reference computed in the
nearest precision BELOW the one the configuration states (float8 e4m3
inputs to every product; bfloat16 too, for scale), judged by the float32
reference the way a served stream is: how far below the float32 maximum
its own argmax tokens lie, over the last positions (as many as the mix's
longest answer) of seeded sequences of four lengths at the cell's own
widths.  The serving check's limit has to lie under the float8 reading
(8-bit arithmetic must come out not correct) and over what the served
path reads in the cell's own runs (``notes.worst_margin``).  Prints one
JSON line a sequence; not run by ``run.py``.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

from chipbench.model import fold_seed, gpt_config, make_params  # noqa: E402

LOWER = (("float8_e4m3fn", jnp.float8_e4m3fn), ("bfloat16", jnp.bfloat16))


def family_of(config: dict):
    """(params(seed), logits(params, padded, rows, round_to), vocab,
    width, lengths, positions read) of the configuration's family."""
    if config.get("family") == "gpt2":
        from chipbench.reference import gpt2 as ref
        cfg = gpt_config(config)
        width = cfg.max_seq

        def logits(params, padded, rows, round_to):
            full = ref.forward(params, jnp.asarray(padded[None]),
                               config["n_head"], round_to)[0]
            return np.asarray(full[rows])
        return (lambda seed: make_params(cfg, fold_seed(seed, 0),
                                         config.get("weights_served_as")),
                logits, cfg.vocab_size, width, (200, 500, 900, width), 128)
    from ray_tpu.models import hybrid
    from chipbench.reference import hybrid_ssm_moe as ref
    from chipbench.traffic.open_loop_http_recurrent import model_config
    cfg, published, held = model_config(config)
    width = config["engine"]["max_seq"]

    def logits(params, padded, rows, round_to):
        return np.asarray(ref.logits(params, padded, published, held,
                                     rows=rows, round_to=round_to))
    return (lambda seed: jax.jit(lambda k: hybrid.init_params(cfg, k))(
        jax.random.PRNGKey(fold_seed(seed, 0))),
        logits, cfg.vocab_size, width, (300, 700, 1500, width), 256)


def loss_readings(name: str, config: dict, seeds: list) -> None:
    """The training check's control (a configuration with a ``trainer``):
    the reference's loss on the cell's own first batch (``b16s1024``'s
    batch x (seq + 1) ids from the seed, as ``train_steps.Feed`` draws
    them) in the lower precisions, beside its float32 loss."""
    from chipbench.reference import gpt2 as ref
    cfg = gpt_config(config)
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           "b16s1024.json")) as f:
        mix = json.load(f)
    for seed in seeds:
        params = make_params(cfg, fold_seed(seed, 0))
        tokens = np.random.default_rng([seed, 1]).integers(
            0, cfg.vocab_size, (mix["batch"], mix["seq"] + 1),
            dtype=np.int32)

        def loss(dt):
            k = mix["loss_rows_per_call"]
            return sum(k * float(ref.loss(params, jnp.asarray(
                tokens[i:i + k]), config["n_head"], dt))
                for i in range(0, mix["batch"], k)) / mix["batch"]
        full = loss(None)
        print(json.dumps({"config": name, "seed": seed, "loss": full, **{
            label + "_loss_diff": abs(loss(dt) - full)
            for label, dt in LOWER}}), flush=True)
        del params


def main() -> None:
    name = sys.argv[1]
    with open(os.path.join(ROOT, "chipbench", "configs",
                           name + ".json")) as f:
        config = json.load(f)
    seeds = [int(s) for s in sys.argv[2:]] or [3000002201]
    if "trainer" in config:
        return loss_readings(name, config, seeds)
    make, logits, vocab, width, lengths, last = family_of(config)
    for seed in seeds:
        params = make(seed)
        rng = np.random.default_rng([seed, 7])
        for n in lengths:
            padded = np.zeros(width, np.int32)
            padded[:n] = rng.integers(0, vocab, n)
            rows = np.arange(n - last, n)
            t = time.time()
            full = logits(params, padded, rows, None)
            srt = np.sort(full, -1)
            rec = {"config": name, "seed": seed, "n": n,
                   "logit_std": float(full.std()),
                   "top2_gap_median": float(np.median(srt[:, -1]
                                                      - srt[:, -2]))}
            for label, dt in LOWER:
                low = logits(params, padded, rows, dt)
                pick = low.argmax(-1)
                margin = full.max(-1) - full[np.arange(len(pick)), pick]
                rec[label] = {
                    "worst_margin": float(margin.max()),
                    "p50": float(np.median(margin)),
                    "p90": float(np.quantile(margin, 0.9)),
                    "share_not_argmax": float(
                        (pick != full.argmax(-1)).mean()),
                    "max_abs_logit_err": float(np.abs(low - full).max())}
            rec["seconds"] = time.time() - t
            print(json.dumps(rec), flush=True)
        del params


if __name__ == "__main__":
    main()
