"""The one general generator of serving traffic (no JAX).

``chat_requests(mix, seconds, seed, vocab)`` reads a traffic mix (a
data file under ``chipbench/traffic/``) and returns the requests of one
run: for each its due time, its prompt's token ids and its output
length.  The multiset of (prompt length, output length, shared head)
the inter-arrival gaps, their order and the number of requests are
fixed by the mix and ``seconds`` alone; the seed chooses the token ids.
"""

from __future__ import annotations

import math
from statistics import NormalDist


def lognormal_grid(n: int, lo: int, hi: int, median: float,
                   sigma: float) -> list:
    """n lengths: the (i + 1/2)/n quantiles of a log-normal with the
    given median and sigma, clipped to [lo, hi], rounded."""
    nd = NormalDist()
    return [int(min(hi, max(lo, round(
        math.exp(math.log(median) + sigma * nd.inv_cdf((i + 0.5) / n))))))
        for i in range(n)]


def zipf_counts(total: int, n: int, a: float) -> list:
    """``total`` split over n ranks in proportion to 1/rank^a (largest
    remainders), so that the counts do not depend on a seed."""
    w = [1.0 / (r + 1) ** a for r in range(n)]
    raw = [total * x / sum(w) for x in w]
    counts = [int(x) for x in raw]
    order = sorted(range(n), key=lambda r: raw[r] - counts[r], reverse=True)
    for r in order[:total - sum(counts)]:
        counts[r] += 1
    return counts


def length_plan(mix: dict, seconds: float) -> dict:
    """What does not depend on the seed: N, the prompt and output
    length grids, and which prompt lengths carry which shared head."""
    n = max(1, round(mix["rate_per_s"] * seconds))
    prompts = lognormal_grid(n, **mix["prompt_len"])
    outputs = lognormal_grid(n, **mix["output_len"])
    if max(prompts) + max(outputs) > mix["max_total"]:
        raise ValueError("prompt hi + output hi exceeds max_total")
    sh = mix["shared_heads"]          # {"n": 0, ...} = nothing shared
    heads = [None] * n
    if sh["n"]:
        # every other prompt long enough to hold a head and a tail of
        # its own gets one, until the share is reached
        eligible = [i for i, p in enumerate(prompts) if p >= sh["len"] + 16]
        want = min(int(n * sh["share"]), len(eligible))
        picked = (eligible[0::2] + eligible[1::2])[:want]
        ids = [h for h, c in enumerate(zipf_counts(want, sh["n"],
                                                   sh["zipf_a"]))
               for _ in range(c)]
        # spread each head over short and long prompts alike
        for j, i in enumerate(sorted(picked)):
            heads[i] = ids[(j * 7919) % want]
    return {"n": n, "prompts": prompts, "outputs": outputs, "heads": heads}


def arrival_gaps(n: int, seconds: float) -> list:
    """n inter-arrival gaps: the (i + 1/2)/n quantiles of an
    exponential distribution (a Poisson process's gaps), scaled to sum
    to ``seconds``."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    return [seconds * g / sum(raw) for g in raw]


def chat_requests(mix: dict, seconds: float, seed: int, vocab: int) -> list:
    """The requests of one run.  The sequence of (gap, prompt length,
    head, output length) is ONE fixed cycle, shuffled once by the mix's
    ``order_seed``; the run's seed chooses every token id (and, in the
    traffic kind, the weights).  So every seed offers the same sizes at
    the same times: on the chip (PR 25) a seed that also moved the
    cycle's starting point moved the tokens that fall inside the window
    by 3 % and the tail of time to first token by 10 %."""
    import numpy as np
    plan = length_plan(mix, seconds)
    n = plan["n"]
    gaps = arrival_gaps(n, seconds)
    base = np.random.default_rng([int(mix["order_seed"]), 3])
    p_at, o_at, g_at = (base.permutation(n) for _ in range(3))
    rng = np.random.default_rng([int(seed), 7])
    sh = mix["shared_heads"]
    head_toks = [rng.integers(0, vocab, sh["len"]).tolist()
                 for _ in range(sh["n"])]
    # Each entry of the cycle owns an interval as long as its gap and is
    # due at the interval's middle; the window's entries tile [0,
    # seconds).  The lead-in is the cycle continued BACKWARDS from 0 to
    # -lead_s, so that the window opens on a system in its steady state
    # and not an empty one (set-up).
    due, edge = {}, 0.0
    for slot in range(n):
        gap = gaps[int(g_at[slot])]
        due[slot] = edge + gap / 2.0
        edge += gap
    edge = 0.0
    for back in range(1, n + 1):
        gap = gaps[int(g_at[-back % n])]
        if gap / 2.0 - edge > mix.get("lead_s", 0.0):
            break
        due[-back] = edge - gap / 2.0
        edge -= gap
    reqs = []
    for slot, t in sorted(due.items()):
        k = slot % n
        i = int(p_at[k])
        p_len, head = plan["prompts"][i], plan["heads"][i]
        if head is None:
            prompt = rng.integers(0, vocab, p_len).tolist()
        else:
            prompt = head_toks[head] + rng.integers(
                0, vocab, p_len - sh["len"]).tolist()
        reqs.append({"id": len(reqs), "due_s": t, "prompt": prompt,
                     "max_tokens": plan["outputs"][int(o_at[k])],
                     "head": head, "lead": slot < 0})
    return reqs
