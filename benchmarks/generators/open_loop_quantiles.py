"""Open loop, independent users. Prompt lengths, output lengths and
inter-arrival gaps are each the n QUANTILES of their distribution, so every
seed offers the same n requests, the same prompt tokens, the same output
tokens and the same set of gaps. ``order_seed`` in the traffic file permutes
the three lists independently, so every seed replays one schedule; the seed
draws the token ids."""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List

MODE = "open"


def _lognormal_quantiles(n, median, sigma, lo, hi) -> List[int]:
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        out.append(int(min(hi, max(lo, round(median * math.exp(sigma * z))))))
    return out


def _exponential_gaps(n, total) -> List[float]:
    """n quantiles of an exponential, rescaled to sum to ``total`` exactly."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = total / sum(raw)
    return [g * scale for g in raw]


def _phase(n, seconds, params, rng, vocab, offset, measured, order):
    import numpy as np

    p, o = params["prompt"], params["output"]
    prompts = _lognormal_quantiles(n, p["median"], p["sigma"], p["min"], p["max"])
    outputs = _lognormal_quantiles(n, o["median"], o["sigma"], o["min"], o["max"])
    gaps = _exponential_gaps(n, seconds)
    prompts = [prompts[i] for i in order.permutation(n)]
    outputs = [outputs[i] for i in order.permutation(n)]
    gaps = [gaps[i] for i in order.permutation(n)]
    ids = rng.integers(1, vocab, size=sum(prompts), dtype=np.int64)
    # a gap precedes its request; shifting all by half the smallest gap (the
    # same under every seed) keeps the last one due before the phase ends
    reqs, t, at = [], offset - 0.5 * min(gaps), 0
    for i in range(n):
        t += gaps[i]
        reqs.append({"due": t, "tokens": ids[at: at + prompts[i]].tolist(),
                     "max_tokens": outputs[i], "measured": measured})
        at += prompts[i]
    return reqs


def generate(params: Dict[str, Any], seed: int, seconds: float,
             vocab: int) -> Dict[str, Any]:
    import numpy as np

    rng = np.random.default_rng(int(seed))
    order = np.random.default_rng(int(params["order_seed"]))
    rate, ramp = params["rate_per_s"], params["ramp_seconds"]
    n = round(rate * seconds)
    n_ramp = round(rate * ramp)
    reqs = _phase(n_ramp, ramp, params, rng, vocab, -ramp, False, order) \
        if n_ramp else []
    reqs += _phase(n, seconds, params, rng, vocab, 0.0, True, order)
    return {"mode": MODE, "requests": reqs, "seconds": seconds,
            "drain_limit_s": params["drain_limit_s"],
            "offered": {"n": n, "prompt_tokens": sum(
                len(r["tokens"]) for r in reqs if r["measured"]),
                "output_tokens": sum(
                    r["max_tokens"] for r in reqs if r["measured"])}}
