"""Closed loop, the shape of an offline pipeline: C callers each send the
next document when the last is answered. Prompt lengths cycle through the
quantiles of a uniform distribution; the seed permutes the cycle and draws
the token ids. Every seed offers the same cycle of lengths."""

from __future__ import annotations

from typing import Any, Dict

MODE = "closed"


def cycle_lengths(params) -> list:
    k, lo, hi = params["cycle"], params["prompt"]["min"], params["prompt"]["max"]
    return [int(round(lo + (hi - lo) * (i + 0.5) / k)) for i in range(k)]


def generate(params: Dict[str, Any], seed: int, seconds: float,
             vocab: int) -> Dict[str, Any]:
    import numpy as np

    rng = np.random.default_rng(int(seed))
    lengths = cycle_lengths(params)
    order = rng.permutation(len(lengths))
    base = int(seed)

    def next_request(i: int):
        n = lengths[order[i % len(order)]]
        ids = np.random.default_rng([base, i]).integers(1, vocab, size=n)
        return {"tokens": ids.tolist(), "max_tokens": params["output_tokens"]}

    return {"mode": MODE, "callers": params["callers"],
            "ramp_seconds": params["ramp_seconds"], "seconds": seconds,
            "drain_limit_s": params["drain_limit_s"],
            "next_request": next_request,
            "offered": {"cycle": len(lengths), "cycle_prompt_tokens": sum(lengths),
                        "output_tokens_each": params["output_tokens"]}}
