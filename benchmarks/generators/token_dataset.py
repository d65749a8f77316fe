"""A seeded token dataset for the Data feed: ``rows`` sequences of
``seq + 1`` uniform random ids; tokens and next-token targets."""

from __future__ import annotations

from typing import Any, Dict

MODE = "dataset"


def generate(params: Dict[str, Any], seed: int, seconds: float,
             vocab: int) -> Dict[str, Any]:
    import numpy as np

    seq, rows = params["seq"], params["rows"]
    seqs = np.random.default_rng(int(seed)).integers(
        0, vocab, (rows, seq + 1), dtype=np.int32)
    return {"mode": MODE, "tokens": seqs[:, :-1], "targets": seqs[:, 1:],
            "seconds": seconds,
            "offered": {"rows": rows, "tokens_per_row": seq}}
