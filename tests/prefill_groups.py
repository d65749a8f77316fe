"""Requests handed to an ``LLMEngine`` in ONE admission, and what a group of
them must show whatever the model's family: shared by
``test_engine_tracing.py`` (Llama) and ``test_nemotron_h.py`` (hybrid)."""
import time
from concurrent.futures import Future

import numpy as np

from ray_tpu.serve.llm import GenRequest


def submit_together(engine, requests):
    """All of ``requests`` in ONE admission: the loop's ``get_nowait`` waits
    for the queue's mutex while they are appended."""
    with engine._pending.mutex:
        engine._pending.queue.extend(requests)
    return [r.future.result(timeout=300) for r in requests]


def request(tokens, max_tokens=4, waited_s=0.0):
    return GenRequest(tokens=list(tokens), max_tokens=max_tokens, eos_token=None,
                      future=Future(),
                      submitted_at=time.perf_counter() - waited_s)


def calls_by_rows(before, after):
    return {rows: n - before["prefill_calls_by_rows"][rows]
            for rows, n in after["prefill_calls_by_rows"].items()}


def check_rows_follow_the_group(engine, n, calls, prompt_len):
    """``n`` requests of one bucket in one admission run the programs
    ``calls`` ({rows: calls}); each answer is what the request gives alone;
    and no row count compiles anything once its bucket has been met (the
    caller has met it)."""
    rng = np.random.default_rng(n)
    prompts = [rng.integers(1, 200, prompt_len - i).tolist() for i in range(n)]
    before = engine.stats()
    together = submit_together(engine, [request(p, 6) for p in prompts])
    after = engine.stats()
    want = dict.fromkeys(before["prefill_calls_by_rows"], 0)
    want.update(calls)
    assert calls_by_rows(before, after) == want
    assert after["prefill_rows_real"] - before["prefill_rows_real"] == n
    assert after["prefill_rows_padded"] - before["prefill_rows_padded"] == \
        sum(rows * c for rows, c in calls.items())
    assert after["compiles"] == before["compiles"]
    for p, got in zip(prompts, together):
        alone = engine.generate(p, max_tokens=6, timeout=300)
        assert got["tokens"] == alone["tokens"] and len(got["tokens"]) == 6


GROUPS = [(1, {1: 1}), (2, {4: 1}), (3, {4: 1}), (4, {4: 1}),
          (5, {4: 1, 1: 1}), (8, {4: 2}), (9, {4: 2, 1: 1})]
