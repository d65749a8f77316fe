"""Serve benchmark: continuous-batched LLM decode req/s + TTFT.

Prints ONE JSON line (the Serve half of BASELINE.json's headline metric:
"Ray Serve req/s + p50 TTFT"). The reference publishes no TPU serving
numbers, so vs_baseline is throughput relative to the engine's own decode
roofline: slots * (1 / per-token step time at full batch) — i.e. how close
continuous batching gets to the hardware's sequential decode ceiling.

Two load models:
- closed-loop (capacity): N clients, zero think time — measures peak req/s;
  its "TTFT" is queue depth, NOT serving latency, and is labeled so;
- open-loop (latency): Poisson arrivals at fixed offered QPS — the honest
  TTFT distribution (arrival -> first token, queueing included) and
  completed-request goodput at sub/near/at-saturation load points.

Drives the engine DIRECTLY (in-process, the replica's own view), so proxy,
router and replica cost nothing here; ``chip_smoke.py`` goes through
``serve.run``, and one benchmark with named cells is ROADMAP Speed item 1.

There is no CPU branch: without a TPU the script prints
``{"ok": false, ...}`` and exits non-zero.
"""

from __future__ import annotations

import json
import sys
import threading
import time


def open_loop_point(engine, prompts, qps: float, max_tokens: int, seed: int):
    """One offered-load point: dispatch each request at its Poisson arrival
    time; TTFT starts at DISPATCH (the scheduled arrival), so queue wait is
    in the number."""
    import numpy as np

    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / qps, len(prompts))
    results = []
    res_lock = threading.Lock()
    threads = []
    t0 = time.perf_counter()
    arrival = 0.0
    for prompt, gap in zip(prompts, gaps):
        arrival += gap
        now = time.perf_counter() - t0
        if arrival > now:
            time.sleep(arrival - now)

        def run(p=prompt):
            try:
                r = engine.generate(p, max_tokens=max_tokens, timeout=600)
            except Exception as e:  # noqa: BLE001 - count as failed
                r = {"error": str(e)}
            with res_lock:
                results.append(r)

        th = threading.Thread(target=run, daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=600)
    wall = time.perf_counter() - t0
    ok = [r for r in results if "error" not in r]
    ttfts = sorted(r["ttft_s"] for r in ok) or [0.0]
    return {
        "offered_qps": qps,
        "offered": len(prompts),
        "completed": len(ok),
        "goodput_req_s": round(len(ok) / wall, 2),
        "p50_ttft_s": round(ttfts[len(ttfts) // 2], 4),
        "p99_ttft_s": round(ttfts[min(len(ttfts) - 1, int(len(ttfts) * 0.99))], 4),
        "tokens_per_sec": round(sum(len(r["tokens"]) for r in ok) / wall, 1),
    }


def main() -> None:
    import concurrent.futures as cf

    import numpy as np

    import jax

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import LLMEngine
    from ray_tpu.utils.device_report import device_report

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise RuntimeError(
            f"bench_serve.py measures a TPU and jax found {platform!r}")
    config = LlamaConfig.llama_1b(max_seq_len=2048, attention_impl="flash")
    # PAGED KV: per-request page commitment instead of slots*max_seq.
    # 64 slots x <=8 pages(64 rows) ~= 1.5 GB KV pool vs 2.9 GB for 32
    # dense slots: double the concurrency in half the HBM.
    num_slots, decode_chunk = 64, 32
    num_requests, max_tokens = 192, 64
    prompt_lens = [32, 64, 128, 256]
    clients = 96
    paged, page_size, total_pages = True, 64, 64 * 8 + 1

    engine = LLMEngine(
        config, num_slots=num_slots, decode_chunk=decode_chunk,
        max_seq_len=min(2048, config.max_seq_len),
        prefill_buckets=[64, 256, 512],
        paged=paged, page_size=page_size, total_pages=total_pages,
    )
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, config.vocab_size, rng.choice(prompt_lens)).tolist()
        for _ in range(num_requests)
    ]

    # warmup: compile prefill buckets + decode program
    engine.generate(prompts[0][:32], max_tokens=decode_chunk, timeout=600)

    t0 = time.perf_counter()
    with cf.ThreadPoolExecutor(max_workers=clients) as pool:
        results = list(pool.map(
            lambda p: engine.generate(p, max_tokens=max_tokens, timeout=600),
            prompts,
        ))
    wall = time.perf_counter() - t0
    # snapshot the cumulative decode counter NOW: the roofline must cover
    # the closed-loop phase only (open-loop traffic below would inflate it)
    closed_stats = engine.stats()

    ttfts = sorted(r["ttft_s"] for r in results)
    p50 = ttfts[len(ttfts) // 2]
    p99 = ttfts[min(len(ttfts) - 1, int(len(ttfts) * 0.99))]
    req_s = num_requests / wall
    tok_s = sum(len(r["tokens"]) for r in results) / wall

    # open-loop latency points: under / near / at the closed-loop capacity
    qps_points = [round(req_s * f, 2) for f in (0.4, 0.8, 1.1)]
    rng2 = np.random.default_rng(1)
    open_loop = []
    for i, qps in enumerate(qps_points):
        n = max(8, min(int(qps * 15), num_requests))
        pts = [
            rng2.integers(1, config.vocab_size, rng2.choice(prompt_lens)).tolist()
            for _ in range(n)
        ]
        open_loop.append(open_loop_point(engine, pts, qps, max_tokens, seed=i))

    # roofline: steady-state full-batch decode throughput measured in-situ
    st = closed_stats
    decode_tok_ceiling = None
    vs = None
    if st["decode_steps"]:
        # tokens the engine COULD have emitted had every slot stayed busy
        decode_tok_ceiling = st["decode_steps"] * num_slots / wall
        vs = round(tok_s / max(decode_tok_ceiling, 1e-9), 4)

    engine.stop()

    print(json.dumps({
        "ok": True,
        "metric": "serve_llm_continuous_batching",
        "value": round(req_s, 2),
        "unit": "req/s",
        "vs_baseline": vs if vs is not None else 0.0,
        # closed-loop TTFT measures queue depth at saturation, not serving
        # latency — the honest latency numbers are in open_loop below
        "closed_loop_p50_ttft_s": round(p50, 4),
        "closed_loop_p99_ttft_s": round(p99, 4),
        "open_loop": open_loop,
        "tokens_per_sec": round(tok_s, 1),
        "requests": num_requests,
        "max_tokens": max_tokens,
        "slots": num_slots,
        "paged": paged,
        "page_size": page_size if paged else None,
        "total_pages": engine.total_pages if paged else None,
        "model_params": config.num_params,
        "decode_attention": engine.decode_attention,
        "device": device_report(),
    }))


if __name__ == "__main__":
    try:
        main()
    except Exception as e:  # noqa: BLE001 - one failure line, then fail
        import traceback

        traceback.print_exc()
        print(json.dumps({
            "ok": False,
            "metric": "serve_llm_continuous_batching",
            "error": f"{type(e).__name__}: {e}"[:400],
        }))
        sys.exit(1)
