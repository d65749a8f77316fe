"""The benchmark's own thin deployment: ``LLMDeployment`` takes preset names
only, so this subclass builds ``LlamaConfig`` from the configuration file and
hands ``LLMEngine`` weights made by one jitted program from the seed. It also
carries what only the chip's holder can do: take a device trace, report the
device, and run the plain reference."""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List

from ray_tpu.serve.llm import LLMDeployment, LLMEngine


class BenchLLM(LLMDeployment):
    def __init__(self, config_file: str, seed: int, rehearse: bool = False):
        import jax

        from ray_tpu.utils.compile_cache import enable_compile_cache

        from benchmarks.harness.weights import (
            llama_config_from_file, load_config_file, make_weights)

        t0 = time.time()
        enable_compile_cache()
        self.cfg = load_config_file(config_file, rehearse)
        dep = self.cfg["deployment"]
        config = llama_config_from_file(self.cfg)
        self.params = make_weights(config, seed)
        jax.block_until_ready(self.params)
        t1 = time.time()
        self.engine = LLMEngine(
            config, self.params, num_slots=dep["num_slots"],
            max_seq_len=dep["max_seq_len"], decode_chunk=dep["decode_chunk"],
            prefill_buckets=dep["prefill_buckets"], paged=True,
            page_size=dep["page_size"], total_pages=dep["total_pages"])
        self.timings = {"constructor_started": t0, "weights_s": t1 - t0,
                        "engine_s": time.time() - t1}
        self._trace_dir = None

    # ------------------------------------------------------------ reports
    def bench_report(self) -> Dict[str, Any]:
        import jax

        from ray_tpu.utils.device_report import device_report

        stats = [d.memory_stats() or {} for d in jax.devices()]
        return {**device_report(), "engine": self.engine.stats(),
                "timings": self.timings,
                "decode_attention": self.engine.decode_attention,
                "memory_peak_bytes": max(
                    (s.get("peak_bytes_in_use") or 0) for s in stats),
                "bytes_limit": max((s.get("bytes_limit") or 0) for s in stats)}

    def reseed(self, seed: int) -> bool:
        """New seeded weights in place (tools only; the engine is idle)."""
        import jax

        from benchmarks.harness.weights import make_weights

        self.params = self.engine.params = None
        self.params = make_weights(self.engine.config, seed)
        jax.block_until_ready(self.params)
        self.engine.params = self.params
        return True

    # -------------------------------------------------------------- trace
    def trace_start(self, trace_dir: str) -> bool:
        import jax

        os.makedirs(trace_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        self._trace_dir = trace_dir
        return True

    def trace_stop(self) -> bool:
        import jax

        jax.profiler.stop_trace()
        return True

    def trace_summary(self) -> Dict[str, Any]:
        from benchmarks.harness.trace_reduce import summarize_dir

        return summarize_dir(self._trace_dir)

    # ---------------------------------------------------------- reference
    def check_requests(self, samples: List[Dict[str, Any]], length: int,
                       quant=None) -> Dict[str, Any]:
        """Teacher-forced gaps of the tokens the SERVED path emitted, against
        the plain float32 reference on the seed's weights."""
        from benchmarks.harness import reference as ref

        gap_fn = ref.make_gap_fn(self.cfg, quant)
        gaps, first = [], []
        for s in samples:
            g = ref.teacher_forced_gaps(gap_fn, self.params, s["prompt"],
                                        s["tokens"], length)
            gaps += g
            first.append(g[0])
        out = ref.summarize_gaps(gaps)
        out["first_token_max_gap"] = max(first) if first else float("inf")
        return out

    def control_tokens(self, prompts: List[List[int]], steps: int, length: int,
                       quant: str) -> List[List[int]]:
        """The reference in a lower precision, put in the program's place."""
        from benchmarks.harness import reference as ref

        fn = ref.make_greedy_fn(self.cfg, quant)
        return [ref.greedy_decode(fn, self.params, p, steps, length)
                for p in prompts]
