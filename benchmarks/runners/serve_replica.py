"""The benchmark's own thin deployment: it asks the configuration's family
(``families/<name>.py``) for the program's configuration, for weights made by
one jitted program from the seed and for the engine of the ``deployment``
block, and serves requests from that engine as ``LLMDeployment`` does. It also
carries what only the chip's holder can do: take a device trace, report the
device, and run the family's plain reference."""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List


class BenchLLM:
    def __init__(self, config_file: str, seed: int, rehearse: bool = False):
        import jax

        from ray_tpu.utils.compile_cache import enable_compile_cache

        from benchmarks.harness.manifest import family_of
        from benchmarks.harness.weights import load_config_file

        t0 = time.time()
        enable_compile_cache()
        self.cfg = load_config_file(config_file, rehearse)
        self.family = family_of(self.cfg)
        self.config = self.family.program_config(self.cfg)
        self.params = self.family.make_weights(self.config, seed)
        jax.block_until_ready(self.params)
        t1 = time.time()
        self.engine = self.family.make_engine(
            self.config, self.params, self.cfg["deployment"])
        self.timings = {"constructor_started": t0, "weights_s": t1 - t0,
                        "engine_s": time.time() - t1}
        self._trace_dir = None

    # ----------------------------------------------------------- requests
    def __call__(self, request: Dict[str, Any]):
        """A request as ``ray_tpu.serve.llm.LLMDeployment`` takes it: with
        ``"stream"`` the engine's generator of token records and a final done
        record, else its whole answer."""
        serve = self.engine.generate_stream if request.get("stream") \
            else self.engine.generate
        return serve(tokens=request["tokens"],
                     max_tokens=int(request.get("max_tokens", 64)),
                     eos_token=request.get("eos_token"),
                     timeout=request.get("timeout"))

    def engine_stats(self) -> Dict[str, Any]:
        return self.engine.stats()

    def __del__(self):
        try:
            self.engine.stop()
        except Exception:  # noqa: BLE001 - the process is going away
            pass

    # ------------------------------------------------------------ reports
    def bench_report(self) -> Dict[str, Any]:
        import jax

        from ray_tpu.utils.device_report import device_report

        stats = [d.memory_stats() or {} for d in jax.devices()]
        engine = self.engine.stats()
        return {**device_report(), "engine": engine,
                "timings": self.timings,
                "decode_attention": engine["decode_attention"],
                "memory_peak_bytes": max(
                    (s.get("peak_bytes_in_use") or 0) for s in stats),
                "bytes_limit": max((s.get("bytes_limit") or 0) for s in stats)}

    def thread_stacks(self) -> Dict[str, str]:
        """Where every thread of this process stands, innermost frames first
        (``file:line function``): what a run whose requests never came back
        prints, so that a stall names the call it sits in."""
        import sys
        import threading
        import traceback

        names = {t.ident: t.name for t in threading.enumerate()}
        out = {}
        for ident, frame in sys._current_frames().items():
            stack = traceback.extract_stack(frame)[-5:]
            out[f"{names.get(ident, '?')}-{ident}"] = " < ".join(
                f"{os.path.basename(f.filename)}:{f.lineno} {f.name}"
                for f in reversed(stack))
        return out

    def reseed(self, seed: int) -> bool:
        """New seeded weights in place (tools only; the engine is idle)."""
        import jax

        self.params = None
        self.family.set_weights(self.engine, None)
        self.params = self.family.make_weights(self.config, seed)
        jax.block_until_ready(self.params)
        self.family.set_weights(self.engine, self.params)
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

    def trace_summary(self, keep=()) -> Dict[str, Any]:
        from benchmarks.harness.trace_reduce import summarize_dir

        return summarize_dir(self._trace_dir, keep)

    # ---------------------------------------------------------- reference
    def check_requests(self, samples: List[Dict[str, Any]], length: int,
                       quant=None) -> Dict[str, Any]:
        """Teacher-forced gaps of the tokens the SERVED path emitted, against
        the family's plain float32 reference on the seed's weights."""
        from benchmarks.harness import reference as ref

        gap_fn = self.family.make_gap_fn(self.cfg, quant)
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

        fn = self.family.make_greedy_fn(self.cfg, quant)
        return [ref.greedy_decode(fn, self.params, p, steps, length)
                for p in prompts]
