"""Continuous-batched LLM serving engine (the TPU-native Serve flagship).

Reference capability: the reference serves LLMs by orchestrating external
GPU engines (ray.serve.llm -> vLLM); here the engine IS the framework:

- the model's cache in HBM, one donated pytree: a page pool
  (models/paged_decode.py) and, beside it, what a family keeps per SLOT: the
  recurrent state of Mamba layers (models/nemotron_h.py), the window rings
  of sliding-attention layers (models/laguna.py: a second kind of KV storage,
  a fixed ring of pages a slot a layer that the allocator never sees), or
  both beside pages that ONE layer writes and several read
  (models/phi4flash.py: a third arrangement, in which the layers that keep
  pages are fewer than the layers that read them); or a pool of LATENT rows
  and nothing beside it (models/kimi_k2.py: a fourth arrangement, one
  compressed row a token a layer that is key and value at once, so there is
  no V pool) — one slot per in-flight request;
- CONTINUOUS batching: new requests are prefilled into free slots while
  other slots keep decoding — no batch barrier (Orca-style iteration-level
  scheduling);
- prefill is bucketed (prompt padded to the next bucket) and batched at a
  few row counts (as many rows as the group admitted needs), so each bucket
  compiles a fixed handful of programs, all when the bucket is first met.
  The padding decides a request's bucket, its group and the iteration's
  budget; what it costs on the device is the family's program's business
  (models/paged_decode.py skips the row pieces past a prompt's length and
  counts what it ran: ``prefill_rows_computed``);
  decode is one compiled multi-step program (T tokens per
  host round trip, so per-program dispatch and the host sync amortize);
- per-request metrics (TTFT, latency) in every reply, and ``stats()``: the
  counters and the flight recorder the benchmark's per-layer metrics read
  (``python3 benchmarks/run.py --workload <cell> ...``; PERF.md 3).

``LLMDeployment`` wraps the engine as a serve deployment; requests are
dicts {"tokens": [...], "max_tokens": N} -> {"tokens": [...], "ttft_s": ...}.
"""

from __future__ import annotations

import bisect
import queue
import sys
import threading
import time
from collections import deque
from concurrent.futures import Future, InvalidStateError
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from ray_tpu.profiling import host_events, joined_stall, span, stall_watch
from ray_tpu.utils.logging import get_logger

logger = get_logger("serve.llm")

PHASES = ("admit", "prefill_dispatch", "decode_dispatch", "device_get", "emit",
          "retire")
# queue-wait histogram: 5 buckets a decade from 1 ms to 63 s, one bucket
# under and one over
QUEUE_WAIT_EDGES_S = tuple(1e-3 * 10 ** (i / 5) for i in range(25))
RING_ITERS = 256
# a ring row: wall start, the six phases in seconds, slots active, requests
# admitted, requests retired
RING_COLUMNS = ("start",) + PHASES + ("active", "admitted", "retired")
# the host's turn by KIND of work, across the phases (``work_ns``), and the
# events ``work_calls`` counts; ``engine.launch``'s ``program`` attribute
WORK_KINDS = ("pack", "launch", "slot_update", "notify")
WORK_CALLS = ("launch", "launch_waited", "slot_update", "notify")
PREFILL, DECODE, BRING_UP = 0, 1, 2
SLOW_ITER_FLOOR_S = 1.0
SLOW_ITER_MEDIANS = 5.0
SLOW_LOG_EVERY_S = 10.0
# the loop's heartbeat for ``profiling.StallWatch``: a beat at each of the
# seven boundaries names the phase then entered (after the last: the row and
# the way back to the next iteration) and one an idle poll. Its limit is the
# slow-iteration threshold, recomputed from the ring's median every
# ``LIMIT_EVERY_ITERS`` iterations once the ring holds ``LIMIT_MIN_ITERS``
BEAT_PHASES = PHASES + ("between", "idle")
BEAT_IDLE = len(PHASES) + 1
LIMIT_MIN_ITERS = 8
LIMIT_EVERY_ITERS = 64
MOE_COUNTERS = ("moe_assignments", "moe_assignments_held",
                "moe_experts_touched", "moe_expert_load_max")
# every counter a family's programs may count on the device: the decode
# program returns them as its fifth result and a family's module says which,
# in order, as ``DECODE_COUNTERS`` (without it: ``MOE_COUNTERS``); a prefill
# program whose module names ``PREFILL_COUNTERS`` returns those as its third.
# ``stats()`` has them all, a name both programs count summed, 0 where the
# family counts no such thing
DEVICE_COUNTERS = MOE_COUNTERS + ("moe_blocks", "moe_blocks_extra",
                                  "attn_rows_full", "attn_rows_window",
                                  "attn_rows_shared", "scan_slots",
                                  "prefill_rows_self", "prefill_rows_cross",
                                  "attn_rows_latent", "prefill_rows",
                                  "prefill_attn_pairs",
                                  "prefill_rows_computed", "kda_rows",
                                  "kda_state_updates")
# the row counts a prefill program is compiled at (those that fit the
# slots): a group of one bucket takes the smallest that holds it. Two, not
# the four powers of two up to 8: every count is one more program to bring
# up a bucket (2.3 s each for the hybrid family, from a warm cache), a lone
# request needs 1, and 4 leaves no group more than 2 rows of padding
PREFILL_ROWS = (1, 4)
# padded prompt tokens ONE iteration may prefill (at least one request).
# Streams get their tokens once an iteration, so a burst is admitted over
# several, a decode chunk between: who decodes, and the burst's own first
# tokens, wait for about 0.2 s of prefill and not for all of it; and a closed
# loop of callers cannot fall into ONE cohort that starts and ends together
PREFILL_TOKENS_PER_ITER = 8192


def _model_of(config):
    """The module that builds ``config``'s weights, cache and programs:
    ``init_params``, ``init_cache``, ``make_paged_prefill_fn``,
    ``make_paged_decode_fn``, ``paged_kernel_fits``, ``SLOT_STATE``, and
    where it has them ``DECODE_COUNTERS``, ``PREFILL_COUNTERS`` and
    ``RING_FIELDS``.
    ``models/paged_decode.py`` for a ``LlamaConfig``; any other family's
    module holds its configuration class beside its programs, and whoever
    made ``config`` has imported it: a Llama replica imports no other
    family."""
    from ray_tpu.models.llama import LlamaConfig

    if isinstance(config, LlamaConfig):
        from ray_tpu.models import paged_decode

        return paged_decode
    return sys.modules[type(config).__module__]


def _nemotron_h_tiny():
    from ray_tpu.models.nemotron_h import NemotronHConfig

    return NemotronHConfig.tiny()


def _laguna_tiny():
    from ray_tpu.models.laguna import LagunaConfig

    return LagunaConfig.tiny()


def _phi4flash_tiny():
    from ray_tpu.models.phi4flash import Phi4FlashConfig

    return Phi4FlashConfig.tiny()


def _kimi_k2_tiny():
    from ray_tpu.models.kimi_k2 import KimiK2Config

    return KimiK2Config.tiny()


def _ling_hybrid_tiny():
    from ray_tpu.models.ling_hybrid import LingHybridConfig

    return LingHybridConfig.tiny()


def model_presets() -> Dict[str, Any]:
    """``LLMDeployment``'s preset names."""
    from ray_tpu.models.llama import LlamaConfig

    return {"tiny": LlamaConfig.tiny, "llama_1b": LlamaConfig.llama_1b,
            "llama3_8b": LlamaConfig.llama3_8b,
            "nemotron_h_tiny": _nemotron_h_tiny, "laguna_tiny": _laguna_tiny,
            "phi4flash_tiny": _phi4flash_tiny, "kimi_k2_tiny": _kimi_k2_tiny,
            "ling_hybrid_tiny": _ling_hybrid_tiny}


def _slots_updated(table, tokens, positions, active, retired, firsts, placed):
    """A round's changes of per-slot state as ONE program: the slots of
    ``retired`` ([num_slots] bool) go dead, their table rows back to the
    trash page, so that a retired slot's frozen decode writes cannot touch
    recycled pages; THEN row ``i`` of a prefill group (``placed``,
    [size, 2 + pages_per_slot]: slot, prompt length, table row) makes its
    slot live at that position with its token of ``firsts`` ([size]). A pad
    row names slot ``num_slots``, which no array has: the scatters drop it."""
    slots = placed[:, 0]
    table = table * ~retired[:, None]
    return (table.at[slots].set(placed[:, 2:], mode="drop"),
            tokens.at[slots].set(firsts, mode="drop"),
            positions.at[slots].set(placed[:, 1], mode="drop"),
            (active & ~retired).at[slots].set(True, mode="drop"))


@dataclass
class GenRequest:
    tokens: List[int]
    max_tokens: int
    eos_token: Optional[int]
    future: Future
    submitted_at: float = field(default_factory=time.perf_counter)
    ttft_s: Optional[float] = None
    out_tokens: List[int] = field(default_factory=list)
    slot: int = -1
    # streaming: tokens pushed here as decoded (None sentinel = done)
    stream_q: Optional["queue.Queue"] = None
    streamed: int = 0
    cancelled: bool = False
    # wall instants (one host) of the first token's and the done sentinel's
    # push, for the done record's hops
    first_pushed_at: float = 0.0
    done_pushed_at: float = 0.0


class LLMEngine:
    """Continuous-batching loop around a model's prefill and decode programs:
    models/paged_decode.py (Llama family, paged KV cache),
    models/nemotron_h.py (hybrid family: pages and per-slot recurrent
    state), models/laguna.py (window and full attention layers: pages for
    the full layers, per-slot rings for the window layers),
    models/phi4flash.py (pages that one layer writes and eight read, rings
    for the window layers, per-slot scan state) or models/kimi_k2.py (pages
    of latent rows, one pool and no V pool; prefill attends unabsorbed,
    decode absorbed). One loop, one admission, one allocator, one set of
    counters for all five.

    HBM is committed per REQUEST (ceil((prompt+max_tokens)/page_size) pages
    from a shared pool), not per-slot*max_seq — so ``num_slots`` is bounded
    by real demand, and short requests do not pay for max_seq rows. Decode
    attention is decided here, once: the repo's Pallas kernel
    (``ops/paged_attention.py``, which does work only for active slots) on
    a TPU backend when head_dim tiles the lane register file (128), else the
    gather reference. ``decode_attention`` names the choice.

    A step that raises (a kernel the chip's compiler refuses, device OOM)
    fails every in-flight and queued request with that exception and stops
    the engine: the donated cache is gone with the failed program, and a
    caller should read the compiler's message, not a timeout.

    ``stats()``, field by field. Counters are cumulative and monotone (take
    differences), written by the loop thread and read without a lock, so one
    snapshot can be torn by at most the iteration in progress; no container
    in it grows with requests or time.

    - ``slots``, ``active``, ``queued``: slots there are, slots in use,
      requests waiting (FIFO + page-pool backlog) now.
    - ``decode_steps``, ``tokens_generated``, ``uptime_s``,
      ``decode_attention``: decode ticks dispatched, tokens of retired
      requests, seconds since construction, the decode attention chosen.
    - ``decode_rows_run``, ``decode_rows_live``: slot-rows the decode chunks
      ran (slots x ticks, a chunk) and those of them whose slot was active:
      their ratio is the share of the batch that decode attention works
      for; the rest it skips. Counted on the host, a chunk.
    - ``iters``, ``iter_ns``: busy iterations of ``_step`` and their time.
      ``phase_ns``: the same time split into the six phases that partition
      an iteration: ``admit`` (pull requests, pages, slots),
      ``prefill_dispatch`` (``_prefill_group``, host side; for a bucket
      met for the first time also ``_bring_up``),
      ``decode_dispatch`` (key split + the decode call), ``device_get`` (the
      one host sync a chunk), ``emit`` (tokens to requests and streams),
      ``retire``. ``idle_ns``: iterations with no slot in use (a 10 ms
      sleep each), in neither ``iters`` nor the ring. ``between_ns``: from
      the end of one busy iteration to the start of the next one that
      follows it with no idle poll between: the flight recorder's row and,
      mostly, the loop thread waiting to run again; in no phase and not in
      ``iter_ns``, so the loop's wall time is ``iter_ns`` + ``between_ns`` +
      ``idle_ns``.
    - ``phase_cpu_ns``, ``loop_cpu_ns``, ``process_cpu_ns``: the loop
      thread's CPU time in the same six phases (``time.thread_time_ns`` at
      the boundaries ``phase_ns`` stamps; they sum to ``loop_cpu_ns``) and
      the whole process's over the same iterations. Wall minus CPU in a
      phase is time the loop thread did not run: in ``device_get`` the wait
      for the chip, in ``admit``, ``emit`` and ``retire``, which wait for no
      device, the wait for the interpreter.
    - ``work_ns``: the iteration by KIND of host work, across the phases:
      ``pack`` (a prefill group's numpy arguments, the rows its slots go
      live by among them, and the program's copies to the chip),
      ``launch`` (the calls into the compiled programs, call to
      return: the prefill program with its ``argmax``, the decode program,
      a bucket's bring-up), ``slot_update`` (every device operation on
      per-slot state between two launches, all issued by ``_slot_update``:
      ONE program a prefill group, which makes its slots live and the slots
      retired since the last one dead, one more in an iteration that retired
      a slot and admits nobody, and a chunk's key split),
      ``notify`` (a stream's queue puts, the end sentinel, the future's
      result). They never overlap; what is left of ``iter_ns`` after them
      and ``phase_ns.device_get`` is Python bookkeeping. ``work_calls``:
      ``launch``, ``slot_update`` (operations: at most prefill groups + 2
      an iteration, whatever it admits or retires), ``notify``
      (hand-overs: a push of a stream's new tokens, or a request's end), and
      ``launch_waited``: launches whose result was ready the moment the call
      returned, which is the loop thread having waited the program out.
    - ``starved_ns``: how long the chip had nothing queued while the loop was
      busy, as far as the loop can know. It sees the chip empty when
      ``device_get`` returns, when a launch returns with its result ready,
      and when an operation behind a launch returns and finds that
      launch's result ready (the operation is then where the loop waited
      the program out); from there to its next launch no program is queued
      (slot updates in between are microseconds of device work and end no
      stretch; an idle poll is ``idle_ns`` and not in it). A LOWER bound of
      the device's idle time: it cannot see the time between the chip
      finishing and ``device_get`` (or the operation that waited) returning;
      of the fetch ``phase_cpu_ns.device_get`` says how much was work.
      The thread and process CPU clocks tick as the kernel accounts them
      (every 10 ms on some machines): read their sums over many iterations.
    - ``admitted``, ``retired``: requests given a slot, requests answered.
    - ``prefill_calls``, ``prefill_rows_real``, ``prefill_rows_padded``,
      ``prefill_tokens_real``, ``prefill_tokens_padded``: prefill programs
      dispatched, their rows holding a request and rows in all, prompt
      tokens and rows x bucket: what padding a group to the next compiled
      row count (``PREFILL_ROWS``) and a prompt to its bucket ADMITS (the
      budget of an iteration, the pages of a call). What of it the device
      computes is ``prefill_rows_computed``: the rows the Llama-shaped
      family's prefill programs ran their row-wise work over (live pieces x
      piece rows, models/paged_decode.py ``PREFILL_PIECE``; a pad row costs
      one piece; the bring-up calls' pad rows count too), counted in the
      program and fetched with the next chunk's ``device_get``; 0 for the
      other families.
      ``prefill_calls_by_rows``: the same calls by the row count each ran
      at, one key a compiled row count. The all-pad calls that bring a
      bucket's programs up prefill no request and enter none of these.
    - ``queue_wait_hist``: ``edges_s`` and ``counts`` (one more than
      edges: under the first edge, between edges, over the last) of
      admission instant minus submission, one count an admitted request.
    - ``compiles``, ``compile_s``, ``gc_pause_ns``, ``gc_pauses_over_50ms``,
      ``gc_longest_s``: the process's ``profiling.host_events()``; so are
      ``stream_items`` (items the process's streaming calls yielded: a
      replica's tokens) and ``stream_items_inline`` (those that went to the
      caller in a reply of the call's own connection, past the agent and
      the GCS).
    - ``ring``: ``columns`` and ``rows`` of the last 256 busy iterations
      (wall start, phases in seconds, slots active, admitted, retired),
      oldest first. ``longest_iter_s``: the longest ever.
    - ``slow_iters``: the last 16 iterations longer than max(1 s, 5 x the
      ring's median), each with its wall instant, total, longest phase,
      compile and GC deltas over it, the CPU time the process (``cpu_s``)
      and the loop thread (``loop_cpu_s``) used in it (the readings the
      CPU counters above add up; near zero: all of it waited, on the device
      or the kernel; near the total: a thread ran)
      and ``steal_s`` (seconds, summed over CPUs, the hypervisor gave to
      others OVER the iteration: since the stall watch's last reading before
      it began, so at most a second more), ``queued`` and ``active``; each is
      also one warning line in the log (at most one every 10 s;
      ``slow_iters_unlogged`` counts the rest). ``stall``, where the
      process's ``profiling.StallWatch`` sampled the wait inside the
      iteration: its whole record (what the threads, the faults, the I/O,
      the throttling and its own lateness did WHILE the wait lasted, and the
      ``class`` its fixed rules give), joined by time when ``stats()`` is
      read.
    - ``in_flight``: ``{loop, phase, for_s}`` while the loop's last beat (one
      at each phase boundary, one an idle poll) is older than its limit,
      max(1 s, 5 x the ring's median) once the ring holds 8 iterations; else
      None. An iteration that never ends is in no other counter: the rest
      of ``stats()`` is then what the last finished iteration left.
    - ``kv_bytes_per_token``: bytes of K and V a cached token takes over
      all the layers that KEEP (write) pages: what a token costs the pool.
      A family whose cache has no V pool (models/kimi_k2.py: one latent row
      is key and value) counts its one pool once: the stored row's width,
      lane padding included, times its layers.
      Layers that only READ another layer's pages (models/phi4flash.py: one
      layer keeps, eight read) are not in it; what a decode tick reads of
      the pool is ``attn_rows_shared`` rows of that width.
      ``state_slots``, ``state_bytes``:
      slots that keep state beside their pages (recurrent state, window
      rings), and the bytes of it (all slots and the trash row); 0 for a
      model that keeps none. ``window_ring_pages``, ``window_state_bytes``:
      the pages held in window rings (over sliding layers, slots and the
      trash ring) and their bytes, a part of ``state_bytes``; 0 for a model
      without a window. ``kv_pages_in_use``, ``kv_pages_total``: pages the
      allocator has handed out now, and those it may (the pool without its
      trash page); rings are in neither.
    - ``moe_assignments``, ``moe_assignments_held``, ``moe_experts_touched``,
      ``moe_expert_load_max``: over decode ticks and expert layers, summed:
      the routed choices of active slots, those that fell on experts held
      here, held experts with at least one token, and the fullest held
      expert's tokens. Counted on the device and fetched with the chunk's
      one ``device_get``; 0 for a model without routed experts.
    - ``attn_rows_full``, ``attn_rows_window``: K/V rows decode attention
      attended over, summed over live slots, ticks and layers of each kind:
      ``length`` a full layer, ``min(length, window)`` a sliding one.
      Counted in the decode program and fetched with the expert counters; 0
      for a family whose module names no such counter (``DECODE_COUNTERS``).
    - ``attn_rows_shared``, ``scan_slots``: K/V rows attended in pages that
      several layers read, summed over live slots, ticks and the READING
      layers (``length`` x readers; a family whose every reading layer keeps
      its own pages counts ``attn_rows_full`` instead), and the per-slot
      scan states a selective scan moved (active slots x scan layers x
      ticks). ``prefill_rows_self``, ``prefill_rows_cross``: prompt tokens
      that ran the layers every row runs, and rows that ran the layers only
      a prompt's last row needs (models/phi4flash.py: one a prompt); counted
      in the prefill program. 0 for the other families.
    - ``attn_rows_latent``: latent rows decode attention attended over,
      summed over live slots, ticks and layers (models/kimi_k2.py; each is
      read once, for its scores and its values). ``prefill_rows``,
      ``prefill_attn_pairs``: the prompt tokens that family's prefill
      programs ran, and the causal (query, key) pairs ONE layer attended over
      for them, the sum of n (n + 1) / 2 (every layer attends the same
      pairs; a pad row counts one of each). 0 for the other families.
    - ``kda_rows``, ``kda_state_updates``: prompt rows the chunked delta-rule
      kernel took (prompt tokens x KDA layers, counted in the prefill
      program; a pad row counts one a layer) and delta-rule states its
      one-token update moved (live slots x KDA layers x ticks, counted in
      the decode program); models/ling_hybrid.py, 0 for the other families.
    - ``moe_blocks``, ``moe_blocks_extra``: calls of the compacted expert
      product (``ops/moe.py``: a layer of a decode tick, a layer and chunk of
      a prefill call) and the blocks they ran beyond their first, which is 0
      unless this share held more of a call's choices than the block takes.
      Counted in the decode AND the prefill program (``PREFILL_COUNTERS``);
      a prefill's ride to the host with the next chunk's ``device_get``.

    Which model: ``_model_of`` maps the configuration's type to the module
    that builds its weights, its cache and its two programs
    (``models/paged_decode.py`` for ``LlamaConfig``,
    ``models/nemotron_h.py`` for ``NemotronHConfig``, ``models/laguna.py``
    for ``LagunaConfig``, ``models/phi4flash.py`` for ``Phi4FlashConfig``,
    ``models/kimi_k2.py`` for ``KimiK2Config``, ``models/ling_hybrid.py``
    for ``LingHybridConfig``).
    The cache is one donated pytree. Where the module
    says ``SLOT_STATE``, the cache also holds state addressed by slot
    (recurrent state, window rings): prefill is told each row's slot (a pad
    row: the trash row ``num_slots``) and overwrites it, so a retired slot
    needs no clearing; decode moves the state of active slots only. A prompt
    longer than ``PREFILL_TOKENS_PER_ITER`` is admitted alone, one to an
    iteration, by a one-row program of its bucket.

    Spans (``profiling.span``, on the device trace's clock): ``engine.admit``,
    ``engine.prefill_bring_up`` (``bucket``), ``engine.prefill_dispatch``
    (``bucket``, ``rows_real``, ``rows_padded``, ``state_rows``),
    ``engine.decode_dispatch``, ``engine.device_get``, ``engine.emit``,
    ``engine.retire``; nested in them ``engine.launch`` (``program``: 0
    prefill, 1 decode, 2 bring-up) and ``engine.slot_update``, one an
    operation ``_slot_update`` issues; ``engine.idle`` around the idle
    poll's sleep."""

    def __init__(self, config, params=None, *, num_slots: int = 8,
                 max_seq_len: Optional[int] = None, decode_chunk: int = 8,
                 temperature: float = 0.0, prefill_buckets: Optional[List[int]] = None,
                 paged: bool = True, page_size: int = 64,
                 total_pages: Optional[int] = None):
        if paged is not True:
            # both benchmarks/families/*.py still pass paged=True: the
            # argument goes when they stop (ROADMAP Design 1 (i))
            raise ValueError(
                "paged must be True: the dense slot cache was removed and "
                "the page pool is the engine's only cache; the argument goes "
                "once the benchmark stops passing it")
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.paged_decode import PageAllocator
        from ray_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
        model = _model_of(config)
        self.config = config
        self.num_slots = num_slots
        self.max_seq = max_seq_len or config.max_seq_len
        self.decode_chunk = decode_chunk
        self.params = params if params is not None else model.init_params(
            config, jax.random.key(0)
        )
        self._slot_state = model.SLOT_STATE
        self._ring_fields = getattr(model, "RING_FIELDS", ())
        self.page_size = page_size
        self.pages_per_slot = -(-self.max_seq // page_size)
        # default pool: every slot can hold max_seq rows (+1 trash page), so
        # no request ever waits for pages. A smaller pool (or more slots at
        # the same pool) is the caller's choice: HBM then tracks real demand
        # instead of slots * max_seq
        self.total_pages = total_pages or (
            1 + num_slots * self.pages_per_slot)
        self.allocator = PageAllocator(self.total_pages)
        self.cache = model.init_cache(config, num_slots, self.total_pages,
                                      page_size)
        self._table = jnp.zeros((num_slots, self.pages_per_slot), jnp.int32)
        self._slot_pages: List[Optional[List[int]]] = [None] * num_slots
        self._prefill = model.make_paged_prefill_fn(config, page_size)
        use_kernel = (jax.default_backend() == "tpu"
                      and model.paged_kernel_fits(config))
        self.decode_attention = "pallas_paged" if use_kernel else "gather"
        self._decode = model.make_paged_decode_fn(
            config, decode_chunk, page_size, temperature,
            use_kernel=use_kernel)
        # buckets are page multiples so prompt K/V scatter is a clean
        # reshape-scatter
        self.prefill_buckets = sorted({
            -(-min(b, self.max_seq) // page_size) * page_size
            for b in (prefill_buckets or [128, 512, 2048])
        })
        self._key = jax.random.key(0)
        # device-side batch state
        self._tokens = jnp.zeros((num_slots,), jnp.int32)
        self._positions = jnp.zeros((num_slots,), jnp.int32)
        self._active = jnp.zeros((num_slots,), bool)
        # the ONE program that changes it (and ``_table``) between launches.
        # It holds no layer and touches no cache: the same for every family,
        # and new only to the first bucket that meets a row count (``_bring_up``)
        self._update = jax.jit(_slots_updated)
        # slots retired on the host that the device still holds live, until
        # the next ``_update`` (before any decode launch: ``_step``)
        self._retiring = np.zeros((num_slots,), bool)
        # host-side state
        self._slots: List[Optional[GenRequest]] = [None] * num_slots
        self._pending: "queue.Queue[GenRequest]" = queue.Queue()
        # head-of-line holding area for requests the page pool couldn't fit
        self._admit_backlog: "deque[GenRequest]" = deque()
        self._shutdown = False
        self._failed: Optional[BaseException] = None
        self._jnp = jnp
        self._jax = jax
        self._steps = 0
        self._decode_rows_live = 0
        self._tokens_out = 0
        self._started = time.perf_counter()
        # always-on counters and the flight recorder (see the class docstring)
        self._host_events = host_events()
        self._heart = stall_watch().heartbeat("llm-engine", BEAT_PHASES)
        self._iters = 0
        self._iter_ns = 0
        self._idle_ns = 0
        self._phase_ns = [0] * len(PHASES)
        self._phase_cpu_ns = [0] * len(PHASES)
        self._loop_cpu_ns = 0
        self._process_cpu_ns = 0
        self._work_ns = dict.fromkeys(WORK_KINDS, 0)
        self._work_calls = dict.fromkeys(WORK_CALLS, 0)
        self._starved_ns = 0
        # the instant (perf_counter_ns) since which the loop knows the chip
        # has nothing queued; 0 while a program it launched may be
        # unfinished, and then ``_in_flight`` is that launch's result
        self._empty_since = time.perf_counter_ns()
        self._in_flight = None
        self._between_ns = 0
        self._ended = 0  # the last iteration's end, if it was a busy one
        self._admitted = 0
        self._retired = 0
        self._prefill_calls = 0
        self._prefill_rows_real = 0
        self._prefill_rows_padded = 0
        self._prefill_tokens_real = 0
        self._prefill_tokens_padded = 0
        self._prefill_rows = tuple(r for r in PREFILL_ROWS if r <= num_slots)
        self._prefill_calls_by_rows = dict.fromkeys(self._prefill_rows, 0)
        self._buckets_up: set = set()
        self._counter_names = getattr(model, "DECODE_COUNTERS", MOE_COUNTERS)
        self._device_counts = np.zeros((len(self._counter_names),), np.int64)
        self._prefill_counter_names = getattr(model, "PREFILL_COUNTERS", ())
        self._prefill_counts = np.zeros(
            (len(self._prefill_counter_names),), np.int64)
        self._prefill_counts_pending: list = []  # on the device, since the last get
        self._cache_stats = self._describe_cache()
        self._queue_wait_counts = [0] * (len(QUEUE_WAIT_EDGES_S) + 1)
        self._ring = np.zeros((RING_ITERS, len(RING_COLUMNS)))
        self._longest_iter_ns = 0
        # (record, the iteration's first and last boundary): the watch's
        # record of the wait inside it is joined when ``stats()`` is read
        self._slow_iters: "deque[tuple]" = deque(maxlen=16)
        self._slow_logged_at = -SLOW_LOG_EVERY_S
        self._slow_unlogged = 0
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="llm-engine")
        self._thread.start()
        logger.info("llm engine on %s: decode attention %s, %d slots, chunk %d",
                    jax.default_backend(), self.decode_attention, num_slots,
                    decode_chunk)

    # ----------------------------------------------------------------- API
    def _submit(self, req: GenRequest) -> None:
        if self._failed is not None:
            raise self._failed
        self._pending.put(req)
        if self._failed is not None:
            # the loop died between the check and the put: nobody will ever
            # pop this request, so fail it here (idempotent with _fail_all)
            self._fail_request(req, self._failed)

    def generate(self, tokens: List[int], max_tokens: int = 64,
                 eos_token: Optional[int] = None,
                 timeout: Optional[float] = None) -> Dict[str, Any]:
        """Blocking generate (replica-thread entry). Returns
        {"tokens", "ttft_s", "latency_s"}."""
        if len(tokens) + max_tokens > self.max_seq:
            raise ValueError(
                f"prompt {len(tokens)} + max_tokens {max_tokens} exceeds "
                f"max_seq_len {self.max_seq}"
            )
        req = GenRequest(tokens=list(tokens), max_tokens=max_tokens,
                         eos_token=eos_token, future=Future())
        self._submit(req)
        return req.future.result(timeout=timeout)

    def generate_stream(self, tokens: List[int], max_tokens: int = 64,
                        eos_token: Optional[int] = None,
                        timeout: Optional[float] = None):
        """Streaming generate: yields {"token": t} the moment each token is
        decoded, then a final {"done": True, "ttft_s", "latency_s",
        "num_tokens", "hops"} record. Abandoning the generator cancels the
        request (its slot retires at the next decode step).

        ``hops``: wall instants (``time.time()``, one host) of this request,
        stamped once a request and for its first token and the done
        sentinel only: ``engine_enter`` (here), ``first_push`` / ``done_push``
        (the loop thread), ``first_pickup`` / ``done_pickup`` (this
        generator), with what ``serve.replica.current_request_hops()`` holds
        of the way in; the proxy adds ``first_recv``, ``done_recv``,
        ``first_write``, ``done_write``."""
        from ray_tpu.serve.replica import current_request_hops

        hops = dict(current_request_hops() or {}, engine_enter=time.time())
        if len(tokens) + max_tokens > self.max_seq:
            raise ValueError(
                f"prompt {len(tokens)} + max_tokens {max_tokens} exceeds "
                f"max_seq_len {self.max_seq}"
            )
        req = GenRequest(tokens=list(tokens), max_tokens=max_tokens,
                         eos_token=eos_token, future=Future())
        req.stream_q = queue.Queue()
        self._submit(req)
        try:
            tok = req.stream_q.get(timeout=timeout)
            hops["first_pickup"] = time.time()
            while tok is not None:
                yield {"token": tok}
                tok = req.stream_q.get(timeout=timeout)
            hops["done_pickup"] = time.time()
            result = req.future.result(timeout=5.0)
            hops["first_push"] = req.first_pushed_at
            hops["done_push"] = req.done_pushed_at
            yield {"done": True, "ttft_s": result["ttft_s"],
                   "latency_s": result["latency_s"],
                   "num_tokens": len(result["tokens"]), "hops": hops}
        finally:
            req.cancelled = True  # no-op if already finished

    def stats(self) -> Dict[str, Any]:
        """Counters and the flight recorder; the class docstring has every
        field. No lock is taken and nothing here grows."""
        host = self._host_events
        n = self._iters
        ring = self._ring if n >= RING_ITERS else self._ring[:n]
        return {
            "slots": self.num_slots,
            "active": sum(r is not None for r in self._slots),
            "queued": self._queued(),
            "decode_steps": self._steps,
            "decode_attention": self.decode_attention,
            "decode_rows_run": self._steps * self.num_slots,
            "decode_rows_live": self._decode_rows_live,
            "tokens_generated": self._tokens_out,
            "uptime_s": time.perf_counter() - self._started,
            "iters": n,
            "iter_ns": self._iter_ns,
            "idle_ns": self._idle_ns,
            "phase_ns": dict(zip(PHASES, self._phase_ns)),
            "phase_cpu_ns": dict(zip(PHASES, self._phase_cpu_ns)),
            "loop_cpu_ns": self._loop_cpu_ns,
            "process_cpu_ns": self._process_cpu_ns,
            "work_ns": dict(self._work_ns),
            "work_calls": dict(self._work_calls),
            "starved_ns": self._starved_ns,
            "between_ns": self._between_ns,
            "admitted": self._admitted,
            "retired": self._retired,
            "prefill_calls": self._prefill_calls,
            "prefill_rows_real": self._prefill_rows_real,
            "prefill_rows_padded": self._prefill_rows_padded,
            "prefill_tokens_real": self._prefill_tokens_real,
            "prefill_tokens_padded": self._prefill_tokens_padded,
            "prefill_calls_by_rows": dict(self._prefill_calls_by_rows),
            "queue_wait_hist": {"edges_s": list(QUEUE_WAIT_EDGES_S),
                                "counts": list(self._queue_wait_counts)},
            "compiles": host.compiles,
            "compile_s": host.compile_s,
            "gc_pause_ns": host.gc_pause_ns,
            "gc_pauses_over_50ms": host.gc_pauses_over_50ms,
            "gc_longest_s": host.gc_longest_ns / 1e9,
            "stream_items": host.stream_items,
            "stream_items_inline": host.stream_items_inline,
            "ring": {"columns": list(RING_COLUMNS),
                     "rows": np.roll(ring, -(n % len(ring)), axis=0).tolist()
                     if n else []},
            "longest_iter_s": self._longest_iter_ns / 1e9,
            "slow_iters": [joined_stall(self._heart.name, *slow)
                           for slow in self._slow_iters],
            "slow_iters_unlogged": self._slow_unlogged,
            "in_flight": self._heart.in_flight(),
            **self._cache_stats,
            "kv_pages_in_use": self.total_pages - 1 - self.allocator.free_pages,
            "kv_pages_total": self.total_pages - 1,
            **self._device_counter_stats(),
        }

    def _device_counter_stats(self) -> Dict[str, int]:
        counts = dict.fromkeys(DEVICE_COUNTERS, 0)
        for names, values in ((self._counter_names, self._device_counts),
                              (self._prefill_counter_names,
                               self._prefill_counts)):
            for name, value in zip(names, values.tolist()):
                counts[name] += value
        return counts

    def _describe_cache(self) -> Dict[str, int]:
        """What the cache's shapes say, read once: the loop thread donates
        the cache itself every step. ``k`` and ``v`` are the page pools (a
        latent cache has ``k`` alone); every other field is state addressed
        by slot, and those the family's module lists as ``RING_FIELDS`` are
        pools of window rings."""
        fields = self.cache._asdict()
        kv = sum(pool.shape[0] * (pool.shape[1] // self.total_pages)
                 * pool.shape[3] * pool.dtype.itemsize
                 for pool in (fields.get("k"), fields.get("v"))
                 if pool is not None)
        state = sum(x.nbytes for name, x in fields.items()
                    if name not in ("k", "v"))
        rings = [fields[name] for name in self._ring_fields]
        return {"kv_bytes_per_token": kv, "state_bytes": state,
                "state_slots": self.num_slots if self._slot_state else 0,
                "window_ring_pages": rings[0].shape[1] if rings else 0,
                "window_state_bytes": sum(x.nbytes for x in rings)}

    def _queued(self) -> int:
        return self._pending.qsize() + len(self._admit_backlog)

    def decode_program_text(self) -> str:
        """The decode program lowered (not compiled) at the engine's shapes.
        ``tpu_custom_call`` in it is the Pallas kernel; its absence is the
        gather path. For checks that must not trust ``decode_attention``."""
        jax = self._jax
        args = (self.params, self.cache, self._tokens, self._positions,
                self._active, self._table, self._key)
        # shapes only: the loop thread donates the live cache every step
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
        return self._decode.lower(*shapes).as_text()

    def stop(self) -> None:
        self._shutdown = True
        # join: a daemon thread still inside a jax dispatch at interpreter
        # shutdown aborts the process (pthread "exception not rethrown")
        self._thread.join(timeout=10)

    # ---------------------------------------------------------------- loop
    def _bucket_for(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        # longer than the largest configured bucket: round up to a 128
        # multiple (one extra compile) rather than silently truncating the
        # prompt — max_seq admission already guaranteed it fits
        bucket = min(self.max_seq, -(-n // 128) * 128)
        bucket = -(-bucket // self.page_size) * self.page_size
        return min(bucket, self.pages_per_slot * self.page_size)

    def _count_admitted(self, req: GenRequest, now: float) -> None:
        self._admitted += 1
        self._queue_wait_counts[bisect.bisect_right(
            QUEUE_WAIT_EDGES_S, now - req.submitted_at)] += 1

    def _count_prefill(self, rows_real: int, rows_padded: int,
                       tokens_real: int, bucket: int) -> None:
        self._prefill_calls += 1
        self._prefill_calls_by_rows[rows_padded] += 1
        self._prefill_rows_real += rows_real
        self._prefill_rows_padded += rows_padded
        self._prefill_tokens_real += tokens_real
        self._prefill_tokens_padded += rows_padded * bucket

    def _admit(self) -> List[tuple]:
        """Pull the admissible requests, in order, up to one iteration's
        budget of padded prompt tokens (``PREFILL_TOKENS_PER_ITER``; what is
        over it heads the next iteration's line), and group them by prefill
        bucket: ONE batched prefill program per group, as (chunk, bucket,
        size) for ``_prefill_group``. ``size``, the program's rows, follows
        what was admitted: the smallest of the bucket's row counts
        (``_rows_of``) that holds the group; a group over the largest is cut
        into programs of the largest first. So a bucket has one compiled
        program a row count, all brought up when the bucket is first met
        (``_bring_up``). What the padding that is left costs is counted
        where the program is dispatched (``prefill_rows_*`` /
        ``prefill_tokens_*`` / ``prefill_calls_by_rows`` of ``stats()``; the
        benchmark's ``prefill_padding_share``). Admission makes no host sync:
        a request's first sampled token stays on the device and is fetched
        together with the next decode chunk."""
        now = time.perf_counter()
        free_slots = [i for i, r in enumerate(self._slots) if r is None]
        admitted: List[tuple] = []  # (req, slot, pages, bucket)
        budget = PREFILL_TOKENS_PER_ITER
        while free_slots:
            if self._admit_backlog:
                req = self._admit_backlog.popleft()
            else:
                try:
                    req = self._pending.get_nowait()
                except queue.Empty:
                    break
            n = len(req.tokens)
            bucket = self._bucket_for(n)
            if admitted and bucket > budget:
                # the next iteration's first (the head of the line)
                self._admit_backlog.appendleft(req)
                break
            need = max(bucket // self.page_size,
                       -(-(n + req.max_tokens) // self.page_size))
            if need > self.allocator.total - 1:
                self._fail_request(req, ValueError(
                    f"request needs {need} KV pages but the pool has "
                    f"{self.allocator.total - 1}; raise total_pages or "
                    "lower max_tokens"))
                continue
            pages = self.allocator.alloc(need)
            if pages is None:
                # pool exhausted: hold at the HEAD of the line (not the back
                # of the FIFO) so a big request can't be starved forever by
                # later-arriving small ones grabbing every freed page
                self._admit_backlog.appendleft(req)
                break
            slot = free_slots.pop(0)
            # the slot owns the request (and its pages) before any program
            # runs: a prefill that raises must find it there (_fail_all)
            req.slot = slot
            self._slots[slot] = req
            self._slot_pages[slot] = pages
            self._count_admitted(req, now)
            admitted.append((req, slot, pages, bucket))
            budget -= bucket
        by_bucket: Dict[int, List[tuple]] = {}
        for item in admitted:
            by_bucket.setdefault(item[3], []).append(item)
        groups = []
        for bucket, group in by_bucket.items():
            rows = self._rows_of(bucket)
            for i in range(0, len(group), rows[-1]):
                chunk = group[i:i + rows[-1]]
                groups.append((chunk, bucket,
                               next(r for r in rows if r >= len(chunk))))
        return groups

    def _rows_of(self, bucket: int) -> tuple:
        """The row counts of ``bucket``'s programs: those an iteration's
        budget can fill."""
        fit = tuple(r for r in self._prefill_rows
                    if r * bucket <= PREFILL_TOKENS_PER_ITER)
        return fit or self._prefill_rows[:1]

    def _launch(self, program: int, call, *args):
        """Every call into a compiled program (``program``: ``PREFILL``,
        ``DECODE`` or ``BRING_UP``), call to return: ``work_ns.launch``. It
        ends a stretch in which the chip had nothing queued (``starved_ns``);
        a result that is ready the moment the call returns (a query, no sync)
        means the loop thread waited the program out (``launch_waited``) and
        the chip is empty again; one that is not stays ``_in_flight`` for
        the operations behind it to ask (``_slot_update``)."""
        t0 = time.perf_counter_ns()
        if self._empty_since:
            self._starved_ns += t0 - self._empty_since
            self._empty_since = 0
        with span("engine.launch", program=program):
            out = call(*args)
        t1 = time.perf_counter_ns()
        self._work_ns["launch"] += t1 - t0
        self._work_calls["launch"] += 1
        self._in_flight = out[0] if isinstance(out, tuple) else out
        if self._in_flight.is_ready():
            self._work_calls["launch_waited"] += 1
            self._chip_empty(t1)
        return out

    def _chip_empty(self, since: int) -> None:
        """The loop has learnt that everything it launched is done."""
        self._empty_since = since
        self._in_flight = None

    def _slot_update(self, op, *args):
        """The ONE place the loop issues a device operation on per-slot state
        between two launches: ``_update`` (``_update_slots``) and a chunk's
        key split. Counts the operation (``work_calls.slot_update``), stamps
        it (``work_ns.slot_update``) and calls it. An operation behind a
        launch may be where the loop waits that program out: when it returns
        and finds the launch's result ready (a query), the chip is empty from
        then on."""
        t0 = time.perf_counter_ns()
        with span("engine.slot_update"):
            out = op(*args)
        t1 = time.perf_counter_ns()
        self._work_calls["slot_update"] += 1
        self._work_ns["slot_update"] += t1 - t0
        if self._in_flight is not None and self._in_flight.is_ready():
            self._chip_empty(t1)
        return out

    def _pad_rows(self, size: int) -> np.ndarray:
        """What places a prefill group's `size` rows, [size, 2 +
        pages_per_slot] (slot, prompt length, table row), with every row a pad
        row: the slot no array has, the trash page."""
        placed = np.zeros((size, 2 + self.pages_per_slot), np.int32)
        placed[:, 0] = self.num_slots
        placed[:, 1] = 1
        return placed

    def _run_prefill(self, chunk: List[tuple], bucket: int, size: int,
                     program: int = PREFILL):
        """The prefill program of `bucket` at `size` rows over `chunk`; pad
        rows write to the trash page and the trash state row and are
        discarded. Returns the rows' first tokens, [size], on the device, and
        what ``_update_slots`` places them by (``_pad_rows``), on the host."""
        jnp = self._jnp
        t0 = time.perf_counter_ns()
        n_pages = bucket // self.page_size
        tokens = np.zeros((size, bucket), np.int32)
        placed = self._pad_rows(size)
        for row, (req, slot, pages, _b) in enumerate(chunk):
            n = len(req.tokens)
            tokens[row, :n] = req.tokens
            placed[row, :2] = slot, n
            placed[row, 2:2 + len(pages)] = pages
        # the program's own view of the same rows: the bucket's pages, the
        # lengths, and for a family with per-slot state the slots
        args = [jnp.asarray(tokens), jnp.asarray(placed[:, 2:2 + n_pages]),
                jnp.asarray(placed[:, 1])]
        if self._slot_state:
            args.append(jnp.asarray(placed[:, 0]))
        self._work_ns["pack"] += time.perf_counter_ns() - t0
        return self._launch(program, self._prefill_firsts, args), placed

    def _prefill_firsts(self, args: list):
        """The prefill program and the pick of each row's first token."""
        # a module that names PREFILL_COUNTERS returns them as well
        logits, self.cache, *counts = self._prefill(
            self.params, self.cache, *args)
        self._prefill_counts_pending += counts
        return self._jnp.argmax(logits, axis=-1).astype(self._jnp.int32)

    def _update_slots(self, firsts, placed: np.ndarray) -> None:
        """ONE ``_update``: the slots retired since the last go dead, then
        the real rows of ``placed`` go live, each with its token of
        ``firsts``."""
        retired, self._retiring = self._retiring, np.zeros_like(self._retiring)
        self._table, self._tokens, self._positions, self._active = \
            self._slot_update(self._update, self._table, self._tokens,
                              self._positions, self._active, retired, firsts,
                              placed)

    def _bring_up(self, bucket: int) -> None:
        """A bucket met for the first time: compile (or load from the
        persistent cache) its program at EVERY row count now, so that no
        later group meets a new shape, by one call each whose rows are all
        pad rows (largest first: the device runs one while the host brings
        the next up), and behind it ``_update`` at that row count, which pad
        rows make a no-op: a program of its own only the first time an engine
        meets the row count, whatever the bucket."""
        t0 = time.perf_counter()
        rows = self._rows_of(bucket)
        for size in reversed(rows):
            self._update_slots(*self._run_prefill([], bucket, size, BRING_UP))
        self._buckets_up.add(bucket)
        logger.info("prefill bucket %d: programs of %s rows up in %.2f s",
                    bucket, rows, time.perf_counter() - t0)

    def _prefill_group(self, chunk: List[tuple], bucket: int, size: int):
        """One batched prefill program for `chunk`, at the `size` rows that
        admission chose for it, and ONE program behind it that makes each
        request's slot live. Returns the rows' first tokens, on the device."""
        self._count_prefill(len(chunk), size,
                            sum(len(req.tokens) for req, *_ in chunk), bucket)
        firsts, placed = self._run_prefill(chunk, bucket, size)
        self._update_slots(firsts, placed)
        return firsts

    def _notify(self, req: GenRequest, done: bool = False) -> None:
        """Hand other threads what the loop has for them (``work_ns.notify``,
        one stamp pair and one ``work_calls.notify`` a call): newly-decoded
        tokens to a streaming consumer, and for a request that is ``done``
        the end sentinel and the future's result."""
        if req.stream_q is None and not done:
            return
        t0 = time.perf_counter_ns()
        if req.stream_q is not None:
            while req.streamed < len(req.out_tokens):
                req.stream_q.put(req.out_tokens[req.streamed])
                req.streamed += 1
            if done:
                req.done_pushed_at = time.time()
                req.stream_q.put(None)  # end-of-stream sentinel
        if done:
            req.future.set_result({
                "tokens": req.out_tokens,
                "ttft_s": req.ttft_s,
                "latency_s": time.perf_counter() - req.submitted_at,
            })
        self._work_ns["notify"] += time.perf_counter_ns() - t0
        self._work_calls["notify"] += 1

    def _finished(self, req: GenRequest) -> bool:
        if req.cancelled:
            return True
        if len(req.out_tokens) >= req.max_tokens:
            return True
        if req.eos_token is not None and req.out_tokens and \
                req.out_tokens[-1] == req.eos_token:
            return True
        if req.slot >= 0 and len(req.tokens) + len(req.out_tokens) >= self.max_seq:
            return True
        return False

    def _retire(self, slots: List[int]) -> None:
        """An iteration's finished slots, on the host: their pages back to
        the allocator and each request's answer. The device still holds them
        live, with table rows of released pages: ``_retiring`` carries them
        to the next ``_update``, which ``_step`` issues before any decode
        launch (the device runs in order, and only a decode program writes
        through the table), so no retired slot's frozen decode writes reach
        pages the allocator has handed out again."""
        self._retiring[slots] = True
        for slot in slots:
            req = self._slots[slot]
            self._slots[slot] = None
            self.allocator.release(self._slot_pages[slot])
            self._slot_pages[slot] = None
            if req.eos_token is not None and req.eos_token in req.out_tokens:
                req.out_tokens = req.out_tokens[
                    : req.out_tokens.index(req.eos_token) + 1]
            self._tokens_out += len(req.out_tokens)
            self._retired += 1
            self._notify(req, done=True)

    def _fail_request(self, req: GenRequest, error: BaseException) -> None:
        try:
            req.future.set_exception(error)
        except InvalidStateError:
            return  # already answered, or failed by the other thread
        if req.stream_q is not None:
            req.stream_q.put(None)

    def _fail_all(self, error: BaseException) -> None:
        """The step raised: every in-flight, backlogged and queued request
        gets the exception, and later submissions are refused with it."""
        self._failed = error
        for req in self._slots:
            if req is not None:
                self._fail_request(req, error)
        self._slots = [None] * self.num_slots
        while self._admit_backlog:
            self._fail_request(self._admit_backlog.popleft(), error)
        while True:
            try:
                self._fail_request(self._pending.get_nowait(), error)
            except queue.Empty:
                return

    def _loop(self) -> None:
        try:
            while not self._shutdown:
                self._step()
        except Exception as e:  # noqa: BLE001 - reported to every caller
            logger.exception("llm engine stopped: step failed")
            self._fail_all(e)
        finally:
            self._heart.close()

    def _step(self) -> None:
        """One iteration, in six phases that partition it (``PHASES``): each
        is a span on the device trace's clock and, at the end, one integer
        add into ``phase_ns`` and one into ``phase_cpu_ns``: the seven
        boundaries are stamped on the wall clock and on the loop thread's CPU
        clock, and each is a beat of the loop's heart."""
        jax = self._jax
        clock, cpu, beat = time.perf_counter_ns, time.thread_time_ns, \
            self._heart.beat
        host = self._host_events
        compiles0, gc_ns0 = host.compiles, host.gc_pause_ns
        admitted0, retired0 = self._admitted, self._retired
        started_wall = time.time()
        process_cpu0 = time.process_time_ns()
        t0, c0 = clock(), cpu()
        beat(0, t0)
        with span("engine.admit"):
            groups = self._admit()
        t1, c1 = clock(), cpu()
        beat(1, t1)
        firsts = []  # a prefill group's first tokens, on the device
        for chunk, bucket, size in groups:
            if bucket not in self._buckets_up:
                with span("engine.prefill_bring_up", bucket=bucket):
                    self._bring_up(bucket)
            with span("engine.prefill_dispatch", bucket=bucket,
                      rows_real=len(chunk), rows_padded=size,
                      state_rows=len(chunk) if self._slot_state else 0):
                firsts.append(self._prefill_group(chunk, bucket, size))
        t2, c2 = clock(), cpu()
        if not any(r is not None for r in self._slots):
            beat(BEAT_IDLE, t2)
            with span("engine.idle"):
                time.sleep(0.01)  # idle: poll for work (_admit drains FIFO)
            idle = clock() - t0
            self._idle_ns += idle
            # an idle loop has launched nothing: the chip stays known empty,
            # and the poll is idle time, not starvation of a busy loop
            if self._empty_since:
                self._empty_since += idle
            self._ended = 0
            return
        if self._ended:  # a busy iteration behind a busy one
            self._between_ns += t0 - self._ended
        beat(2, t2)
        with span("engine.decode_dispatch"):
            if self._retiring.any():  # no prefill group carried them
                size = self._prefill_rows[0]
                self._update_slots(np.zeros((size,), np.int32),
                                   self._pad_rows(size))
            self._key, sub = self._slot_update(jax.random.split, self._key)
            # a model with routed experts returns their counts as well
            sampled, last, self._positions, self.cache, *counts = \
                self._launch(
                    DECODE, self._decode, self.params, self.cache,
                    self._tokens, self._positions, self._active, self._table,
                    sub,
                )
            self._tokens = last
            self._steps += self.decode_chunk
        t3, c3 = clock(), cpu()
        beat(3, t3)
        with span("engine.device_get"):
            # ONE host sync per chunk: the chunk's tokens and, one array a
            # group, the first tokens of this round's prefills
            host_tokens, host_firsts, host_counts, prefill_counts = \
                jax.device_get((sampled, firsts, counts,
                                self._prefill_counts_pending))
            if host_counts:
                self._device_counts += host_counts[0]
            for call_counts in prefill_counts:
                self._prefill_counts += call_counts
            self._prefill_counts_pending = []
        t4, c4 = clock(), cpu()
        beat(4, t4)
        self._chip_empty(t4)  # the fetch holds the last program's result
        now = t4 / 1e9  # perf_counter's clock, as submitted_at
        now_wall = time.time()
        active = self._admitted - retired0  # every admitted request retires
        self._decode_rows_live += active * self.decode_chunk
        t5, c5 = self._emit(host_tokens, groups, host_firsts, now, now_wall)
        t6, c6 = clock(), cpu()
        beat(6, t6)
        self._ended = t6
        self._record_iter(started_wall, (t0, t1, t2, t3, t4, t5, t6),
                          (c0, c1, c2, c3, c4, c5, c6),
                          time.process_time_ns() - process_cpu0,
                          active, self._admitted - admitted0,
                          self._retired - retired0,
                          host.compiles - compiles0, host.gc_pause_ns - gc_ns0)

    def _emit(self, host_tokens, groups: List[tuple], host_firsts, now: float,
              now_wall: float) -> tuple:
        """Tokens to requests and streams: to a request of this round's
        ``groups`` the token its prefill sampled, by its row of the group's
        array in ``host_firsts``; to every live slot its row of the chunk.
        Then the iteration's finished slots are retired together. Returns the
        instant between the two, where ``engine.emit`` ends and
        ``engine.retire`` starts, on the wall clock and on the loop thread's
        CPU clock."""
        finished = []
        with span("engine.emit"):
            for (chunk, _b, _s), firsts in zip(groups, host_firsts):
                for (req, *_), first in zip(chunk, firsts):  # pad rows: last
                    req.ttft_s = now - req.submitted_at
                    req.first_pushed_at = now_wall
                    req.out_tokens.append(int(first))
                    self._notify(req)  # first token streams immediately
            for slot, req in enumerate(self._slots):
                if req is None:
                    continue
                if not self._finished(req):
                    for t in host_tokens[slot]:
                        req.out_tokens.append(int(t))
                        if self._finished(req):
                            break
                    self._notify(req)
                if self._finished(req):
                    finished.append(slot)
        boundary = time.perf_counter_ns(), time.thread_time_ns()
        self._heart.beat(5, boundary[0])
        if finished:
            with span("engine.retire"):
                self._retire(finished)
        return boundary

    def _record_iter(self, started_wall: float, t: tuple, cpu: tuple,
                     process_cpu_ns: int, active: int, admitted: int,
                     retired: int, compiles: int, gc_ns: int) -> None:
        """Constant work an iteration: the counters, one ring row, and the
        slow-iteration check (the ring's median is read for an iteration over
        the 1 s floor, and every ``LIMIT_EVERY_ITERS`` iterations for the
        heart's limit). ``t`` and ``cpu``: the
        seven phase boundaries on the wall clock and on the loop thread's
        CPU clock; ``process_cpu_ns``: the process's over the iteration."""
        total = t[6] - t[0]
        loop_cpu_ns = cpu[6] - cpu[0]
        phases = [b - a for a, b in zip(t, t[1:])]
        acc, acc_cpu = self._phase_ns, self._phase_cpu_ns
        for i, ns in enumerate(phases):
            acc[i] += ns
            acc_cpu[i] += cpu[i + 1] - cpu[i]
        self._iter_ns += total
        self._loop_cpu_ns += loop_cpu_ns
        self._process_cpu_ns += process_cpu_ns
        self._ring[self._iters % RING_ITERS] = (
            started_wall, *(ns / 1e9 for ns in phases), active, admitted, retired)
        self._iters += 1
        if total > self._longest_iter_ns:
            self._longest_iter_ns = total
        slow = total > SLOW_ITER_FLOOR_S * 1e9
        due = self._iters == LIMIT_MIN_ITERS \
            or self._iters % LIMIT_EVERY_ITERS == 0
        if not (slow or due):
            return
        rows = self._ring[:min(self._iters, RING_ITERS)]
        median_s = float(np.median(rows[:, 1:1 + len(PHASES)].sum(axis=1)))
        if due:
            self._heart.limit_ns = int(1e9 * max(
                SLOW_ITER_FLOOR_S, SLOW_ITER_MEDIANS * median_s))
        if not slow or total <= SLOW_ITER_MEDIANS * median_s * 1e9:
            return
        worst = max(range(len(PHASES)), key=phases.__getitem__)
        record = {
            "at": started_wall, "total_s": total / 1e9,
            "phase": PHASES[worst], "phase_s": phases[worst] / 1e9,
            "median_s": median_s, "compiles": compiles, "gc_s": gc_ns / 1e9,
            "cpu_s": process_cpu_ns / 1e9, "loop_cpu_s": loop_cpu_ns / 1e9,
            "steal_s": stall_watch().steal_since(t[0]),
            "queued": self._queued(), "active": active,
        }
        self._slow_iters.append((record, t[0], t[6]))
        now = time.perf_counter()
        if now - self._slow_logged_at < SLOW_LOG_EVERY_S:
            self._slow_unlogged += 1
            return
        self._slow_logged_at = now
        logger.warning(
            "slow engine iteration: %.3f s at %.3f (median %.3f s); longest "
            "phase %s %.3f s; compiles %d, gc %.3f s; cpu %.3f s (loop thread "
            "%.3f s), steal %.2f s over it; queued %d, active %d; %d "
            "earlier ones not logged", record["total_s"], started_wall,
            median_s, record["phase"], record["phase_s"], compiles,
            record["gc_s"], record["cpu_s"], record["loop_cpu_s"],
            record["steal_s"], record["queued"], active, self._slow_unlogged)


class LLMDeployment:
    """Serve deployment wrapping LLMEngine. Construct via serve.deployment:

        app = serve.deployment(LLMDeployment, name="llm").bind(model="tiny")
        handle = serve.run(app)
        handle.generate.remote({"tokens": [...], "max_tokens": 32}).result()
    """

    def __init__(self, model: Any = "tiny", num_slots: int = 8,
                 decode_chunk: int = 8, max_seq_len: Optional[int] = None,
                 temperature: float = 0.0, params=None,
                 total_pages: Optional[int] = None):
        """``model``: a preset's name (``model_presets()``) or a configuration
        object of any family (``LlamaConfig``, ``NemotronHConfig``,
        ``LagunaConfig``, ``Phi4FlashConfig``, ``KimiK2Config``)."""
        config = model
        if isinstance(model, str):
            factories = model_presets()
            if model not in factories:
                raise ValueError(
                    f"unknown model '{model}'; options: {sorted(factories)}")
            config = factories[model]()
        self.engine = LLMEngine(
            config, params, num_slots=num_slots, decode_chunk=decode_chunk,
            max_seq_len=max_seq_len, temperature=temperature,
            total_pages=total_pages,
        )

    def __call__(self, request: Dict[str, Any]):
        if request.get("stream"):
            return self.generate_stream(request)
        return self.generate(request)

    def generate(self, request: Dict[str, Any]) -> Dict[str, Any]:
        return self.engine.generate(
            tokens=request["tokens"],
            max_tokens=int(request.get("max_tokens", 64)),
            eos_token=request.get("eos_token"),
            timeout=request.get("timeout"),
        )

    def generate_stream(self, request: Dict[str, Any]):
        """Token-streaming generate: yields {"token": t} per decoded token
        then a final {"done": True, ...} record. Route via a stream=True
        deployment (HTTP chunks) or handle.options(stream=True)."""
        return self.engine.generate_stream(
            tokens=request["tokens"],
            max_tokens=int(request.get("max_tokens", 64)),
            eos_token=request.get("eos_token"),
            timeout=request.get("timeout"),
        )

    def engine_stats(self) -> Dict[str, Any]:
        return self.engine.stats()

    def runtime_report(self) -> Dict[str, Any]:
        """The replica's process and device, which attention its decode
        program holds (claimed, and counted in the lowered text), and its
        compile-cache counts."""
        from ray_tpu.utils.device_report import device_report

        return {
            **device_report(),
            "decode_attention": self.engine.decode_attention,
            "decode_kernel_calls":
                self.engine.decode_program_text().count("tpu_custom_call"),
        }

    def __del__(self):
        try:
            self.engine.stop()
        except Exception:  # noqa: BLE001
            pass
