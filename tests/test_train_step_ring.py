"""The train loop's flight recorder (``profiling.StepRing``): the marks at the
three places every train loop passes, on the CPU at a tiny size. What it
records is asserted; what it costs is measured on the chip (PERF.md)."""
import logging
import time

import numpy as np
import pytest

from ray_tpu import profiling
from ray_tpu.train.config import RunConfig, ScalingConfig
from ray_tpu.train.trainer import TpuTrainer

STEPS, SLEEP_AT, SLEEP_S, REPORT_EVERY = 32, 20, 1.0, 4


def train_fn(config):
    import jax
    import jax.numpy as jnp

    from ray_tpu import train
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.train.session import get_dataset_shard
    from ray_tpu.utils.device_report import device_report

    cfg = LlamaConfig.tiny(dtype=jnp.float32, remat=None,
                           attention_impl="reference")
    opt = train.default_optimizer(lr=1e-2, warmup_steps=1, total_steps=50)
    state = train.make_train_state_factory(cfg, opt)(jax.random.key(0))
    step = train.make_train_step(cfg, opt, donate=False)
    assert hasattr(step, "lower")  # the jitted function's own, through the marks
    i = 0
    for batch in get_dataset_shard("train").iter_jax_batches(batch_size=2):
        state, out = step(state, batch["tokens"], batch["targets"])
        loss = float(jax.device_get(out["loss"]))
        i += 1
        if i == SLEEP_AT:
            time.sleep(SLEEP_S)  # the loop thread asleep, like a stalled fetch
        if i % REPORT_EVERY == 0:
            train.report({"step": i, "loss": loss})
    time.sleep(0.2)  # the watch closes its record a sample after the beat
    train.report({"final": True, "steps": i, "stats": train.loop_stats(),
                  "host": device_report()["host"]})


@pytest.fixture(scope="module")
def final(tmp_path_factory):
    import ray_tpu
    import ray_tpu.data as rd

    lines = []

    class Catch(logging.Handler):
        def emit(self, record):
            lines.append(record.getMessage())

    handler = Catch(level=logging.WARNING)
    profiling.logger.addHandler(handler)
    ray_tpu.init(num_cpus=4)
    try:
        tokens = np.random.default_rng(0).integers(
            0, 256, (2 * STEPS, 32)).astype(np.int32)
        ds = rd.from_numpy({"tokens": tokens,
                            "targets": np.roll(tokens, -1, axis=1)})
        result = TpuTrainer(
            train_fn,
            scaling_config=ScalingConfig(num_workers=1, cpus_per_worker=2),
            run_config=RunConfig(name="ring", storage_path=str(
                tmp_path_factory.mktemp("ring"))),
            datasets={"train": ds},
        ).fit()
    finally:
        ray_tpu.shutdown()
        profiling.logger.removeHandler(handler)
    assert result.error is None
    out = result.metrics_history[-1]
    assert out.get("final") and out["steps"] == STEPS
    out["lines"] = lines
    return out


def test_one_ring_row_a_step_and_the_parts_sum_to_the_interval(final):
    stats = final["stats"]
    ring = stats["ring"]
    assert ring["columns"] == list(profiling.STEP_COLUMNS)
    # a step's row closes at the next take: the last one stays open
    assert stats["steps"] == len(ring["rows"]) == STEPS - 1
    assert stats["reports"] == STEPS // REPORT_EVERY
    for row in ring["rows"]:
        r = dict(zip(ring["columns"], row))
        parts = sum(r[c] for c in profiling.STEP_PARTS)
        assert abs(parts - r["interval"]) < 1e-3
        assert all(r[c] >= 0 for c in profiling.STEP_PARTS)
        assert r["dispatch"] > 0 and r["buffered"] >= 1
        assert 0 <= r["cpu"] <= r["interval"] + 0.05
    starts = [row[0] for row in ring["rows"]]
    assert starts == sorted(starts) and abs(starts[-1] - time.time()) < 600
    reported = [dict(zip(ring["columns"], row)) for row in ring["rows"]]
    assert sum(r["report_put"] + r["report_wake"] > 0 for r in reported) \
        == (STEPS - 1) // REPORT_EVERY
    sums = stats["sum_ns"]
    assert abs(sums["interval"] - 1e9 * sum(r["interval"] for r in reported)) < 1e6
    assert stats["longest_step_ns"] == max(
        round(r["interval"] * 1e9) for r in reported) or \
        abs(stats["longest_step_ns"] / 1e9 - max(r["interval"] for r in reported)) < 1e-6


def test_a_step_that_sleeps_is_one_slow_step_with_the_watchs_record(final):
    stats = final["stats"]
    assert len(stats["slow_steps"]) == 1
    rec = stats["slow_steps"][0]
    assert rec["step"] == SLEEP_AT and rec["part"] == "rest"
    assert SLEEP_S <= rec["total_s"] and rec["part_s"] >= SLEEP_S
    assert rec["total_s"] > stats["limit_s"] >= profiling.SLOW_STEP_OVER_S
    # the loop thread slept through it
    assert rec["loop_cpu_s"] < rec["total_s"] - 0.5 and rec["steal_s"] >= 0
    stall = rec["stall"]
    assert stall["loop"] == stats["loop"] and stall["phase"] == "rest"
    assert stall["class"] == "all_asleep", stall
    assert stall["waited_s"] >= SLEEP_S and stall["samples"] >= 5
    assert stall["late_longest_s"] < 0.5 * stall["waited_s"]
    slow_lines = [m for m in final["lines"] if m.startswith("slow train step")]
    assert len(slow_lines) == 1 and "rest" in slow_lines[0]


def test_loop_stats_and_the_device_report_hold_the_same_ring(final):
    stats, host = final["stats"], final["host"]
    loop = host["loops"][stats["loop"]]
    assert loop["ring"]["rows"] == stats["ring"]["rows"]
    assert [s["at"] for s in loop["slow_steps"]] == \
        [s["at"] for s in stats["slow_steps"]]
    assert loop["in_flight"] is None and loop["limit_s"] == stats["limit_s"]
    watch = host["watch"]
    mine = [s for s in watch["stalls"] if s["loop"] == stats["loop"]]
    assert len(mine) == 1 and mine[0]["at_ns"] == stats["slow_steps"][0]["stall"]["at_ns"]
    assert watch["stalls_total"] >= 1 and watch["ticks"] > 0
    assert watch["late_ring"] and all(len(pair) == 2 for pair in watch["late_ring"])


def test_the_marks_by_hand(monkeypatch):
    """The ring alone, its clock in the test's hands: parts, the threshold
    after eight steps, the wrap past 256 rows."""
    clock = [1_000_000_000]
    monkeypatch.setattr(profiling.time, "perf_counter_ns", lambda: clock[0])
    ring = profiling.StepRing("by-hand")
    try:
        def step(feed_ms, dispatch_ms, rest_ms, report=False):
            ring.dispatch()
            clock[0] += dispatch_ms * 1_000_000
            ring.dispatched()
            clock[0] += rest_ms * 1_000_000
            if report:
                ring.report_put()
                clock[0] += 2_000_000
                ring.report_wait()
                clock[0] += 3_000_000
                ring.reported()
            ring.back()
            clock[0] += feed_ms * 1_000_000
            ring.take(2)

        ring.take(2)
        for _ in range(7):
            step(10, 20, 70)
        assert ring.stats()["limit_s"] == 0 and ring.heart.limit_ns == 0
        step(10, 20, 70, report=True)
        stats = ring.stats()
        assert stats["steps"] == 8 and stats["reports"] == 1
        last = dict(zip(stats["ring"]["columns"], stats["ring"]["rows"][-1]))
        assert [round(1e3 * last[c]) for c in (
            "interval", "feed", "dispatch", "report_put", "report_wake", "rest")] \
            == [105, 10, 20, 2, 3, 70]
        # median 0.1 s: the floor over it decides, and is the heart's limit
        assert stats["limit_s"] == pytest.approx(0.1 + profiling.SLOW_STEP_OVER_S)
        assert ring.heart.limit_ns == round(stats["limit_s"] * 1e9)
        step(10, 20, 300)  # 0.33 s: under the threshold
        assert not ring.stats()["slow_steps"]
        step(10, 400, 70)  # 0.48 s in all, most of it inside the call
        slow = ring.stats()["slow_steps"]
        assert len(slow) == 1 and slow[0]["part"] == "dispatch"
        assert slow[0]["total_s"] == pytest.approx(0.48) and "stall" not in slow[0]
        for _ in range(profiling.RING_STEPS):
            step(10, 20, 70)
        stats = ring.stats()
        assert stats["steps"] == 10 + profiling.RING_STEPS
        assert len(stats["ring"]["rows"]) == profiling.RING_STEPS
        assert stats["sum_ns"]["dispatch"] == (8 * 20 + 20 + 400 + 256 * 20) * 1_000_000
    finally:
        ring.close()
