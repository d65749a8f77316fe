"""The readers of the train loop's step ring and of the stall watch's
lateness ring, each on a hand-made ``ctx``: what they read, what they leave
out (steps outside the window, after ``rate_until``, the profiler's call),
and that a program without the ring (the parent) reads None, never 0.0."""
import pytest

from benchmarks.harness import manifest as mf
from benchmarks.readers import host_pause, train_step_ring

COLUMNS = ["start", "interval", "feed", "dispatch", "report_put",
           "report_wake", "rest", "cpu", "buffered"]
TRAIN = ["train_4k", "train_moe_8k", "train_gdn_32k"]
SATURATED = ["serve_longprompt", "serve_hybrid_longreply",
             "serve_window_longctx", "serve_yoco_longctx", "serve_chat_sat",
             "serve_mla_longdoc", "serve_kda_longdoc"]


def step(start, interval, dispatch=0.004):
    return [start, interval, 0.001, dispatch, 0.0, 0.0,
            interval - 0.001 - dispatch, 0.05, 2]


def train_ctx(rows, late=(), rate_until=None):
    return {
        "window_open": 1000.0, "window_close": 1050.0, "rate_until": rate_until,
        "device_report": {"host": {
            "watch": {"late_ring": [list(p) for p in late]},
            "loops": {"train": {"ring": {"columns": COLUMNS, "rows": rows}}}}},
    }


@pytest.fixture
def stalled_ctx():
    """Warm-up steps before the window, then 53 steps inside it: 52 of 0.86 s
    and one of 2.1 s (the stall); one more that ends after the window."""
    rows, t = [], 1000.01 - 5 * 0.9
    for _ in range(5):
        rows.append(step(t, 0.9, dispatch=0.3))
        t += 0.9
    for i in range(53):
        length = 2.1 if i == 20 else 0.86
        rows.append(step(t, length))
        t += length
    rows.append(step(t, 5.0))  # began inside, ended after the close
    return train_ctx(rows)


def read(name, ctx):
    return mf.read_metric(name, ctx)


def test_the_three_train_entries_read_the_steps_of_the_window(stalled_ctx):
    assert len(train_step_ring.steps(stalled_ctx)) == 53
    assert read("train_step_wall_p50", stalled_ctx) == pytest.approx(860.0)
    assert read("train_longest_step", stalled_ctx) == pytest.approx(2.1)
    share = read("train_over_median_share", stalled_ctx)
    assert 2.6 < share < 2.7
    assert share == pytest.approx(100 * (2.1 - 0.86) / (52 * 0.86 + 2.1))
    # any column of the ring, by the reader's parameters
    assert train_step_ring.read(stalled_ctx, {
        "column": "dispatch", "stat": "mean", "scale": 1e3}) == pytest.approx(4.0)


def test_steps_after_rate_until_are_left_out(stalled_ctx):
    """A traced run: the profiler starts 15 s into the window, before the
    stall; the steps it slows are not read."""
    stalled_ctx["rate_until"] = 1015.0
    kept = train_step_ring.steps(stalled_ctx)
    assert len(kept) == 17 and all(r["interval"] == 0.86 for r in kept)
    assert read("train_longest_step", stalled_ctx) == pytest.approx(0.86)
    assert read("train_over_median_share", stalled_ctx) == pytest.approx(0.0)


def test_a_quiet_run_reads_about_nothing_over_its_median():
    rows = [step(1000.0 + 0.86 * i, 0.86 + 0.001 * (i % 3)) for i in range(50)]
    assert read("train_over_median_share", train_ctx(rows)) < 0.1


@pytest.mark.parametrize("ctx", [
    {}, {"device_report": {"platform": "tpu"}},
    {"window_open": 1000.0, "window_close": 1050.0,
     "device_report": {"host": {"loops": {}, "watch": {}}}},
    train_ctx([]), train_ctx([step(10.0, 0.86)]),
], ids=["empty", "parent", "no_loop", "no_rows", "none_inside"])
def test_a_program_without_the_ring_reads_none(ctx):
    for name in ("train_step_wall_p50", "train_longest_step",
                 "train_over_median_share", "host_pause_longest.train",
                 "host_pause_longest"):
        assert read(name, ctx) is None


def test_the_longest_pause_of_a_train_window():
    late = [(990, 9_000_000_000), (1000, 400_000), (1010, 1_900_000_000),
            (1020, 300_000), (1049, 700_000), (1050, 8_000_000_000)]
    ctx = train_ctx([], late)
    assert read("host_pause_longest.train", ctx) == pytest.approx(1900.0)
    ctx["rate_until"] = 1005.0  # the profiler started before the pause
    assert read("host_pause_longest.train", ctx) == pytest.approx(0.4)


def test_the_longest_pause_of_a_serving_window_outside_the_profilers_call():
    """Window 100-150 on the runner's clock (wall = clock + 1000), the
    profiler's call 121-127."""
    late = [(1099, 5e9), (1100, 2e5), (1110, 3e5), (1120, 4e9), (1124, 6e9),
            (1127, 3e9), (1129, 2.5e6), (1140, 1.2e9), (1149, 1e5), (1150, 7e9)]
    ctx = {"marks": {"open": 100.0, "close": 150.0, "open_wall": 1100.0,
                     "trace_call": (121.0, 127.0), "polls": []},
           "device_report": {"host": {"watch": {"late_ring": late}}}}
    assert read("host_pause_longest", ctx) == pytest.approx(1200.0)
    del ctx["marks"]["trace_call"]  # an untraced run reads every second
    assert read("host_pause_longest", ctx) == pytest.approx(6000.0)
    del ctx["marks"]["close"]
    assert read("host_pause_longest", ctx) is None


def test_the_five_entries_are_in_the_manifest_at_its_end():
    manifest = mf.load_manifest()
    names = [m["name"] for m in manifest["per_layer"]]
    mine = ["train_step_wall_p50", "train_longest_step",
            "train_over_median_share", "host_pause_longest.train",
            "host_pause_longest"]
    at = names.index(mine[0])
    assert names[at:at + 5] == mine
    for m in manifest["per_layer"][at:at + 5]:
        assert m["source"] == "program_counter" and m["better"] == "lower"
        serve = m["name"] == "host_pause_longest"
        assert m["workloads"][:7 if serve else 3] == (SATURATED if serve else TRAIN)
        assert m["moves"] == ("serve_tokens_per_s" if serve
                              else "train_tokens_per_s_chip")
