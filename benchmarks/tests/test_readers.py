"""Each metric's reader on hand-made observations."""
import math

import pytest

from benchmarks.harness import manifest as mf
from benchmarks.harness import trace_reduce
from benchmarks.harness.loadgen import RequestRecord
from benchmarks.readers import (
    engine_step_wall, generator_lag, idle_share, ingress_overhead, input_wait,
    kernel_roofline, module_time, peak_hbm, report_stall, report_wait,
    ring_percentile, serve_token_rate,
    slot_occupancy, tpot_percentile, train_mfu, train_token_rate, ttft_mean,
    ttft_percentile)


def rec(i, due, first, n, gap, measured=True, error=None, engine_latency=None):
    r = RequestRecord(i, due, 100, n, measured)
    r.sent = due + 0.002
    r.arrivals = [first + k * gap for k in range(n)]
    r.tokens = list(range(n))
    r.finished = r.arrivals[-1] + 0.01 if n else due + 1
    r.error = error
    r.done = {"latency_s": engine_latency} if engine_latency is not None else None
    return r


@pytest.fixture
def serve_ctx():
    records = [rec(i, 10.0 + i, 10.5 + i + 0.01 * i, 11, 0.05 + 0.001 * i,
                   engine_latency=0.9) for i in range(10)]
    records.append(rec(99, 5.0, 5.5, 4, 0.05, measured=False))
    return {"records": records, "t_open": 10.0, "t_close": 21.0}


def test_ttft_and_tpot_percentiles(serve_ctx):
    assert ttft_percentile.read(serve_ctx, {"q": 0.9}) == pytest.approx(0.5 + 0.08)
    assert tpot_percentile.read(serve_ctx, {"q": 0.9}) == pytest.approx(50 + 8)
    assert ttft_percentile.read({"records": []}, {"q": 0.9}) is None


def test_failed_requests_count_as_missing(serve_ctx):
    serve_ctx["records"][0].error = "HTTP 500"
    serve_ctx["records"][1].error = "timeout"
    assert ttft_percentile.read(serve_ctx, {"q": 0.9}) == math.inf
    assert ttft_percentile.read(serve_ctx, {"q": 0.5}) < 1.0


def test_ttft_mean_counts_a_failed_request_as_the_drain_limit(serve_ctx):
    serve_ctx["drain_limit_s"] = 60.0
    # ten measured requests, first tokens 0.50, 0.51 ... 0.59 s after due
    assert ttft_mean.read(serve_ctx, {}) == pytest.approx(0.545)
    serve_ctx["records"][0].error = "HTTP 500"      # it had waited 0.50 s
    serve_ctx["records"][9].arrivals = []           # no token ever came
    assert ttft_mean.read(serve_ctx, {}) == pytest.approx(
        (sum(0.5 + 0.01 * i for i in range(1, 9)) + 2 * 60.0) / 10)
    assert ttft_percentile.read(serve_ctx, {"q": 0.9}) == math.inf
    assert ttft_mean.read({"records": [], "drain_limit_s": 60.0}, {}) is None


def test_serve_token_rate_is_read_between_arrivals():
    r = rec(0, 0.0, 1.0, 5, 1.0)          # tokens at 1, 2, 3, 4, 5
    ctx = {"records": [r], "t_open": 1.5, "t_close": 4.6}
    # inside: 2, 3, 4 -> two tokens after the first instant over 2 s
    assert serve_token_rate.read(ctx, {}) == pytest.approx(1.0)


def test_ingress_and_generator_lag(serve_ctx):
    r0 = serve_ctx["records"][0]
    expected = 1e3 * ((r0.finished - r0.sent) - 0.9)
    got = ingress_overhead.read(serve_ctx, {})
    assert min(1e3 * ((r.finished - r.sent) - 0.9) for r in serve_ctx["records"][:10]) \
        <= got <= 1e3 * ((serve_ctx["records"][9].finished
                          - serve_ctx["records"][9].sent) - 0.9)
    assert expected > 0
    assert generator_lag.read(serve_ctx, {"q": 0.99}) == pytest.approx(2.0)


def test_engine_counters_leave_out_the_traced_seconds():
    polls = [(t, {"decode_steps": int(100 * t), "active": 10 if t < 5 else 20})
             for t in [1, 2, 3, 4, 5, 6, 7, 8]]
    ctx = {"marks": {"open": 0.5, "close": 8.5, "polls": polls,
                     "trace_call": (3.5, 5.5)}}
    # segments 1..2 (3 is within 0.6 s of the trace) and 7..8 (6 likewise)
    assert engine_step_wall.read(ctx, {}) == pytest.approx(10.0)
    assert slot_occupancy.read(ctx, {}) == pytest.approx(15.0)
    assert engine_step_wall.read({"marks": {"polls": []}}, {}) is None


@pytest.mark.parametrize("stalled", [1, 3, 5])
def test_the_train_rate_sees_a_stall_in_the_window(stalled):
    """The end-to-end rate is every token over every second between the
    window's first and last report: one host stall of 1.3 s in one of five
    intervals of 8.67 s reads 2.5-3% low THERE (a later PR that adds or
    removes such a stall moves the metric), and not in the median of the
    intervals' rates that the runner prints beside it."""
    times = [100.0]
    for k in range(1, 6):
        times.append(times[-1] + 8.67 + (1.3 if k == stalled else 0.0))
    ctx = {"reports": times, "report_tokens": [49_152.0] + [163_840.0] * 5,
           "window_open": 100.0, "window_close": 150.0, "chips": 1}
    steady = 163_840.0 / 8.67
    metric, beside = train_token_rate.both(ctx)
    assert train_token_rate.read(ctx, {}) == metric
    assert 0.025 < 1 - metric / steady < 0.03
    assert beside == pytest.approx(steady)


def test_train_rate_mfu_and_waits():
    ctx = {"reports": [100.0, 110.0, 120.0, 130.0], "report_tokens": [3e4, 1e5, 1e5, 1e5],
           "window_open": 100.0, "window_close": 125.0, "chips": 1,
           "device_report": {"kind": "TPU v5 lite"},
           "cfg": {"family": "llama", "hidden_size": 4096, "intermediate_size": 14336,
                   "num_attention_heads": 32, "num_key_value_heads": 8,
                   "head_dim": 128, "vocab_size": 32768, "num_hidden_layers": 4,
                   "deployment": {"max_seq_len": 4096, "warmup_steps": 1}},
           "input_waits": [9.0, 0.001, 0.003], "report_waits": [5.0, 0.002, 0.004]}
    assert train_token_rate.read(ctx, {}) == pytest.approx(1e4)
    assert train_token_rate.both(ctx) == pytest.approx((1e4, 1e4))
    ctx["rate_until"] = 111.0   # a traced run: only the reports before the profiler
    assert train_token_rate.read(ctx, {}) == pytest.approx(1e4)
    ctx["chips"] = 4
    assert train_token_rate.read(ctx, {}) == pytest.approx(2.5e3)
    ctx["chips"] = 1
    mfu = train_mfu.read(ctx, {})
    assert mfu == pytest.approx(100 * 1e4 * 6.44e9 / 197e12, rel=5e-3)
    assert input_wait.read(ctx, {}) == pytest.approx(2.0)
    assert report_wait.read(ctx, {}) == pytest.approx(3.0)
    # a step in the window is 1e5 tokens at 1e4 tokens/s: 10 s. The probe's
    # three steps, a report after each, took 13.5 s each
    assert report_stall.read(ctx, {}) is None
    ctx["tokens_per_step"] = 1e5
    ctx["probe_reports"] = [130.0, 141.0, 152.0, 170.5]
    assert report_stall.read(ctx, {}) == pytest.approx(3500.0)


def test_trace_readers():
    trace = {"busy_s": 3.0, "window_s": 4.0,
             "module_s": {"jit__unknown(1)": 2.4, "jit__unknown(2)": 0.6,
                          "jit_step_fn(3)": 2.7},
             "module_count": {"jit__unknown(1)": 8, "jit__unknown(2)": 2,
                              "jit_step_fn(3)": 3},
             # of the eight decode calls the profile cut one at either edge
             "module_whole_s": {"jit__unknown(1)": 2.1, "jit__unknown(2)": 0.6,
                                "jit_step_fn(3)": 0.9},
             "module_whole_count": {"jit__unknown(1)": 6, "jit__unknown(2)": 2,
                                    "jit_step_fn(3)": 1},
             "module_ops": {"jit__unknown(1)": {"paged_attention.10 = (f32[64": 0.2},
                            "jit__unknown(2)": {
                                "closed_call.15 = bf16[8,32,2048,128]{3} "
                                "custom-call(bf16[8,32,2048,128]{3}": 0.1}},
             "op_self_s": {"paged_attention.10 = (f32[64,32,1,128]": 0.25},
             "op_count": {"paged_attention.10 = (f32[64,32,1,128]": 500}}
    cfg = {"num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128,
           "deployment": {"decode_chunk": 8, "num_slots": 64}}
    ctx = {"trace": trace, "cfg": cfg, "device_report": {
        "kind": "TPU v5 lite", "memory_peak_bytes": 7.0e9}}
    assert idle_share.read(ctx, {}) == pytest.approx(25.0)
    assert peak_hbm.read(ctx, {}) == pytest.approx(7.0)
    assert module_time.read(ctx, {"contains": "^paged_attention",
                                  "steps_key": "decode_chunk"}) == pytest.approx(43.75)
    assert module_time.read(ctx, {"contains": "^paged_attention", "cut_at_edges": False,
                                  "steps_key": "decode_chunk"}) == pytest.approx(37.5)
    assert module_time.read(ctx, {"contains": r"custom-call\(bf16\[8,32,\d+,128\]",
                                  "without": "^paged_attention"}) == pytest.approx(300.0)
    # the training runner starts and stops its profile between steps
    assert module_time.read(ctx, {"module": "^jit_step_fn",
                                  "cut_at_edges": False}) == pytest.approx(900.0)
    assert module_time.read(ctx, {"contains": "nothing"}) is None
    # 10,000 live tokens through the traced interval
    r = rec(0, 0.0, 1.0, 50, 0.1)
    r.prompt_len = 9975
    ctx.update(records=[r], marks={"traced": (3.0, 4.0)})
    live = kernel_roofline._live_tokens(ctx)
    assert 9975 + 20 <= live <= 9975 + 31
    share = kernel_roofline.read(ctx, {"pattern": "^paged_attention", "kind": "paged_attn"})
    need = 500 * (2 * live * 8 * 128 * 2 + 2 * 64 * 32 * 128 * 2)
    assert share == pytest.approx(100 * need / 819e9 / 0.25)
    assert kernel_roofline.read({"trace": {}, "cfg": cfg}, {"pattern": "x", "kind": "flash_fwd"}) is None


# ---- a profile over a span of wall time, as the serving runner takes it ----
MS = 1_000_000  # ns
FLASH = ("closed_call.14 = bf16[{r},32,2048,128]{{3,2,1,0:T(8,128)(2,1)S(1)}} "
         "custom-call(bf16[{r},32,2048,128]{{3,2,1,0:T(8,128)(2,1)S(1)}} %q, ")
CONCAT = ("custom-call.7 = bf16[4,32,2048,128]{3,2,1,0:T(8,128)(2,1)S(1)} "
          "custom-call(bf16[1,32,2048,128]{3,2,1,0:T(8,128)(2,1)S(1)} %slice-done, ")


def _call(ops, modules, name, start, dur, rows=None):
    """One call of program ``name``: thirteen fusions larger than its flash
    call, so that only a ``keep`` pattern holds the flash call in the table."""
    modules.append([name, start, dur])
    inside = [[f"fusion.{i} = bf16[2048,14336]{{1,0}} fusion(", start + i * dur // 20,
               dur // 25] for i in range(13)]
    if rows is not None:
        inside.append([FLASH.format(r=rows), start + 14 * dur // 20, dur // 100])
        if rows == 4:   # four slices of one row each, put together: not the rows
            inside.append([CONCAT, start + 15 * dur // 20, dur // 50])
    ops.extend(inside)


@pytest.fixture
def wall_profile():
    """4-row calls of 200 ms and 1-row calls of 50 ms, a decode chunk of 80 ms
    between; the profile begins 100 ms before a 4-row call ends and stops
    20 ms into a 1-row call."""
    ops, modules = [], []
    four, one, decode = ("jit_paged_prefill(11)", "jit_paged_prefill(22)",
                         "jit_paged_decode_steps(33)")
    t = 0
    for name, dur, rows in [(four, 100 * MS, 4), (decode, 80 * MS, None),
                            (four, 200 * MS, 4), (one, 50 * MS, 1),
                            (decode, 80 * MS, None), (four, 200 * MS, 4),
                            (one, 50 * MS, 1), (decode, 80 * MS, None),
                            (one, 20 * MS, 1)]:
        _call(ops, modules, name, t, dur, rows)
        t += dur + 5 * MS
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": []}


@pytest.mark.parametrize("params,expected", [
    # a row: (2 x 200 + 2 x 50) ms over 2 x 4 + 2 x 1 rows
    ({"module": "^jit_paged_prefill", "rows_from": "metric"}, 50.0),
    # a call, whatever its rows
    ({"module": "^jit_paged_prefill"}, 125.0),
    # a step of a call that holds eight
    ({"module": "^jit_paged_decode", "steps_key": "decode_chunk"}, 10.0),
    # with the two cut calls counted as calls: what the reader did before
    ({"module": "^jit_paged_prefill", "cut_at_edges": False}, 620.0 / 6),
    # no such program in the trace
    ({"module": "^jit_nemotron_h_prefill", "rows_from": "metric"}, None),
    # a program whose rows no operation names
    ({"module": "^jit_paged_decode", "rows_from": "metric"}, None),
])
def test_module_time_leaves_out_cut_calls_and_reads_a_row(wall_profile, params,
                                                          expected):
    rows_from = trace_reduce.FLASH_CALL_ROWS
    if params.get("rows_from") == "metric":
        params = {**params, "rows_from": rows_from}
    trace = trace_reduce.reduce(wall_profile, keep=[rows_from])
    ctx = {"trace": trace, "cfg": {"deployment": {"decode_chunk": 8}}}
    got = module_time.read(ctx, params)
    assert got is None if expected is None else got == pytest.approx(expected)


def test_the_reducer_keeps_the_operation_a_pattern_names(wall_profile):
    rows_from = trace_reduce.FLASH_CALL_ROWS
    bare = trace_reduce.reduce(wall_profile)
    assert not any("closed_call" in k for k in bare["module_ops"]["jit_paged_prefill(11)"])
    assert module_time.read({"trace": bare}, {"module": "^jit_paged_prefill",
                                              "rows_from": rows_from}) is None
    kept = trace_reduce.reduce(wall_profile, keep=[rows_from])
    assert len(kept["module_ops"]["jit_paged_prefill(11)"]) == 13
    assert kept["module_count"]["jit_paged_prefill(11)"] == 3
    assert kept["module_whole_count"] == {
        "jit_paged_prefill(11)": 2, "jit_paged_prefill(22)": 2,
        "jit_paged_decode_steps(33)": 3}


# ---- shares of a program's time, by the operations a pattern names ----
POOL = "bf16[8,12296,64,128]{3,2,1,0:T(8,128)(2,1)}"
STACK = "bf16[8,16,2304,896]{3,2,1,0:T(8,128)(2,1)}"
SHARES = [
    # the parent's two scatter fusions of a decode tick and the kernel that
    # took their place; not the prompt's writes (32 page ids, whole pages)
    ("token_write_decode_share.chat", "mistral-7b-v0.3-serve",
     "jit_paged_decode_steps(1)", {
         f"fusion.135 = {POOL} fusion({POOL} %get-tuple-element.1076, "
         "s32[512]{0:T(512)S(1)} %reshape.333, bf16[512,128]{1,0:T(8,128)(2,1)"
         "S(1)} %bitcast.185), kind=kCustom": 0.04,
         f"token_rows_write.9 = ({POOL}, {POOL}) custom-call(s32[64]{{0:T(128)"
         "S(1)} %broadcast_add_fusion.5, s32[64]{0:T(128)S(1)} %gte.1152)": 0.02},
     {f"fusion.137 = {POOL} fusion({POOL} %get-tuple-element.77, s32[32]"
      "{0:T(128)} %pages, bf16[32,8,64,128]{3,2,1,0} %rows)": 0.5,
      "paged_attention.10 = (f32[64,32,1,128]{3,2,1,0}) custom-call(%q)": 0.3},
     6.0),
    # a compacted block's rows onto their tokens, forward and reverse
    ("experts_combine_train_share", "mellum2-12b-a2.5b-train", "jit_step_fn(1)", {
        "rows-to-tokens.36 = f32[4096,2304]{1,0:T(8,128)} custom-call(s32[1]"
        "{0:T(128)} %bitcast.1499, s32[16384]{0:T(1024)S(1)} %copy-done.91)": 0.011,
        "rows-to-tokens.39 = f32[4096,2304]{1,0:T(8,128)} custom-call(s32[1]"
        "{0:T(128)} %bitcast.1654, s32[16384]{0:T(1024)S(1)} %copy-done.90)": 0.006},
     {"ragged-dot-rows-t.58 = f32[16384,2304]{1,0:T(8,128)} custom-call(%a)": 0.2,
      "add.2635 = f32[16384,2304]{1,0:T(8,128)} add(%b, %c)": 0.1}, 1.7),
    # AdamW's update of a leaf: parameter, first and second moment, one float
    # shape; not three index vectors of the expert layer
    ("optimizer_train_share", "mellum2-12b-a2.5b-train", "jit_step_fn(1)", {
        f"fusion.642 = ({STACK}, {STACK}, {STACK}) fusion({STACK} %state.1, "
        f"{STACK} %g, {STACK} %m, {STACK} %n), kind=kLoop": 0.016,
        "fusion.77 = (bf16[8,2304,4096]{2,1,0}, bf16[8,2304,4096]{2,1,0}, "
        "bf16[8,2304,4096]{2,1,0}) fusion(%p, %g, %m, %n)": 0.008,
        "fusion.614 = (f32[8,2304,64]{1,2,0:T(8,128)}, f32[8,2304,64]{1,2,0:"
        "T(8,128)}, f32[8,2304,64]{1,2,0:T(8,128)}) fusion(%router, %lr)": 0.002},
     {"subtract_convert_fusion.20 = (bf16[2,8192,32,64]{3,1,2,0:T(8,128)(2,1)}, "
      "bf16[2,8192,32,64]{3,1,2,0:T(8,128)(2,1)}) fusion(%a, %b)": 0.3,
      "multiply_reduce_fusion.28 = (f32[16384]{0:T(1024)S(1)}, bf16[16384,896]"
      "{1,0}, bf16[16384,896]{1,0}, bf16[16384,896]{1,0}) fusion(%a)": 0.2,
      "compare_select_fusion.124 = (s32[16384,1]{0,1:T(1,128)}, s32[16384,1]"
      "{0,1:T(1,128)S(1)}, s32[16384,1]{0,1:T(1,128)S(1)}) fusion(%i, %n)": 0.1,
      "select_add_fusion.16 = bf16[16,2304,896]{2,1,0} fusion(%w, %g)": 0.1},
     2.6),
]


@pytest.mark.parametrize("name,config,program,named,others,expected", SHARES,
                         ids=[case[0] for case in SHARES])
def test_a_share_counts_the_operations_its_pattern_names(
        name, config, program, named, others, expected):
    """``ops_share`` entries on operation names as the chip's traces hold
    them: the named operations' seconds over the program's, the other
    operations and the other programs left out; nothing where none ran."""
    import os

    from benchmarks.harness.weights import load_config_file

    cfg = load_config_file(os.path.join(
        mf.ROOT, "benchmarks", "configs", config + ".json"))
    modules = {program: 1.0, "jit_paged_prefill(2)": 5.0, "jit__argmax(3)": 0.5}
    ctx = {"cfg": cfg, "trace": {"op_self_s": {**named, **others},
                                 "module_s": modules}}
    assert mf.read_metric(name, ctx) == pytest.approx(expected)
    ctx["trace"]["op_self_s"] = others
    assert mf.read_metric(name, ctx) is None


# ---- one entry a meaning: what a family or a configuration decides ----
FAMILY_PROGRAMS = {
    "mistral-7b-v0.3-serve": ("jit_paged_decode_steps", "jit_paged_prefill", None),
    "nemotron-3-nano-30b-a3b-serve": ("jit_nemotron_h_decode_steps",
                                      "jit_nemotron_h_prefill", 64),
    "laguna-xs.2-serve": ("jit_laguna_decode_steps", "jit_laguna_prefill", 32),
    "phi-4-mini-flash-reasoning-serve": ("jit_phi4flash_decode",
                                         "jit_phi4flash_prefill", None),
    "kimi-k2.6-serve": ("jit_kimi_k2_decode", "jit_kimi_k2_prefill", 12),
}


@pytest.mark.parametrize("config", sorted(FAMILY_PROGRAMS))
def test_one_entry_reads_each_family_by_what_its_own_files_state(config):
    """``decode_device_per_step``, ``prefill_device_per_call`` and
    ``expert_load_max_over_mean`` are ONE entry each for every family: the
    program's name in the trace and the operation that tells a call's rows
    come from the family's module, the experts held from the configuration's
    file (``manifest.resolve_params``). A trace that holds EVERY family's
    programs reads the cell's own and no other's."""
    import os

    from benchmarks.harness.weights import load_config_file

    cfg = load_config_file(os.path.join(
        mf.ROOT, "benchmarks", "configs", config + ".json"))
    decode, prefill, held = FAMILY_PROGRAMS[config]
    flash = FLASH.format(r=2)
    module_s, count, ops = {}, {}, {}
    for k, (dec, pre, _h) in enumerate(FAMILY_PROGRAMS.values()):
        mine = dec == decode
        module_s[dec + "(1)"] = 0.8 if mine else 7.0 + k
        module_s[pre + "(2)"] = 0.3 if mine else 9.0 + k
        count[dec + "(1)"], count[pre + "(2)"] = 10, 3
        ops[pre + "(2)"] = {flash: 0.01}
    # a prefill program of which the profile saw only a cut call, before its
    # flash call: it has no whole call, names no rows, and silences nothing
    module_s[prefill + "(3)"], ops[prefill + "(3)"] = 0.05, {}
    trace = {"module_s": module_s, "module_count": dict(count, **{prefill + "(3)": 1}),
             "module_whole_s": {k: v for k, v in module_s.items() if "(3)" not in k},
             "module_whole_count": count, "module_ops": ops}
    polls = [(t, {"iters": 10 * t, "decode_steps": 80 * t,
                  "moe_expert_load_max": 50 * t, "moe_assignments_held": 400 * t})
             for t in (1.0, 2.0, 3.0)]
    ctx = {"trace": trace, "cfg": cfg,
           "marks": {"open": 0.0, "close": 9.0, "polls": polls}}
    chunk = cfg["deployment"]["decode_chunk"]
    for name in ("decode_device_per_step", "decode_device_per_step.chat"):
        assert mf.read_metric(name, ctx) == pytest.approx(1e3 * 0.8 / (10 * chunk))
    # a family whose prefill call holds several rows reads a ROW's time
    rows = 2 if mf.family_of(cfg).PREFILL_ROWS_FROM else 1
    for name in ("prefill_device_per_call", "prefill_device_per_call.chat"):
        assert mf.read_metric(name, ctx) == pytest.approx(1e3 * 0.3 / (3 * rows))
    if held is None:
        assert "held_experts" not in cfg
    else:
        assert mf.read_metric("expert_load_max_over_mean", ctx) \
            == pytest.approx(held * 50 / 400)
    # no configuration to resolve by: a miswired caller, not a silent metric
    with pytest.raises(KeyError, match="module"):
        mf.read_metric("decode_device_per_step", {"trace": trace})


def test_resolve_params_names_what_it_cannot_find():
    cfg = {"family": "laguna", "held_experts": [8, 40], "depth": 3}
    got = mf.resolve_params(
        {"a": {"family": "DECODE_MODULE"}, "b": {"family": "PREFILL_ROWS_FROM"},
         "c": {"depth": 3}, "d": {"config_span": "held_experts"},
         "e": 7, "f": {"two": 1, "keys": 2}}, cfg)
    # what the family states as None (its prefill call holds one row) leaves
    # the parameter out; a dict that is not a reference passes as it is
    assert got == {"a": "^jit_laguna_decode", "c": {"depth": 3}, "d": 32, "e": 7,
                   "f": {"two": 1, "keys": 2}}
    # what the family does not state at all is an error that names it: a
    # forgotten PREFILL_ROWS_FROM would read a call's time for a row's
    with pytest.raises(AttributeError, match="laguna states no NO_SUCH_NAME"):
        mf.resolve_params({"a": {"family": "NO_SUCH_NAME"}}, cfg)
    with pytest.raises(KeyError):
        mf.resolve_params({"c": {"config_span": "nothing"}}, cfg)
    with pytest.raises(KeyError, match="'c'"):
        mf.resolve_params({"c": {"config_span": "held_experts"}}, None)
    assert mf.resolve_params({"e": 7}, None) == {"e": 7}


@pytest.mark.parametrize("family", ["llama", "nemotron_h", "laguna", "phi4flash",
                                    "kimi_k2"])
def test_every_serving_family_states_its_programs_and_its_rows(family):
    """The names ``decode_device_per_step`` and ``prefill_device_per_call``
    resolve by: each serving family states all three, ``PREFILL_ROWS_FROM``
    as None where a prefill call holds one row."""
    module = mf.load_plugin("families", family)
    for name in ("DECODE_MODULE", "PREFILL_MODULE"):
        assert getattr(module, name).startswith("^jit_")
    assert module.PREFILL_ROWS_FROM in (None, trace_reduce.FLASH_CALL_ROWS)
    got = mf.metric_params("prefill_device_per_call", {"family": family})
    assert ("rows_from" in got) == (module.PREFILL_ROWS_FROM is not None)


# ---- the flight recorder's ring: which shape admission settled in ----
def _ring_ctx(admitted):
    """Window 100-150 on the runner's clock (wall = clock + 1000); an
    iteration every 0.4 s from 1101, admitting what ``admitted`` cycles
    through; one iteration before the window and one inside the profiler's
    call (121-127) admit 30 and are left out."""
    cols = ["start", "admit", "prefill_dispatch", "decode_dispatch",
            "device_get", "emit", "retire", "active", "admitted", "retired"]
    rows = [[1090.0, 0, 0.2, 0, 0.1, 0, 0, 28.0, 30.0, 0.0],
            [1123.0, 0, 0.2, 0, 0.1, 0, 0, 28.0, 30.0, 0.0]]
    for i in range(45):
        rows.append([1101.0 + 0.4 * i, 0, 0.2, 0, 0.1, 0, 0, 28.0,
                     float(admitted[i % len(admitted)]), 0.0])
    stats = {"ring": {"columns": cols, "rows": rows}}
    return {"marks": {"open": 100.0, "close": 150.0, "open_wall": 1100.0,
                      "trace_call": (121.0, 127.0), "polls": [(110.0, stats)]}}


@pytest.mark.parametrize("admitted,expected", [
    ([3, 4, 3, 4], 4.0),                       # the smooth loop: ring 3434...
    ([10, 22, 0, 0, 0, 0, 0, 0, 0], 22.0),     # one cohort: ring ++0000000
])
def test_admit_burst_says_which_shape_the_loop_is_in(admitted, expected):
    params = mf.metric_file("admit_burst_p90")["params"]
    assert ring_percentile.read(_ring_ctx(admitted), params) == expected
    assert ring_percentile.read({}, params) is None
    assert ring_percentile.read(_ring_ctx(admitted),
                                {"column": "nothing", "q": 0.9}) is None
