"""The Laguna family behind the benchmark's seam (this test names the family
on purpose): its configuration file against the published one and the cut's
arithmetic, its surface, its reference against the program and against the
control in fp8 / bf16 at the rehearsal widths, the bytes and operations its
rooflines count, and its metrics' readers on a hand-made context. Names here
are ``laguna_*`` so that ``tests/test_benchmark_tracing_readers.py`` can import
them beside the other families' tests."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import laguna as family
from benchmarks.harness import manifest as mf
from benchmarks.harness import reference as ref
from benchmarks.harness.loadgen import RequestRecord
from benchmarks.harness.weights import load_config_file

LAGUNA_FILE = os.path.join(mf.ROOT, "benchmarks", "configs",
                           "laguna-xs.2-serve.json")
CELL = "serve_window_longctx"


@pytest.fixture(scope="module")
def laguna_setup():
    cfg = load_config_file(LAGUNA_FILE, rehearse=True)
    cfg = {**cfg, "torch_dtype": "float32"}
    config = family.program_config(cfg)
    params = family.make_weights(config, 3_000_000_019)
    tokens = np.random.default_rng(0).integers(1, 256, (2, 160), dtype=np.int32)
    return cfg, config, params, tokens


def test_laguna_configuration_keeps_every_published_width():
    cfg = load_config_file(LAGUNA_FILE)
    with open(os.path.join(mf.ROOT, "benchmarks", "published",
                           cfg["published"] + ".json")) as f:
        published = json.load(f)["config"]
    cut = {k for k, v in published.items() if cfg[k] != v}
    assert cut == {"num_hidden_layers", "layer_types", "mlp_layer_types",
                   "num_attention_heads_per_layer", "num_experts",
                   "vocab_size"} == set(cfg["reduced"])
    # the lists are cut with the depth and nothing in them is reordered: the
    # leading dense layer and two whole periods S S S F
    for key in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer"):
        assert cfg[key] == published[key][:9] and len(cfg[key]) == 9
    assert cfg["num_hidden_layers"] == 9
    assert cfg["layer_types"].count("full_attention") == 3
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 8
    assert cfg["num_attention_heads_per_layer"] == [48, 64, 64, 64] * 2 + [48]
    # no width moved
    for key, width in (("hidden_size", 2048), ("head_dim", 128),
                       ("num_key_value_heads", 8), ("intermediate_size", 8192),
                       ("moe_intermediate_size", 512),
                       ("shared_expert_intermediate_size", 512),
                       ("num_experts_per_tok", 8), ("sliding_window", 512),
                       ("moe_routed_scaling_factor", 2.5)):
        assert cfg[key] == published[key] == width
    assert cfg["rope_parameters"] == published["rope_parameters"]
    # the share and the floors: the router keeps its width, an eighth is held
    assert cfg["n_router_outputs"] == published["num_experts"] == 256
    assert cfg["held_experts"] == [0, 32] and cfg["num_experts"] == 32 >= 8
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert cfg["share"]["chips_sharing_a_layer"] == 8
    # ISSUE 35's table, part by part
    config = family.program_config(cfg)
    params = jax.eval_shape(lambda k: family.init_weights(config, k),
                            jax.random.key(0))
    sizes = [sum(x.size for x in jax.tree.leaves(lp)) for lp in params["layers"]]
    full, sliding, dense, sparse = 29_462_528, 37_883_904, 50_331_648, 104_333_312
    assert sizes[0] == full + dense
    assert sizes[1] == sizes[2] == sizes[3] == sliding + sparse
    assert sizes[4] == sizes[8] == full + sparse
    count = sum(x.size for x in jax.tree.leaves(params))
    assert count == sum(sizes) + 2 * 12544 * 2048 + 2048
    assert abs(count - 1.252e9) < 1e6
    dep = cfg["deployment"]
    assert dep["total_pages"] == dep["num_slots"] * (
        dep["max_seq_len"] // dep["page_size"]) + 1 == 9601
    cache = jax.eval_shape(lambda: family._program().init_cache(
        config, dep["num_slots"], dep["total_pages"], dep["page_size"]))
    assert cache.k.shape == (8, 3 * 9601, 64, 128)
    assert cache.k_win.shape == (8, 6 * 25 * 9, 64, 128)


def test_laguna_family_gives_the_serve_surface(laguna_setup):
    cfg, config, params, _ = laguna_setup
    for name in ("program_config", "init_weights", "make_weights",
                 "reference_logits", "make_gap_fn", "make_greedy_fn",
                 "make_engine", "set_weights", "serve_programs"):
        assert callable(getattr(family, name)), name
    sized = family.serve_programs(config, cfg["deployment"])
    assert [p[0] for p in sized["programs"]] == [
        "decode", "prefill_4x64", "prefill_4x128"]
    assert set(sized["state"]._fields) == {"k", "v", "k_win", "v_win"}
    again = family.make_weights(config, 3_000_000_019)
    assert all(bool(jnp.array_equal(a, b)) for a, b in
               zip(jax.tree.leaves(params), jax.tree.leaves(again)))


def test_laguna_without_the_program_fails_at_the_first_request(monkeypatch):
    """On a commit that lacks ``ray_tpu.models.laguna`` the replica starts,
    and its first request raises: the benchmark's command ends soon."""
    monkeypatch.setattr(family, "_program", lambda: None)
    cfg = load_config_file(LAGUNA_FILE, rehearse=True)
    config = family.program_config(cfg)
    assert config is None and family.make_weights(config, 1) == {}
    engine = family.make_engine(config, {}, cfg["deployment"])
    assert engine.stats() == {}
    with pytest.raises(RuntimeError, match="no ray_tpu.models.laguna"):
        engine.generate_stream(tokens=[1], max_tokens=1)
    engine.stop()


def test_laguna_served_tokens_agree_with_the_reference_in_float32(laguna_setup):
    """Through the engine the family builds (pages and rings; a prompt of
    120 at a window of 32 has wrapped its ring of 5 pages three times), in
    float32: every emitted token is the reference's own choice up to the
    order of float32 sums."""
    cfg, config, params, tokens = laguna_setup
    engine = family.make_engine(config, params, cfg["deployment"])
    try:
        prompt = tokens[0][:120].tolist()
        out = engine.generate(tokens=prompt, max_tokens=40, eos_token=None,
                              timeout=600)["tokens"]
    finally:
        engine.stop()
    gaps = ref.teacher_forced_gaps(family.make_gap_fn(cfg), params, prompt,
                                   out, 160)
    assert len(out) == 40 and max(gaps) < 1e-4


def _laguna_served_like(cfg, params, prompt, steps, quant):
    return ref.greedy_decode(family.make_greedy_fn(cfg, quant), params, prompt,
                             steps, 96)


def test_laguna_control_in_fp8_is_not_correct_and_bf16_is(laguna_setup):
    """bf16 stands in for a sound program, fp8 is the control: the
    comparison that decides ``correct`` tells them apart."""
    cfg, _config, params, tokens = laguna_setup
    gap_fn = family.make_gap_fn(cfg)
    sound, control = [], []
    for row in tokens:
        prompt = row[:64].tolist()
        for quant, into in (("bf16", sound), ("fp8", control)):
            into += ref.teacher_forced_gaps(
                gap_fn, params, prompt,
                _laguna_served_like(cfg, params, prompt, 24, quant), 96)
    s, c = ref.summarize_gaps(sound), ref.summarize_gaps(control)
    exact = ref.teacher_forced_gaps(
        gap_fn, params, tokens[0][:64].tolist(),
        _laguna_served_like(cfg, params, tokens[0][:64].tolist(), 8, None), 96)
    assert max(exact) == 0.0  # the reference agrees with itself
    assert c["mean_gap"] > 3 * max(s["mean_gap"], 1e-4)


@pytest.mark.parametrize("make", ["make_gap_fn", "make_greedy_fn"])
def test_laguna_reference_stays_out_of_the_compile_cache(make, monkeypatch):
    """The reference is compiled with the persistent cache off (138 MB at
    the check's length would push the cell's own programs out of the chip
    machine's 192 MiB), and the switch is left as it was found, also where
    the call raises."""
    from benchmarks.families import laguna_reference as lr

    seen = []

    def fake(_fn):
        def jitted(*args):
            seen.append(jax.config.jax_enable_compilation_cache)
            if args[0] == "raise":
                raise ValueError("from the reference")
            return args
        return jitted

    monkeypatch.setattr(lr, "gap_fn_of", fake)
    monkeypatch.setattr(lr, "greedy_fn_of", fake)
    was_on = jax.config.jax_enable_compilation_cache
    fn = getattr(lr, make)({})
    assert fn(1, 2, 3) == (1, 2, 3)
    with pytest.raises(ValueError):
        fn("raise")
    assert seen == [False, False]
    assert jax.config.jax_enable_compilation_cache == was_on


def test_laguna_bytes_and_operations_by_hand():
    cfg = load_config_file(LAGUNA_FILE)
    # 10 calls of a full layer over 24 slots that hold 30,000 tokens: 30,000
    # rows a call, each 8 x 128 bf16 a side, plus q and the output of 24
    # slots x 48 heads
    assert family.full_attn_decode_bytes(cfg, 10, 24, 30_000.0) == pytest.approx(
        10 * (30_000 * 2 * 2048 + 2 * 24 * 48 * 128 * 2))
    # a sliding layer is charged the min(length, 512) rows, not the ring's 9
    # pages: 24 full windows over 6 layers, 64 heads
    rows = 6 * 24 * 512
    assert family.window_attn_decode_bytes(cfg, 6, 24, rows) == pytest.approx(
        6 * (24 * 512 * 2 * 2048 + 2 * 24 * 64 * 128 * 2))
    # the windowed flash forward: query i sees min(i + 1, 512) keys
    seen = sum(min(i + 1, 512) for i in range(8192))
    assert family.flash_window_fwd_flops(cfg, 1, 64, 8192, 128) \
        == 4 * 64 * 128 * seen
    assert family.flash_window_fwd_flops(cfg, 1, 64, 300, 128) \
        == 4 * 64 * 128 * (300 * 301 // 2)


# ------------------------------------------------- the new metrics' readers
FULL_OP = ("paged_attention.27 = bf16[24,8,6,128]{3,2,1,0:T(8,128)(2,1)S(1)} "
           "custom-call(s32[24]{0:T(128)S(6)} %copy-done.103, s32[9600]{0} %x, ")
WINDOW_OP = ("paged_attention_window.54 = bf16[24,8,8,128]{3,2,1,0:T(8,128)(2,1)S(1)} "
             "custom-call(s32[24]{0:T(128)S(6)} %copy-done.111, s32[24]{0} %y, ")
FLASH_OP = ("flash_window_fwd.6 = bf16[1,64,%d,128]{3,2,1,0:T(8,128)(2,1)} "
            "custom-call(bf16[1,64,%d,128]{3,2,1,0:T(8,128)(2,1)} %%transpose.1, ")
FULL_FLASH_OP = ("laguna_prefill.3 = bf16[1,48,8192,128]{3,2,1,0:T(8,128)(2,1)} "
                 "custom-call(bf16[1,48,8192,128]{3,2,1,0} %transpose.9, ")
MOE_OP = ("ragged-dot-none.7 = f32[192,512]{1,0:T(8,128)S(1)} custom-call("
          "bf16[192,2048]{1,0} %fusion.1546, bf16[32,2048,512]{2,1,0} %param.9, ")
PREFILL_MOE_OP = ("ragged-dot-none.1 = f32[32768,512]{1,0:T(8,128)S(1)} custom-call("
                  "bf16[32768,2048]{1,0} %fusion.766, bf16[32,2048,512]{2,1,0} %p, ")
DECODE, PREFILL = "jit_laguna_decode_steps(123)", "jit_laguna_prefill(456)"


@pytest.fixture
def laguna_ctx():
    """A hand-made context: 2 decode calls of 8 ticks (48 full and 96 window
    kernel calls, 128 grouped expert products of the 24 x 8 routed rows; the
    prefill's, of 32,768 rows, are not decode's), two prefill calls (8192 and 16384),
    six polls a second apart around a profile called for from 2.7 to 3.3 s
    and taken from 2.8 to 3.2 s, and the client's records: in the profile's
    seconds 24 requests are in flight with 12,000 tokens each in the cache,
    where the engine's counter read 14,000 rows a slot before it."""
    ops = {FULL_OP: (0.13, 48), WINDOW_OP: (0.0096, 96),
           FLASH_OP % (8192, 8192): (0.012, 6), FLASH_OP % (16384, 16384): (0.024, 6),
           FULL_FLASH_OP: (0.05, 3), MOE_OP: (0.032, 128),
           PREFILL_MOE_OP: (0.1, 36)}
    trace = {"op_self_s": {k: v[0] for k, v in ops.items()},
             "op_count": {k: v[1] for k, v in ops.items()},
             "module_s": {DECODE: 0.4, PREFILL: 0.6},
             "module_count": {DECODE: 2, PREFILL: 2}}
    trace["module_whole_s"] = trace["module_s"]
    trace["module_whole_count"] = trace["module_count"]

    def poll(t):
        return (float(t), {
            "decode_steps": 100 * t, "iters": 12 * t,
            "attn_rows_full": 100 * t * 3 * 24 * 14_000,
            "attn_rows_window": 100 * t * 6 * 24 * 512,
            "moe_assignments": 100 * t * 8 * 24 * 8,
            "moe_assignments_held": 100 * t * 8 * 24,
            "moe_expert_load_max": 100 * t * 8 * 2,
            "kv_pages_in_use": 4000 + 800 * t, "kv_pages_total": 9600})

    def request(prompt, first, tokens, finished):
        rec = RequestRecord(0, 0.0, prompt, 1024, True)
        rec.arrivals = [first + 0.01 * k for k in range(tokens)]
        rec.finished = finished
        return rec

    # in flight through the profile; one that ended before it, one whose
    # first token comes after it
    records = [request(11_900, 1.0, 100, None) for _ in range(24)] + [
        request(20_000, 0.5, 100, 2.5), request(20_000, 3.5, 100, None)]
    return {"trace": trace, "cfg": load_config_file(LAGUNA_FILE),
            "device_report": {"kind": "TPU v5 lite"}, "records": records,
            "marks": {"polls": [poll(t) for t in (1, 2, 3, 4, 5, 6)],
                      "open": 0.0, "close": 7.0, "trace_call": (2.7, 3.3),
                      "traced": (2.8, 3.2)}}


def _laguna_read(ctx, name):
    return mf.read_metric(name, ctx)


def test_laguna_readers_on_a_hand_made_context(laguna_ctx):
    cfg, peak = laguna_ctx["cfg"], 819e9
    assert _laguna_read(laguna_ctx, "decode_device_per_step") \
        == pytest.approx(1e3 * 0.4 / 16)
    assert _laguna_read(laguna_ctx, "prefill_device_per_call") \
        == pytest.approx(1e3 * 0.6 / 2)
    # both kernels are attention; the share tells neither from the other
    assert _laguna_read(laguna_ctx, "attn_decode_share") \
        == pytest.approx(100 * (0.13 + 0.0096) / 0.4)
    assert _laguna_read(laguna_ctx, "moe_decode_share.laguna") \
        == pytest.approx(100 * 0.032 / 0.4)
    # the rooflines: each kernel by its own name; the full layers' by the
    # tokens in the cache in the profile's own seconds, from the records
    want = 100 * family.full_attn_decode_bytes(cfg, 48, 24, 24 * 12_000) \
        / peak / 0.13
    assert _laguna_read(laguna_ctx, "full_attn_decode_roofline") \
        == pytest.approx(want)
    assert 47 < want < 56
    for lacks in ("traced", "records"):
        bare = {k: v for k, v in laguna_ctx.items() if k != lacks}
        bare["marks"] = {k: v for k, v in laguna_ctx["marks"].items()
                         if k != lacks}
        assert _laguna_read(bare, "full_attn_decode_roofline") is None
    want = 100 * family.window_attn_decode_bytes(cfg, 96, 24, 6 * 24 * 512) \
        / peak / 0.0096
    assert _laguna_read(laguna_ctx, "window_attn_decode_roofline") \
        == pytest.approx(want)
    # the flash roofline takes each call's sequence from its shape, and not
    # the full-attention calls of the same program
    flops = 6 * family.flash_window_fwd_flops(cfg, 1, 64, 8192, 128) \
        + 6 * family.flash_window_fwd_flops(cfg, 1, 64, 16384, 128)
    assert _laguna_read(laguna_ctx, "flash_window_fwd_roofline") \
        == pytest.approx(100 * flops / 197e12 / 0.036)
    # counters and levels
    assert _laguna_read(laguna_ctx, "kv_pool_fill") == pytest.approx(
        100 * (4800 + 5600 + 7200 + 8000 + 8800) / 5 / 9600)
    assert _laguna_read(laguna_ctx, "held_assignment_share") \
        == pytest.approx(12.5)
    assert _laguna_read(laguna_ctx, "expert_load_max_over_mean") \
        == pytest.approx(32 * 2 / 24)


def test_laguna_cell_reports_what_the_manifest_says():
    manifest = mf.load_manifest()
    per_layer = {m["name"] for m in mf.metrics_for(manifest, CELL, "per_layer")}
    assert {"full_attn_decode_roofline", "window_attn_decode_roofline",
            "flash_window_fwd_roofline", "attn_decode_share", "kv_pool_fill",
            "peak_hbm.serve", "device_idle_share.serve",
            # the way to the first token: proxy, router, replica, admission
            "ttft_p90", "ttft_mean", "compiles_in_window",
            "decode_device_per_step", "prefill_device_per_call",
            "ingress_overhead_p50", "client_to_engine_p50",
            "first_token_return_p50", "admit_burst_p90"} <= per_layer
    # stale counts that would charge a window layer for rows it does not read
    assert not {"paged_attn_roofline", "decode_device_per_step.chat",
                "prefill_device_per_call.chat",
                "shared_kv_decode_roofline"} & per_layer
    assert {m["name"] for m in mf.metrics_for(manifest, CELL, "end_to_end")} \
        == {"serve_tokens_per_s", "tpot_p50", "setup_s"}
    with open(os.path.join(mf.ROOT, "benchmarks", "traffic",
                           "longctx_closed.json")) as f:
        traffic = json.load(f)
    p = traffic["params"]
    assert (p["callers"], p["cycle"], p["output_tokens"], p["ramp_seconds"]) \
        == (24, 48, 1024, 12)
    assert p["prompt"] == {"min": 4096, "max": 24576}
    assert traffic["check"]["length"] >= 24576 + 1024


def test_laguna_readers_read_nothing_from_a_program_without_the_family(laguna_ctx):
    """The parent commit's trace has no such program, operation or counter:
    every new reader returns None and raises nothing."""
    bare = {"trace": {"op_self_s": {"paged_attention.1 = bf16[64,8,4,128]{3,2,1,0} custom-call(": 1.0},
                      "op_count": {"paged_attention.1 = bf16[64,8,4,128]{3,2,1,0} custom-call(": 3},
                      "module_s": {"jit_paged_decode_steps(1)": 2.0},
                      "module_count": {"jit_paged_decode_steps(1)": 4}},
            "cfg": laguna_ctx["cfg"], "device_report": {"kind": "TPU v5 lite"},
            "marks": {"open": 0.0, "close": 9.0, "polls": [
                (t, {"decode_steps": 10 * t, "iters": t}) for t in (1.0, 2.0, 3.0)]}}
    names = [n for n in os.listdir(os.path.join(mf.ROOT, "benchmarks", "metrics"))
             if "laguna" in n] + [
        "full_attn_decode_roofline.json", "window_attn_decode_roofline.json",
        "flash_window_fwd_roofline.json", "attn_decode_share.json",
        "kv_pool_fill.json", "decode_device_per_step.json",
        "prefill_device_per_call.json", "held_assignment_share.json",
        "expert_load_max_over_mean.json"]
    assert len(names) == 10
    for name in names:
        assert _laguna_read(bare, name[:-5]) is None, name
        assert _laguna_read({"cfg": bare["cfg"]}, name[:-5]) is None, name
