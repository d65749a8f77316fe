"""The phi4flash family behind the benchmark's seam (this test names the
family on purpose): its configuration file against the published one and the
parameter count's arithmetic, its surface, its reference against the program
and against the control in fp8 / bf16 at the rehearsal widths, the bytes and
operations its rooflines count at hand-worked sizes, and its metrics' readers
on a hand-made context. Names here are ``phi4flash_*`` so that
``tests/test_benchmark_tracing_readers.py`` can import them beside the other
families' tests."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import phi4flash as family
from benchmarks.harness import manifest as mf
from benchmarks.harness import reference as ref
from benchmarks.harness.loadgen import RequestRecord
from benchmarks.harness.weights import load_config_file
from benchmarks.readers import engine_counters

PHI_FILE = os.path.join(mf.ROOT, "benchmarks", "configs",
                        "phi-4-mini-flash-reasoning-serve.json")
PHI_CELL = "serve_yoco_longctx"


@pytest.fixture(scope="module")
def phi4flash_setup():
    cfg = load_config_file(PHI_FILE, rehearse=True)
    config = family.program_config(cfg)
    params = family.make_weights(config, 3_000_000_019)
    tokens = np.random.default_rng(0).integers(1, 256, (2, 160), dtype=np.int32)
    return cfg, config, params, tokens


def test_phi4flash_configuration_is_the_catalog_row_key_for_key():
    """Nothing is reduced: every key of the published config is in the
    configuration file with its value, the sizes the config.json is silent
    on are under ``assumed`` with their reason, and they reproduce the
    card's parameter count layer kind by layer kind."""
    cfg = load_config_file(PHI_FILE)
    with open(os.path.join(mf.ROOT, "benchmarks", "published",
                           cfg["published"] + ".json")) as f:
        published = json.load(f)["config"]
    assert published == {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
        "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 200064}
    assert all(cfg[k] == v for k, v in published.items())
    assert cfg["reduced"] == {}
    for key in ("mamba_d_state", "mamba_d_conv", "mamba_expand",
                "mamba_dt_rank", "head_pairing", "gmu_memory", "attention_bias",
                "lambda_init", "scan_state_dtype", "torch_dtype"):
        assert cfg["assumed"][key].strip(), key
    assert family.sizes(cfg) == {"head_dim": 64, "d_inner": 5120, "d_state": 16,
                                 "d_conv": 4, "dt_rank": 160}
    config = family.program_config(cfg)
    assert config.layer_kinds == ("mamba", "window") * 8 + (
        "mamba", "full") + ("gmu", "cross") * 7
    params = jax.eval_shape(lambda k: family.init_weights(config, k),
                            jax.random.key(0))
    by_kind = {}
    for kind, lp in zip(config.layer_kinds, params["layers"]):
        by_kind.setdefault(kind, set()).add(
            sum(x.size for x in jax.tree.leaves(lp)))
    mlp, norms = 2560 * 20480 + 10240 * 2560, 4 * 2560
    mamba = 2560 * 10240 + 5 * 5120 + 5120 * 192 + 160 * 5120 + 5120 \
        + 5120 * 16 + 5120 + 5120 * 2560
    attn = 2560 * 5120 + 5120 + 2560 * 2560 + 2560 + 4 * 64 + 128
    cross = 2560 * 2560 + 2560 + 2560 * 2560 + 2560 + 4 * 64 + 128
    assert by_kind == {"mamba": {mamba + mlp + norms},
                       "window": {attn + mlp + norms},
                       "full": {attn + mlp + norms},
                       "gmu": {2 * 2560 * 5120 + mlp + norms},
                       "cross": {cross + mlp + norms}}
    count = sum(x.size for x in jax.tree.leaves(params))
    assert "lm_head" not in params  # tied
    assert abs(count - 3.853e9) < 2e6
    dep = cfg["deployment"]
    assert dep["total_pages"] == dep["num_slots"] * (
        dep["max_seq_len"] // dep["page_size"]) + 1 == 6337
    assert len(dep["prefill_buckets"]) <= 4
    assert dep["prefill_buckets"][-1] + 512 == dep["max_seq_len"]
    cache = jax.eval_shape(lambda: family._program().init_cache(
        config, dep["num_slots"], dep["total_pages"], dep["page_size"]))
    assert cache.k.shape == (10, 6337, 64, 128)            # ONE layer's pages
    assert cache.k_win.shape == (10, 8 * 25 * 9, 64, 128)
    assert cache.ssm.shape == (9, 25, 16, 40, 128) and cache.ssm.dtype == jnp.float32
    assert cache.conv.shape == (9, 25, 3, 5120)
    assert family.kv_row_bytes(cfg) == 5120 and family.page_readers(cfg) == 8


def test_phi4flash_family_gives_the_serve_surface(phi4flash_setup):
    cfg, config, params, _ = phi4flash_setup
    for name in ("program_config", "init_weights", "make_weights",
                 "reference_logits", "make_gap_fn", "make_greedy_fn",
                 "make_engine", "set_weights", "serve_programs"):
        assert callable(getattr(family, name)), name
    sized = family.serve_programs(config, cfg["deployment"])
    assert [p[0] for p in sized["programs"]] == [
        "decode", "prefill_4x64", "prefill_4x128"]
    assert set(sized["state"]._fields) == {"k", "v", "k_win", "v_win", "ssm",
                                           "conv"}
    again = family.make_weights(config, 3_000_000_019)
    assert all(bool(jnp.array_equal(a, b)) for a, b in
               zip(jax.tree.leaves(params), jax.tree.leaves(again)))


def test_phi4flash_without_the_program_fails_at_the_first_request(monkeypatch):
    """On a commit that lacks ``ray_tpu.models.phi4flash`` the replica
    starts, and its first request raises: the benchmark's command ends soon."""
    monkeypatch.setattr(family, "_program", lambda: None)
    cfg = load_config_file(PHI_FILE, rehearse=True)
    config = family.program_config(cfg)
    assert config is None and family.make_weights(config, 1) == {}
    engine = family.make_engine(config, {}, cfg["deployment"])
    assert engine.stats() == {}
    with pytest.raises(RuntimeError, match="no ray_tpu.models.phi4flash"):
        engine.generate_stream(tokens=[1], max_tokens=1)
    engine.stop()


def test_phi4flash_served_tokens_agree_with_the_reference_in_float32(phi4flash_setup):
    """Through the engine the family builds (one layer's pages, rings, scan
    state; a prompt of 120 at a window of 32 has wrapped its ring of 5 pages
    three times), in float32: every emitted token is the reference's own
    choice up to the order of float32 sums."""
    cfg, config, params, tokens = phi4flash_setup
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


def _phi4flash_served_like(cfg, params, prompt, steps, quant):
    return ref.greedy_decode(family.make_greedy_fn(cfg, quant), params, prompt,
                             steps, 96)


def test_phi4flash_control_in_fp8_is_not_correct_and_bf16_is(phi4flash_setup):
    """bf16 stands in for a sound program, fp8 is the control: the
    comparison that decides ``correct`` tells them apart."""
    cfg, _config, params, tokens = phi4flash_setup
    gap_fn = family.make_gap_fn(cfg)
    sound, control = [], []
    for row in tokens:
        prompt = row[:64].tolist()
        for quant, into in (("bf16", sound), ("fp8", control)):
            into += ref.teacher_forced_gaps(
                gap_fn, params, prompt,
                _phi4flash_served_like(cfg, params, prompt, 24, quant), 96)
    s, c = ref.summarize_gaps(sound), ref.summarize_gaps(control)
    exact = ref.teacher_forced_gaps(
        gap_fn, params, tokens[0][:64].tolist(),
        _phi4flash_served_like(cfg, params, tokens[0][:64].tolist(), 8, None), 96)
    assert max(exact) == 0.0  # the reference agrees with itself
    assert c["mean_gap"] > 3 * max(s["mean_gap"], 1e-4)


def test_phi4flash_reference_is_a_program_a_layer_kind_and_no_cache_entry(
        phi4flash_setup, monkeypatch):
    """The reference's layers of one kind are ONE jitted function called in a
    loop (five for 32 layers, ``lam0`` an argument), and it runs with the
    persistent compilation cache off, the switch left as it was found."""
    from benchmarks.families import phi4flash_reference as pr

    cfg, _config, params, tokens = phi4flash_setup
    made, seen = [], []
    layer_fn = pr.layer_fn

    def counting(kind, *a):
        made.append(kind)
        fn = layer_fn(kind, *a)

        def call(*args):
            seen.append(jax.config.jax_enable_compilation_cache)
            return fn(*args)
        return call

    monkeypatch.setattr(pr, "layer_fn", counting)
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", True)
    try:
        gaps = pr.make_gap_fn(cfg)(params, tokens[0][:32],
                                   np.zeros((32,), np.int32))
        assert jax.config.jax_enable_compilation_cache is True
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
    assert gaps.shape == (32,)
    assert sorted(made) == ["cross", "full", "gmu", "mamba", "window"]
    assert seen == [False] * cfg["num_hidden_layers"]


def test_phi4flash_bytes_and_operations_by_hand():
    cfg = load_config_file(PHI_FILE)
    # 16 reads of the shared pages (8 readers x 2 ticks) over 24 slots that
    # hold 230,000 tokens: 230,000 rows a call, 20 x 64 bf16 a side; plus 40
    # query heads of 64 in and 40 of 128 out, 24 slots
    assert family.shared_kv_decode_bytes(cfg, 16, 24, 230_000.0) == pytest.approx(
        16 * (230_000 * 5120 + 24 * 40 * (64 + 128) * 2))
    # a window layer is charged the min(length, 512) rows: 24 full windows
    # over 8 layers
    rows = 8 * 24 * 512
    assert family.window_attn_decode_bytes(cfg, 8, 24, rows) == pytest.approx(
        8 * (24 * 512 * 5120 + 24 * 40 * 192 * 2))
    # the windowed flash forward as differential attention needs it: per
    # query head and key, 2 x 64 for the score and 2 x 128 for [v1 | v2]
    seen = sum(min(i + 1, 512) for i in range(8192))
    assert family.flash_diff_fwd_flops(cfg, 1, 40, 8192, 128) \
        == 40 * (2 * 64 + 2 * 128) * seen
    assert family.flash_diff_fwd_flops(cfg, 1, 40, 300, 128) \
        == 40 * 384 * (300 * 301 // 2)
    # the scan: x and y a bfloat16, dt a float32 a channel a step, B and C a
    # state
    assert family.selective_scan_fwd_bytes(cfg, 1, 4096) \
        == 4096 * (5120 * (2 + 4 + 2) + 32 * 2)
    # one token a slot: the state in and out, 5120 x 16 float32
    assert family.selective_scan_step_bytes(cfg, 72, 24) == pytest.approx(
        72 * 24 * (2 * 5120 * 16 * 4 + 3 * 5120 * 4 + 32 * 4))


# ------------------------------------------------- the new metrics' readers
SHARED_OP = ("paged_attention.27 = bf16[24,10,4,128]{3,2,1,0:T(8,128)(2,1)S(1)} "
             "custom-call(s32[24]{0:T(128)S(6)} %copy-done.103, s32[6336]{0} %x, ")
RING_OP = ("paged_attention_window.54 = bf16[24,10,4,128]{3,2,1,0:T(8,128)(2,1)S(1)} "
           "custom-call(s32[24]{0:T(128)S(6)} %copy-done.111, s32[24]{0} %y, ")
DIFF_FLASH_OP = ("flash_window_fwd.6 = bf16[1,40,%d,128]{3,2,1,0:T(8,128)(2,1)} "
                 "custom-call(bf16[1,40,%d,128]{3,2,1,0:T(8,128)(2,1)} %%transpose.1, ")
SCAN_OP = ("selective_scan_fwd.3 = (f32[1,4096,40,128]{3,2,1,0:T(8,128)}, "
           "f32[1,16,40,128]{3,2,1,0:T(8,128)}) custom-call(f32[1,65536]{1,0} %a, ")
STEP_OP = ("selective_scan_step.9 = (f32[24,40,128]{2,1,0:T(8,128)}, "
           "f32[9,25,16,40,128]{4,3,2,1,0:T(8,128)}) custom-call(f32[24,16]{1,0} %b, ")
PHI_DECODE, PHI_PREFILL = "jit_phi4flash_decode(123)", "jit_phi4flash_prefill(456)"


@pytest.fixture
def phi4flash_ctx():
    """A hand-made context: 2 decode calls of 8 ticks (128 reads of the shared
    pages, 128 window calls, 144 one-token scan updates), two prefill calls
    (8192 and 16384 rows: 8 windowed flash calls and 18 + 36 scan pieces of
    4,096 rows), six polls a second apart around a profile called for from
    2.7 to 3.3 s and taken from 2.8 to 3.2 s, and the client's records: in
    the profile's seconds 24 requests are in flight with 9,600 tokens each
    in the cache."""
    ops = {SHARED_OP: (0.2, 128), RING_OP: (0.0128, 128),
           DIFF_FLASH_OP % (8192, 8192): (0.008, 8),
           DIFF_FLASH_OP % (16384, 16384): (0.016, 8),
           SCAN_OP: (0.108, 54), STEP_OP: (0.0072, 144)}
    trace = {"op_self_s": {k: v[0] for k, v in ops.items()},
             "op_count": {k: v[1] for k, v in ops.items()},
             "module_s": {PHI_DECODE: 0.4, PHI_PREFILL: 0.9},
             "module_count": {PHI_DECODE: 2, PHI_PREFILL: 2}}
    trace["module_whole_s"] = trace["module_s"]
    trace["module_whole_count"] = trace["module_count"]

    def poll(t):
        return (float(t), {
            "decode_steps": 100 * t, "iters": 12 * t,
            "attn_rows_shared": 100 * t * 8 * 24 * 9_600,
            "attn_rows_window": 100 * t * 8 * 24 * 512,
            "scan_slots": 100 * t * 9 * 24,
            "prefill_rows_self": 9216 * 3 * t, "prefill_rows_cross": 3 * t,
            "kv_pages_in_use": 2000 + 400 * t, "kv_pages_total": 6336})

    def request(prompt, first, tokens, finished):
        rec = RequestRecord(0, 0.0, prompt, 512, True)
        rec.arrivals = [first + 0.01 * k for k in range(tokens)]
        rec.finished = finished
        return rec

    records = [request(9_500, 1.0, 100, None) for _ in range(24)] + [
        request(16_000, 0.5, 100, 2.5), request(16_000, 3.5, 100, None)]
    return {"trace": trace, "cfg": load_config_file(PHI_FILE),
            "device_report": {"kind": "TPU v5 lite"}, "records": records,
            "marks": {"polls": [poll(t) for t in (1, 2, 3, 4, 5, 6)],
                      "open": 0.0, "close": 7.0, "trace_call": (2.7, 3.3),
                      "traced": (2.8, 3.2)}}


def _phi4flash_read(ctx, name):
    return mf.read_metric(name, ctx)


def test_phi4flash_readers_on_a_hand_made_context(phi4flash_ctx):
    cfg, peak = phi4flash_ctx["cfg"], 819e9
    assert _phi4flash_read(phi4flash_ctx, "decode_device_per_step") \
        == pytest.approx(1e3 * 0.4 / 16)
    assert _phi4flash_read(phi4flash_ctx, "prefill_device_per_call") \
        == pytest.approx(1e3 * 0.9 / 2)
    # the shared reads alone: the window calls are another kernel by name
    assert _phi4flash_read(phi4flash_ctx, "shared_kv_decode_share") \
        == pytest.approx(100 * 0.2 / 0.4)
    assert _phi4flash_read(phi4flash_ctx, "scan_prefill_share") \
        == pytest.approx(100 * 0.108 / 0.9)
    # the shared pages' roofline by the tokens in the cache in the profile's
    # own seconds, from the records: 24 x 9,600, read by every one of the
    # 128 calls (8 readers a tick)
    want = 100 * family.shared_kv_decode_bytes(cfg, 128, 24, 24 * 9_600) \
        / peak / 0.2
    assert _phi4flash_read(phi4flash_ctx, "shared_kv_decode_roofline") \
        == pytest.approx(want)
    assert 90 < want < 95
    want = 100 * family.window_attn_decode_bytes(cfg, 128, 24, 8 * 24 * 512) \
        / peak / 0.0128
    assert _phi4flash_read(phi4flash_ctx, "window_attn_decode_roofline") \
        == pytest.approx(want)
    flops = 8 * family.flash_diff_fwd_flops(cfg, 1, 40, 8192, 128) \
        + 8 * family.flash_diff_fwd_flops(cfg, 1, 40, 16384, 128)
    assert _phi4flash_read(phi4flash_ctx, "flash_diff_fwd_roofline") \
        == pytest.approx(100 * flops / 197e12 / 0.024)
    # the scan's calls carry their rows in their shape
    assert _phi4flash_read(phi4flash_ctx, "selective_scan_fwd_roofline") \
        == pytest.approx(100 * 54 * family.selective_scan_fwd_bytes(cfg, 1, 4096)
                         / peak / 0.108)
    assert _phi4flash_read(phi4flash_ctx, "selective_scan_step_roofline") \
        == pytest.approx(100 * family.selective_scan_step_bytes(cfg, 144, 24)
                         / peak / 0.0072)
    # counters and levels
    # (cross_rows_in_prefill, a constant of the layout, was retired in PR 51;
    # its two counters stay in stats() and read as before)
    assert engine_counters.read(phi4flash_ctx, {
        "plus": ["prefill_rows_cross"], "over": "prefill_rows_self",
        "scale": 100}) == pytest.approx(100 / 9216)
    assert _phi4flash_read(phi4flash_ctx, "kv_pool_fill") == pytest.approx(
        100 * (2400 + 2800 + 3600 + 4000 + 4400) / 5 / 6336)


def test_phi4flash_cells_report_what_the_manifest_says():
    manifest = mf.load_manifest()
    per_layer = {m["name"] for m in mf.metrics_for(manifest, PHI_CELL, "per_layer")}
    assert {"shared_kv_decode_roofline", "window_attn_decode_roofline",
            "selective_scan_fwd_roofline", "selective_scan_step_roofline",
            "flash_diff_fwd_roofline", "shared_kv_decode_share",
            "scan_prefill_share", "kv_pool_fill",
            "decode_device_per_step", "prefill_device_per_call",
            "ttft_mean", "ttft_p90", "peak_hbm.serve",
            "device_idle_share.serve", "compiles_in_window",
            "ingress_overhead_p50", "client_to_engine_p50",
            "first_token_return_p50", "admit_burst_p90"} <= per_layer
    # another family's counts would charge rows this one does not read
    assert not {"paged_attn_roofline", "full_attn_decode_roofline",
                "decode_device_per_step.chat", "latent_attn_decode_roofline",
                "moe_decode_share.laguna"} & per_layer
    assert {m["name"] for m in mf.metrics_for(manifest, PHI_CELL, "end_to_end")} \
        == {"serve_tokens_per_s", "tpot_p50", "setup_s"}
    with open(os.path.join(mf.ROOT, "benchmarks", "traffic",
                           "history_closed.json")) as f:
        traffic = json.load(f)
    p = traffic["params"]
    assert (p["callers"], p["cycle"], p["output_tokens"], p["ramp_seconds"],
            p["drain_limit_s"]) == (24, 48, 512, 12, 60)
    assert p["prompt"] == {"min": 2048, "max": 16384}
    assert traffic["check"]["length"] == 16384 + 512
    assert set(traffic["check"]["limits"]) == {
        "mean_gap", "mismatch_share", "first_token_max_gap"}
    # the second cell: the chat configuration's programs at capacity
    sat = {m["name"] for m in mf.metrics_for(manifest, "serve_chat_sat", "per_layer")}
    docs = {m["name"] for m in mf.metrics_for(manifest, "serve_longprompt", "per_layer")}
    assert sat == docs - {"paged_attn_roofline"}
    assert {m["name"] for m in mf.metrics_for(manifest, "serve_chat_sat",
                                              "end_to_end")} \
        == {"serve_tokens_per_s", "setup_s"}
    with open(os.path.join(mf.ROOT, "benchmarks", "traffic",
                           "chat_closed.json")) as f:
        chat = json.load(f)
    p = chat["params"]
    assert (p["callers"], p["cycle"], p["output_tokens"], p["ramp_seconds"],
            p["drain_limit_s"]) == (64, 128, 112, 12, 60)
    assert p["prompt"] == {"min": 32, "max": 672}
    with open(os.path.join(mf.ROOT, "benchmarks", "traffic",
                           "docs_batch.json")) as f:
        assert chat["check"]["limits"] == json.load(f)["check"]["limits"]


def test_phi4flash_readers_read_nothing_from_a_program_without_the_family(
        phi4flash_ctx):
    """The parent commit's trace has no such program, scan or counter: every
    reader of a new metric returns None and raises nothing (the shared-page
    roofline would match another family's ``paged_attention`` by name: it is
    listed in this cell alone, which the parent cannot run)."""
    bare = {"trace": {"op_self_s": {"fusion.1 = bf16[64,4096]{1,0} fusion(": 1.0},
                      "op_count": {"fusion.1 = bf16[64,4096]{1,0} fusion(": 3},
                      "module_s": {"jit_paged_decode_steps(1)": 2.0},
                      "module_count": {"jit_paged_decode_steps(1)": 4}},
            "cfg": phi4flash_ctx["cfg"], "device_report": {"kind": "TPU v5 lite"},
            "marks": {"open": 0.0, "close": 9.0, "polls": [
                (t, {"decode_steps": 10 * t, "iters": t}) for t in (1.0, 2.0, 3.0)]}}
    manifest = mf.load_manifest()
    names = [m["name"] for m in manifest["per_layer"]
             if m.get("workloads") == [PHI_CELL]
             and not m["name"].startswith("ttft_")]
    # and what this family reads under a name it shares since PR 51
    names += ["decode_device_per_step", "prefill_device_per_call",
              "kv_pool_fill", "window_attn_decode_roofline"]
    assert len(names) == 10
    for name in names:
        assert _phi4flash_read(bare, name) is None, name
        assert _phi4flash_read({"cfg": bare["cfg"]}, name) is None, name
