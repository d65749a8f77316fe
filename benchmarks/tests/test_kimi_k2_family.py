"""The kimi_k2 family behind the benchmark's seam (this test names the family
on purpose): its configuration file against the published one and the
parameter count's arithmetic, its surface, its reference against the program
and against the control in fp8 / bf16 at the rehearsal widths, the bytes and
operations its rooflines count at hand-worked sizes, and its metrics' readers
on a hand-made context. Names here are ``kimi_k2_*`` so that
``tests/test_benchmark_tracing_readers.py`` can import them beside the other
families' tests."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import kimi_k2 as family
from benchmarks.harness import manifest as mf
from benchmarks.harness import reference as ref
from benchmarks.harness.weights import load_config_file
from benchmarks.readers import poll_level

KIMI_FILE = os.path.join(mf.ROOT, "benchmarks", "configs",
                         "kimi-k2.6-serve.json")
KIMI_CELL = "serve_mla_longdoc"


@pytest.fixture(scope="module")
def kimi_k2_setup():
    cfg = load_config_file(KIMI_FILE, rehearse=True)
    config = family.program_config(cfg)
    params = family.make_weights(config, 3_000_000_019)
    tokens = np.random.default_rng(0).integers(1, 256, (2, 160), dtype=np.int32)
    return cfg, config, params, tokens


def test_kimi_k2_configuration_is_the_catalog_row_but_for_the_share():
    """Every key of the published config is in the configuration file with
    its value but the three ``reduced`` lists, each with the published count
    beside it; the share and the sizes the config.json is silent on are
    stated; and the widths reproduce the issue's parameter arithmetic."""
    cfg = load_config_file(KIMI_FILE)
    with open(os.path.join(mf.ROOT, "benchmarks", "published",
                           cfg["published"] + ".json")) as f:
        published = json.load(f)["config"]
    assert published["model_type"] == "kimi_k2"
    assert (published["kv_lora_rank"], published["qk_rope_head_dim"],
            published["qk_nope_head_dim"], published["v_head_dim"],
            published["q_lora_rank"], published["rms_norm_eps"]) \
        == (512, 64, 128, 128, 1536, 1e-05)
    assert published["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    changed = {k for k, v in published.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (6, 12, 20480)
    assert cfg["share"]["published"] == {
        "n_routed_experts": 384, "vocab_size": 163840, "num_hidden_layers": 61}
    assert cfg["share"]["chips_sharing_a_layer"] == 32
    assert cfg["n_router_outputs"] == 384 and cfg["held_experts"] == [0, 12]
    # the floors: a whole period and >= 4 layers after the dense one, >= 8
    # experts, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8 and cfg["vocab_size"] * 8 >= 163840
    for key in ("rotary_layout", "yarn", "router_dtype", "stored_row",
                "e_score_correction_bias", "routed_expert_out_scale",
                "torch_dtype", "eos", "vision_tower"):
        assert cfg["assumed"][key].strip(), key
    config = family.program_config(cfg)
    assert abs(config.softmax_scale - 0.14468) < 1e-5
    params = jax.eval_shape(lambda k: family.init_weights(config, k),
                            jax.random.key(0))

    def count(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    attn = 7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256 \
        + 8192 * 7168
    assert attn == 11_010_048 + 18_874_368 + 4_128_768 + 8_388_608 + 58_720_256
    norms = 2 * 7168 + 1536 + 512
    expert = 3 * 7168 * 2048
    dense = params["dense_layers"][0]
    assert count(dense) == attn + norms + 3 * 7168 * 18432
    layers = params["layers"]
    assert count(layers) == 5 * (
        attn + norms + 7168 * 384 + 384 + 12 * expert + expert)
    assert layers["router"]["w"].dtype == layers["router"]["bias"].dtype \
        == jnp.float32
    assert layers["experts"]["w_up"].shape == (5, 12, 7168, 2048)
    total = count(params)
    assert total == count(dense) + count(layers) + 2 * 20480 * 7168 + 7168
    assert abs(total - 4.173e9) < 2e6
    dep = cfg["deployment"]
    assert dep["total_pages"] == dep["num_slots"] * (
        dep["max_seq_len"] // dep["page_size"]) + 1 == 6273
    assert dep["prefill_buckets"] == [24576]  # ONE prefill program
    assert dep["prefill_buckets"][-1] + 512 == dep["max_seq_len"]
    cache = jax.eval_shape(lambda: family._program().init_cache(
        config, dep["num_slots"], dep["total_pages"], dep["page_size"]))
    assert cache._fields == ("k",) and cache.k.shape == (1, 6 * 6273, 64, 640)
    assert family.latent_row_bytes(cfg) == 1152


def test_kimi_k2_family_gives_the_serve_surface(kimi_k2_setup):
    cfg, config, params, _ = kimi_k2_setup
    for name in ("program_config", "init_weights", "make_weights",
                 "reference_logits", "make_gap_fn", "make_greedy_fn",
                 "make_engine", "set_weights", "serve_programs"):
        assert callable(getattr(family, name)), name
    sized = family.serve_programs(config, cfg["deployment"])
    assert [p[0] for p in sized["programs"]] == [
        "decode", "prefill_4x64", "prefill_4x128"]
    assert set(sized["state"]._fields) == {"k"}
    again = family.make_weights(config, 3_000_000_019)
    assert all(bool(jnp.array_equal(a, b)) for a, b in
               zip(jax.tree.leaves(params), jax.tree.leaves(again)))


def test_kimi_k2_without_the_program_fails_at_the_first_request(monkeypatch):
    """On a commit that lacks ``ray_tpu.models.kimi_k2`` the replica starts,
    and its first request raises: the benchmark's command ends soon."""
    monkeypatch.setattr(family, "_program", lambda: None)
    cfg = load_config_file(KIMI_FILE, rehearse=True)
    config = family.program_config(cfg)
    assert config is None and family.make_weights(config, 1) == {}
    engine = family.make_engine(config, {}, cfg["deployment"])
    assert engine.stats() == {}
    with pytest.raises(RuntimeError, match="no ray_tpu.models.kimi_k2"):
        engine.generate_stream(tokens=[1], max_tokens=1)
    engine.stop()


def test_kimi_k2_served_tokens_agree_with_the_reference_in_float32(kimi_k2_setup):
    """Through the engine the family builds (unabsorbed prefill, absorbed
    decode, one latent pool), in float32: every emitted token is the
    reference's own choice up to the order of float32 sums."""
    cfg, config, params, tokens = kimi_k2_setup
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


def test_kimi_k2_control_in_fp8_is_not_correct_and_bf16_is(kimi_k2_setup):
    """bf16 stands in for a sound program, fp8 is the control: the
    comparison that decides ``correct`` tells them apart."""
    cfg, _config, params, tokens = kimi_k2_setup
    gap_fn = family.make_gap_fn(cfg)

    def served_like(prompt, steps, quant):
        return ref.greedy_decode(family.make_greedy_fn(cfg, quant), params,
                                 prompt, steps, 96)

    sound, control = [], []
    for row in tokens:
        prompt = row[:64].tolist()
        for quant, into in (("bf16", sound), ("fp8", control)):
            into += ref.teacher_forced_gaps(
                gap_fn, params, prompt, served_like(prompt, 24, quant), 96)
    s, c = ref.summarize_gaps(sound), ref.summarize_gaps(control)
    first = tokens[0][:64].tolist()
    exact = ref.teacher_forced_gaps(gap_fn, params, first,
                                    served_like(first, 8, None), 96)
    assert max(exact) == 0.0  # the reference agrees with itself
    assert c["mean_gap"] > 3 * max(s["mean_gap"], 1e-4)


def test_kimi_k2_bytes_and_operations_by_hand():
    cfg = load_config_file(KIMI_FILE)
    # 48 calls (6 layers x 8 ticks) over 16 slots that hold 265,600 tokens: a
    # tick attends 6 x 265,600 rows, a call 265,600, each 576 bf16 read ONCE;
    # plus 64 heads' queries of 576 in and outputs of 512 out, 16 slots
    assert family.latent_attn_decode_bytes(cfg, 48, 16, 6 * 265_600.0) \
        == pytest.approx(48 * (265_600 * 1152 + 16 * 64 * (576 + 512) * 2))
    # a row costs 64 heads a dot of 576 and a row of 512: 139,264, 121 a byte
    assert family.latent_attn_decode_flops(cfg, 1.0) == 139_264
    assert family.latent_attn_decode_flops(cfg, 1.0) / 1152 == pytest.approx(
        120.9, abs=0.1)
    # the unabsorbed prefill: a kernel call of 16 heads over a prompt of
    # 16,384 (134,225,920 causal pairs), whatever bucket it was padded to
    pairs = 16384 * 16385 // 2
    assert family.flash_mla_fwd_flops(cfg, pairs, 1, 16, 24576, 128) \
        == pairs * 2 * 16 * (192 + 128)
    # 4 such calls a layer are the issue's 64 x (192 + 128) x 2 a pair
    assert 4 * family.flash_mla_fwd_flops(cfg, 1.0, 1, 16, 8192, 128) \
        == 64 * 320 * 2


# ------------------------------------------------- the new metrics' readers
LATENT_OP = ("paged_attention_latent.7 = bf16[16,1,64,512]{3,2,1,0:T(8,128)(2,1)} "
             "custom-call(s32[16]{0:T(128)S(6)} %copy-done.3, s32[6272]{0} %x, ")
MLA_FLASH_OP = ("flash_mla_fwd.%d = bf16[1,16,24576,128]{3,2,1,0:T(8,128)(2,1)} "
                "custom-call(s32[1]{0} %%p, bf16[1,16,24576,192]{3,2,1,0:T(8,128)(2,1)} "
                "%%transpose.1, ")
KIMI_DECODE, KIMI_PREFILL = "jit_kimi_k2_decode(123)", "jit_kimi_k2_prefill(456)"


@pytest.fixture
def kimi_k2_ctx():
    """A hand-made context: 2 decode calls of 8 ticks (96 calls of the latent
    kernel), two prefill calls of the one bucket (48 calls of the flash
    kernel, 4 head groups x 6 layers each: 8 in the dense layer's copy of the
    kernel, 40 in the scanned expert layers'), six polls a second apart
    around a profile called for from 2.7 to 3.3 s. Between polls the engine
    runs 100 ticks over 16 slots of 16,600 rows and 2 prefills of 150 M
    causal pairs each."""
    ops = {LATENT_OP: (0.06, 96),
           MLA_FLASH_OP % 8: (0.1, 8), MLA_FLASH_OP % 9: (0.6, 40)}
    trace = {"op_self_s": {k: v[0] for k, v in ops.items()},
             "op_count": {k: v[1] for k, v in ops.items()},
             "module_s": {KIMI_DECODE: 0.2, KIMI_PREFILL: 1.6},
             "module_count": {KIMI_DECODE: 2, KIMI_PREFILL: 2}}
    trace["module_whole_s"] = trace["module_s"]
    trace["module_whole_count"] = trace["module_count"]

    def poll(t):
        return (float(t), {
            "decode_steps": 100 * t, "iters": 12 * t,
            "attn_rows_latent": 100 * t * 6 * 16 * 16_600,
            "prefill_calls": 2 * t, "prefill_attn_pairs": 2 * t * 150_000_000,
            "prefill_rows": 2 * t * 17_320,
            "moe_assignments": 100 * t * 5 * 16 * 8,
            "moe_assignments_held": 100 * t * 20, "moe_expert_load_max": 100 * t * 5,
            "kv_bytes_per_token": 7680,
            "kv_pages_in_use": 2000 + 400 * t, "kv_pages_total": 6272})

    return {"trace": trace, "cfg": load_config_file(KIMI_FILE),
            "device_report": {"kind": "TPU v5 lite"},
            "marks": {"polls": [poll(t) for t in (1, 2, 3, 4, 5, 6)],
                      "open": 0.0, "close": 7.0, "trace_call": (2.7, 3.3),
                      "traced": (2.8, 3.2)}}


def _kimi_k2_read(ctx, name):
    return mf.read_metric(name, ctx)


def test_kimi_k2_readers_on_a_hand_made_context(kimi_k2_ctx):
    cfg = kimi_k2_ctx["cfg"]
    assert _kimi_k2_read(kimi_k2_ctx, "decode_device_per_step") \
        == pytest.approx(1e3 * 0.2 / 16)
    assert _kimi_k2_read(kimi_k2_ctx, "prefill_device_per_call") \
        == pytest.approx(1e3 * 1.6 / 2)
    assert _kimi_k2_read(kimi_k2_ctx, "latent_attn_decode_share") \
        == pytest.approx(100 * 0.06 / 0.2)
    assert _kimi_k2_read(kimi_k2_ctx, "mla_prefill_attn_share") \
        == pytest.approx(100 * 0.7 / 1.6)
    # the latent kernel's roofline by the program's OWN count of the rows it
    # attended a tick (6 layers x 16 x 16,600), each 1,152 B read once
    want = 100 * family.latent_attn_decode_bytes(cfg, 96, 16, 6 * 16 * 16_600) \
        / 819e9 / 0.06
    assert _kimi_k2_read(kimi_k2_ctx, "latent_attn_decode_roofline") \
        == pytest.approx(want)
    assert 55 < want < 65
    # the prefill kernel's by the program's own pairs a prefill call (150 M),
    # not by the bucket the calls were padded to
    flops = 48 * family.flash_mla_fwd_flops(cfg, 150e6, 1, 16, 0, 128)
    assert flops == 2 * 6 * 150e6 * 64 * 320 * 2
    assert _kimi_k2_read(kimi_k2_ctx, "flash_mla_fwd_roofline") \
        == pytest.approx(100 * flops / 197e12 / 0.7)
    # counters and levels
    # (latent_bytes_per_token, a constant of the layout, was retired in PR 51;
    # the level stays in stats() and reads as before)
    assert poll_level.read(kimi_k2_ctx, {
        "key": "kv_bytes_per_token", "scale": 1 / 6}) == pytest.approx(1280.0)
    assert _kimi_k2_read(kimi_k2_ctx, "kv_pool_fill") == pytest.approx(
        100 * (2400 + 2800 + 3600 + 4000 + 4400) / 5 / 6272)
    assert _kimi_k2_read(kimi_k2_ctx, "held_assignment_share") \
        == pytest.approx(100 * 20 / 640)
    assert _kimi_k2_read(kimi_k2_ctx, "expert_load_max_over_mean") \
        == pytest.approx(12 * 5 / 20)


def test_kimi_k2_cell_reports_what_the_manifest_says():
    manifest = mf.load_manifest()
    per_layer = {m["name"] for m in mf.metrics_for(manifest, KIMI_CELL, "per_layer")}
    own = {m["name"] for m in manifest["per_layer"]
           if m.get("workloads") == [KIMI_CELL]}
    assert own >= {
        "latent_attn_decode_share", "mla_prefill_attn_share",
        "moe_decode_share.mla", "latent_attn_decode_roofline",
        "flash_mla_fwd_roofline", "tpot_p50.longdoc"}
    # one entry a meaning since PR 51: what other families report too is read
    # under the name they read it under
    assert own | {"decode_device_per_step", "prefill_device_per_call",
                  "kv_pool_fill", "held_assignment_share",
                  "expert_load_max_over_mean", "ttft_mean", "ttft_p90",
                  "peak_hbm.serve", "device_idle_share.serve",
                  "compiles_in_window", "ingress_overhead_p50",
                  "client_to_engine_p50", "first_token_return_p50",
                  "admit_burst_p90", "engine_step_wall",
                  "gc_pause_in_window"} <= per_layer
    # another family's counts would charge rows this one does not read
    assert not {"paged_attn_roofline", "full_attn_decode_roofline",
                "decode_device_per_step.chat", "window_attn_decode_roofline",
                "shared_kv_decode_roofline"} & per_layer
    # tpot_p50 spread over half its bound in six runs (two and a half rounds
    # of 16 prefills a window): it is a per-layer metric here, as ISSUE 44 says
    assert {m["name"] for m in mf.metrics_for(manifest, KIMI_CELL, "end_to_end")} \
        == {"serve_tokens_per_s", "setup_s"}
    with open(os.path.join(mf.ROOT, "benchmarks", "traffic",
                           "longdoc_closed.json")) as f:
        traffic = json.load(f)
    p = traffic["params"]
    assert (p["callers"], p["cycle"], p["output_tokens"], p["ramp_seconds"],
            p["drain_limit_s"]) == (16, 48, 512, 12, 60)
    assert p["prompt"] == {"min": 8192, "max": 24576}
    assert traffic["check"]["requests"] == 4
    assert traffic["check"]["length"] == 24576 + 512
    assert set(traffic["check"]["limits"]) == {
        "mean_gap", "mismatch_share", "first_token_max_gap"}
    cell = next(w for w in manifest["workloads"] if w["name"] == KIMI_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-k2.6-serve", "longdoc_closed", 1)


def test_kimi_k2_readers_read_nothing_from_a_program_without_the_family(
        kimi_k2_ctx):
    """The parent commit's trace has no such program, kernel or counter:
    every reader of a new metric returns None and raises nothing."""
    bare = {"trace": {"op_self_s": {"fusion.1 = bf16[64,4096]{1,0} fusion(": 1.0},
                      "op_count": {"fusion.1 = bf16[64,4096]{1,0} fusion(": 3},
                      "module_s": {"jit_paged_decode_steps(1)": 2.0},
                      "module_count": {"jit_paged_decode_steps(1)": 4}},
            "cfg": kimi_k2_ctx["cfg"], "device_report": {"kind": "TPU v5 lite"},
            "marks": {"open": 0.0, "close": 9.0, "polls": [
                (t, {"decode_steps": 10 * t, "iters": t}) for t in (1.0, 2.0, 3.0)]}}
    manifest = mf.load_manifest()
    names = [m["name"] for m in manifest["per_layer"]
             if m.get("workloads") == [KIMI_CELL]
             and not m["name"].startswith(("ttft_", "tpot_"))]
    # and what this family reads under a name it shares since PR 51
    names += ["decode_device_per_step", "prefill_device_per_call",
              "kv_pool_fill", "held_assignment_share",
              "expert_load_max_over_mean"]
    assert len(names) == 10
    for name in names:
        assert _kimi_k2_read(bare, name) is None, name
        assert _kimi_k2_read({"cfg": bare["cfg"]}, name) is None, name
