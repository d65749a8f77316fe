"""The ling_hybrid family behind the benchmark's seam (this test names the
family on purpose): its configuration file against the catalog row and the
parameter count's arithmetic, its surface, its reference against the program
and against the control in fp8 / bf16 at the rehearsal widths, the bytes and
operations its rooflines count at hand-worked sizes, and its metrics' readers
on a hand-made context. Names here are ``ling_*`` so that
``tests/test_benchmark_tracing_readers.py`` can import them beside the other
families' tests."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import ling_hybrid as family
from benchmarks.harness import manifest as mf
from benchmarks.harness import reference as ref
from benchmarks.harness.weights import load_config_file

LING_FILE = os.path.join(mf.ROOT, "benchmarks", "configs",
                         "ling-3.0-flash-serve.json")
LING_CELL = "serve_kda_longdoc"


@pytest.fixture(scope="module")
def ling_setup():
    cfg = load_config_file(LING_FILE, rehearse=True)
    config = family.program_config(cfg)
    params = family.make_weights(config, 3_000_000_019)
    tokens = np.random.default_rng(0).integers(1, 256, (2, 160), dtype=np.int32)
    return cfg, config, params, tokens


def test_ling_configuration_is_the_catalog_row_but_for_the_share():
    """Every key of the published config is in the configuration file with
    its value but the five ``reduced`` lists; the share, the gate forms and
    the sizes the config.json is silent on are stated; the widths reproduce
    the issue's parameter arithmetic and the floors hold."""
    cfg = load_config_file(LING_FILE)
    with open(os.path.join(mf.ROOT, "benchmarks", "published",
                           cfg["published"] + ".json")) as f:
        published = json.load(f)["config"]
    assert published["model_type"] == "bailing_hybrid"
    assert (published["hidden_size"], published["num_attention_heads"],
            published["head_dim"], published["kv_lora_rank"],
            published["qk_rope_head_dim"], published["qk_nope_head_dim"],
            published["v_head_dim"], published["q_lora_rank"],
            published["moe_intermediate_size"], published["intermediate_size"],
            published["num_experts_per_tok"], published["n_group"],
            published["topk_group"], published["kda_lower_bound"],
            published["short_conv_kernel_size"], published["layer_group_size"],
            published["rms_norm_eps"], published["rope_theta"]) \
        == (2560, 32, 128, 512, 64, 128, 128, None, 768, 6144, 8, 8, 4, -5, 4,
            6, 1e-06, 6000000)
    changed = {k for k, v in published.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "num_experts",
        "vocab_size", "num_nextn_predict_layers"}
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["num_experts"], cfg["vocab_size"],
            cfg["num_nextn_predict_layers"]) == (6, 1, 128, 39296, 0)
    assert cfg["share"]["published"] == {
        "num_experts": 512, "vocab_size": 157184, "num_hidden_layers": 42}
    assert cfg["share"]["chips_sharing_a_layer"] == 4
    assert cfg["n_router_outputs"] == 512 and cfg["held_experts"] == [0, 128]
    # the floors: a whole group, five layers after the dense one, >= 8
    # experts, an eighth of the vocabulary or more
    assert cfg["num_hidden_layers"] == cfg["layer_group_size"]
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["num_experts"] >= 8 and cfg["vocab_size"] * 8 >= 157184
    assert [family.is_mla(cfg, i) for i in range(6)] == [False] * 5 + [True]
    for key in ("kda_gate", "kda_output_gate", "kda_decay_seed", "kda_heads",
                "kda_qk_norm", "kda_conv", "mla_qk_norm", "mla_gate",
                "rotary_layout", "router_dtype", "routed_expert_out_scale",
                "stored_row", "torch_dtype", "eos"):
        assert cfg["assumed"][key].strip(), key
    config = family.program_config(cfg)
    params = jax.eval_shape(lambda k: family.init_weights(config, k),
                            jax.random.key(0))

    def count(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    kda = 2560 * 5 * 4096 + 4096 * 2560 + 2560 * 32 + 4 * 3 * 4096 + 32 \
        + 4096 + 128
    mla = 2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 2560 * 32 \
        + 4096 * 2560 + 512
    assert 2560 * 32 * 192 == 15_728_640 and 512 * 32 * 256 == 4_194_304
    expert = 3 * 2560 * 768
    assert expert == 5_898_240
    ffn = 2560 * 512 + 512 + 128 * expert + expert
    layers = params["layers"]
    assert count(layers[0]) == kda + 2 * 2560 + 3 * 2560 * 6144
    for i in (1, 2, 3, 4):
        assert count(layers[i]) == kda + 2 * 2560 + ffn
    assert count(layers[5]) == mla + 2 * 2560 + ffn
    assert layers[1]["router"]["w"].dtype == jnp.float32
    assert layers[1]["experts"]["w_up"].shape == (128, 2560, 768)
    total = count(params)
    assert total == sum(count(lp) for lp in layers) + 2 * 39296 * 2560 + 2560
    assert abs(total - 4.4065e9) < 2e6
    assert cfg["hbm_reckoning"]["weights_bytes"] == 8_826_255_232
    dep = cfg["deployment"]
    assert dep["total_pages"] == dep["num_slots"] * (
        dep["max_seq_len"] // dep["page_size"]) + 1 == 12481
    assert dep["prefill_buckets"] == [32768]  # ONE prefill program
    cache = jax.eval_shape(lambda: family._program().init_cache(
        config, dep["num_slots"], dep["total_pages"], dep["page_size"]))
    assert cache._fields == ("k", "kda", "conv")
    assert cache.k.shape == (1, 12481, 64, 640)          # ONE latent layer
    assert cache.kda.shape == (5, 25, 32, 128, 128)
    assert cache.conv.shape == (5, 25, 3, 12288)
    state = sum(x.size * x.dtype.itemsize for x in cache)
    assert state == cfg["hbm_reckoning"]["state_bytes"] == 1_293_803_520
    # the traffic: 24 callers, 48 quantiles of 8,192-32,768, 256 out
    with open(os.path.join(mf.ROOT, "benchmarks", "traffic",
                           "kda_longdoc_closed.json")) as f:
        traffic = json.load(f)
    assert traffic["params"]["callers"] == dep["num_slots"] == 24
    assert (traffic["params"]["cycle"], traffic["params"]["prompt"],
            traffic["params"]["output_tokens"]) == (
        48, {"min": 8192, "max": 32768}, 256)
    longest = 8192 + round(24576 * 47.5 / 48) + 256
    assert longest <= traffic["check"]["length"] <= dep["max_seq_len"]
    assert set(traffic["check"]["limits"]) == {
        "mean_gap", "mismatch_share", "first_token_max_gap"}


def test_ling_family_gives_the_serve_surface(ling_setup):
    cfg, config, params, _ = ling_setup
    for name in ("program_config", "init_weights", "make_weights",
                 "reference_logits", "make_gap_fn", "make_greedy_fn",
                 "make_engine", "set_weights", "serve_programs"):
        assert callable(getattr(family, name)), name
    assert (family.DECODE_MODULE, family.PREFILL_MODULE,
            family.PREFILL_ROWS_FROM) == ("^jit_ling_decode",
                                         "^jit_ling_prefill", None)
    again = family.make_weights(config, 3_000_000_019)
    assert all(bool(jnp.array_equal(a, b)) for a, b in
               zip(jax.tree.leaves(params), jax.tree.leaves(again)))
    cell = mf.resolve_cell(mf.load_manifest(), LING_CELL)
    assert cell["cell"]["chips"] == 1
    assert cell["config_file"] == LING_FILE


def test_ling_without_the_program_fails_at_the_first_request(monkeypatch):
    """On a commit that lacks ``ray_tpu.models.ling_hybrid`` the replica
    starts, and its first request raises: the benchmark's command ends soon."""
    monkeypatch.setattr(family, "_program", lambda: None)
    cfg = load_config_file(LING_FILE, rehearse=True)
    config = family.program_config(cfg)
    assert config is None and family.make_weights(config, 1) == {}
    engine = family.make_engine(config, {}, cfg["deployment"])
    assert engine.stats() == {}
    with pytest.raises(RuntimeError, match="no ray_tpu.models.ling_hybrid"):
        engine.generate_stream(tokens=[1], max_tokens=1)
    engine.stop()


def test_ling_served_tokens_agree_with_the_reference_in_float32(ling_setup):
    """Through the engine the family builds (both caches, the recurrence's
    fallback on the CPU), in float32: every emitted token is the reference's
    own choice up to the order of float32 sums."""
    cfg, config, params, tokens = ling_setup
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


def test_ling_control_in_fp8_is_not_correct_and_bf16_is(ling_setup):
    """bf16 stands in for a sound program, fp8 is the control: the
    comparison that decides ``correct`` tells them apart."""
    cfg, _config, params, tokens = ling_setup
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


def test_ling_bytes_and_operations_by_hand():
    cfg = load_config_file(LING_FILE)
    assert (family.layers_of(cfg, False), family.layers_of(cfg, True)) == (5, 1)
    # a token of a KDA layer: 32 heads x three products of 128 x 128 x 2
    assert family.kda_row_flops(cfg) == 32 * 3 * 2 * 128 * 128 == 3_145_728
    # a prompt of 20,480 tokens is 102,400 rows of the 5 KDA layers; its
    # program calls the kernel 5 x (20480 / 2048 + 1/2) times in the mean,
    # and the calls together need rows x 3,145,728
    one = family.kda_chunk_fwd_flops(cfg, 102_400.0, 1, 32, 2048, 128)
    assert one * 5 * 10.5 == pytest.approx(102_400 * 3_145_728)
    # 40 calls (5 layers x 8 ticks) over 24 slots of which 22.5 are live a
    # tick (112.5 updates): a live slot's 2 MiB state in and out, and its
    # tokens: q, k, v, o in bf16 and the decay in float32
    assert family.kda_step_bytes(cfg, 40, 24, 112.5) == pytest.approx(
        40 * 22.5 * (2 * 32 * 128 * 128 * 4 + 32 * 128 * (4 * 2 + 4)))


# ------------------------------------------------- the new metrics' readers
CHUNK_OP = ("kda_chunk_fwd.%d = (bf16[1,32,2048,128]{3,2,1,0:T(8,128)(2,1)}, "
            "f32[1,32,128,128]{3,2,1,0:T(8,128)}) custom-call(s32[1]{0} %%p, ")
STEP_OP = ("kda_step.%d = (bf16[24,32,128]{2,1,0:T(8,128)(2,1)}, "
           "f32[5,25,32,128,128]{4,3,2,1,0:T(8,128)}) custom-call(f32[24,32]{1,0} %%b, ")
LING_DECODE, LING_PREFILL = "jit_ling_decode(123)", "jit_ling_prefill(456)"


@pytest.fixture
def ling_ctx():
    """A hand-made context: 2 decode calls of 8 ticks (80 calls of the step
    kernel), two prefill calls (105 calls of the chunk kernel), six polls a
    second apart around a profile called for from 2.7 to 3.3 s. Between polls
    the engine runs 100 ticks over 22 live slots and 2 prefills of 20,480
    tokens each."""
    ops = {STEP_OP % 1: (0.05, 80), CHUNK_OP % 2: (0.3, 105)}
    trace = {"op_self_s": {k: v[0] for k, v in ops.items()},
             "op_count": {k: v[1] for k, v in ops.items()},
             "module_s": {LING_DECODE: 0.25, LING_PREFILL: 0.9},
             "module_count": {LING_DECODE: 2, LING_PREFILL: 2}}
    trace["module_whole_s"] = trace["module_s"]
    trace["module_whole_count"] = trace["module_count"]

    def poll(t):
        return (float(t), {
            "decode_steps": 100 * t, "iters": 12 * t,
            "kda_state_updates": 100 * t * 5 * 22,
            "prefill_calls": 2 * t, "kda_rows": 2 * t * 5 * 20_480,
            "prefill_rows": 2 * t * 20_480})

    return {"trace": trace, "cfg": load_config_file(LING_FILE),
            "device_report": {"kind": "TPU v5 lite"},
            "marks": {"polls": [poll(t) for t in (1, 2, 3, 4, 5, 6)],
                      "open": 0.0, "close": 7.0, "trace_call": (2.7, 3.3),
                      "traced": (2.8, 3.2)}}


def test_ling_readers_on_a_hand_made_context(ling_ctx):
    cfg = ling_ctx["cfg"]
    assert mf.read_metric("decode_device_per_step", ling_ctx) \
        == pytest.approx(1e3 * 0.25 / 16)
    assert mf.read_metric("prefill_device_per_call", ling_ctx) \
        == pytest.approx(1e3 * 0.9 / 2)
    assert mf.read_metric("kda_prefill_share", ling_ctx) \
        == pytest.approx(100 * 0.3 / 0.9)
    assert mf.read_metric("kda_decode_share", ling_ctx) \
        == pytest.approx(100 * 0.05 / 0.25)
    want = 100 * 105 * family.kda_chunk_fwd_flops(
        cfg, 5 * 20_480.0, 1, 32, 2048, 128) / 197e12 / 0.3
    assert mf.read_metric("kda_prefill_roofline", ling_ctx) \
        == pytest.approx(want)
    want = 100 * family.kda_step_bytes(cfg, 80, 24, 5 * 22.0) / 819e9 / 0.05
    assert mf.read_metric("kda_update_roofline", ling_ctx) \
        == pytest.approx(want)
    assert 0 < want < 100
    # a program without the kernels or the counters (the parent): nothing
    bare = {**ling_ctx, "trace": {**ling_ctx["trace"], "op_self_s": {},
                                  "op_count": {}}}
    for name in ("kda_prefill_roofline", "kda_update_roofline",
                 "kda_prefill_share", "kda_decode_share",
                 "kda_proj_prefill_share", "moe_decode_share.ling",
                 "moe_prefill_share.ling"):
        assert mf.read_metric(name, bare) is None, name


def test_ling_cell_reports_what_the_manifest_says():
    manifest = mf.load_manifest()
    e2e = [m["name"] for m in mf.metrics_for(manifest, LING_CELL, "end_to_end")]
    assert e2e == ["serve_tokens_per_s", "setup_s"]
    per = [m["name"] for m in mf.metrics_for(manifest, LING_CELL, "per_layer")]
    for name in ("kda_prefill_roofline", "kda_update_roofline",
                 "kda_prefill_share", "kda_decode_share",
                 "kda_proj_prefill_share", "moe_decode_share.ling",
                 "moe_prefill_share.ling", "decode_device_per_step",
                 "prefill_device_per_call", "kv_pool_fill", "peak_hbm.serve",
                 "device_idle_share.serve", "tpot_p90", "ttft_mean"):
        assert name in per, name
    # three accepted entries this cell could read stay Kimi's alone:
    # benchmarks/tests/test_kimi_k2_family.py holds their ``workloads`` to
    # exactly its cell, and no PR but a ``benchmark`` one may edit that file
    assert not {"latent_attn_decode_roofline", "flash_mla_fwd_roofline",
                "tpot_p50.longdoc"} & set(per)
