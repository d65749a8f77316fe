"""The mellum family behind the benchmark's seam (this test names the family
on purpose): its configuration file against the published one and the catalog
row, the parameter count's arithmetic, its ``train`` surface at the rehearsal
widths (program against reference, the fp8 control apart), the operations its
metrics count at hand-worked sizes, and its metrics' readers on a hand-made
context. Names here are ``mellum_*`` so that
``tests/test_benchmark_tracing_readers.py`` can import them beside the other
families' tests."""
import json
import os

import jax
import numpy as np
import pytest

from benchmarks.families import mellum as family
from benchmarks.harness import manifest as mf
from benchmarks.harness.weights import load_config_file

MELLUM_FILE = os.path.join(mf.ROOT, "benchmarks", "configs",
                           "mellum2-12b-a2.5b-train.json")
MELLUM_CELL = "train_moe_8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_mellum_configuration_is_the_catalog_row_but_for_the_share():
    """Every key of the published config is in the configuration file with
    its value but the five ``reduced`` lists, each with the published count
    beside it; the share, the floors and what the config is silent on are
    stated; the widths reproduce the issue's parameter arithmetic."""
    cfg = load_config_file(MELLUM_FILE)
    with open(os.path.join(mf.ROOT, "benchmarks", "published",
                           cfg["published"] + ".json")) as f:
        published = json.load(f)
    assert published["source"] == cfg["source"]
    published = published["config"]
    assert published["model_type"] == "mellum"
    changed = {k for k, v in published.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "layer_types", "mlp_layer_types", "num_experts",
        "vocab_size"}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) \
        == (8, 16, 24576)
    assert cfg["layer_types"] == published["layer_types"][:8] \
        == ["sliding_attention"] * 3 + ["full_attention"] \
        + ["sliding_attention"] * 3 + ["full_attention"]
    assert cfg["mlp_layer_types"] == published["mlp_layer_types"][:8]
    assert cfg["share"]["published"] == {
        "num_experts": 64, "vocab_size": 98304, "num_hidden_layers": 28}
    assert cfg["share"]["chips_sharing_a_layer"] == 4
    assert cfg["n_router_outputs"] == 64 and cfg["held_experts"] == [0, 16]
    # the floors: whole periods and >= 4 layers, >= 8 experts, >= 1/8 vocabulary
    assert cfg["num_hidden_layers"] % 4 == 0 and cfg["num_hidden_layers"] >= 4
    assert cfg["num_experts"] >= 8 and cfg["vocab_size"] * 8 >= 98304
    for key in ("router_scoring", "qk_norm", "auxiliary_loss", "yarn",
                "mtp_head", "torch_dtype", "weights", "optimizer", "data"):
        assert cfg["assumed"][key].strip(), key
    assert "NONE" in cfg["assumed"]["auxiliary_loss"]
    dep = cfg["deployment"]
    assert (dep["max_seq_len"], dep["batch_rows"], dep["fsdp"], dep["warmup_steps"],
            dep["check_rows"], dep["report_every"], dep["report_probe_steps"]) \
        == (8192, 2, 1, 3, 1, 10, 9)
    config = family.program_config(cfg)
    params = jax.eval_shape(lambda k: family.init_weights(config, k),
                            jax.random.key(0))

    def count(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    attention = 9_437_184 + 2 * 1_179_648 + 9_437_184
    expert = 3 * 2304 * 896
    assert (attention, expert) == (21_233_664, 6_193_152)
    layer = attention + 147_456 + 2 * 2304 + 16 * expert
    assert layer == 120_476_160
    assert count(params["layers"]) == 8 * layer
    assert count(params) == 8 * layer + 2 * 24576 * 2304 + 2304 == 1_077_057_792
    # whole, as published: 12.15 B, 2.44 B of it active
    whole = 28 * (layer + 48 * expert) + 2 * 98304 * 2304 + 2304
    active = whole - 28 * 56 * expert
    assert round(whole / 1e9, 2) == 12.15 and round(active / 1e9, 2) == 2.44


def test_mellum_published_file_is_the_catalog_row_key_by_key():
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    with open(os.path.join(mf.ROOT, "benchmarks", "published",
                           "mellum2-12b-a2.5b.json")) as f:
        published = json.load(f)
    assert published["source"] == row["source_url"]
    assert set(published["config"]) == set(row["config"])
    for key, value in row["config"].items():
        assert published["config"][key] == value, key
    manifest = mf.load_manifest()
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "mellum2-12b-a2.5b-train")
    assert entry["source"] == row["source_url"]
    cell = next(w for w in manifest["workloads"] if w["name"] == MELLUM_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mellum2-12b-a2.5b-train", "pretrain_8k", 1)
    with open(mf.resolve_cell(manifest, MELLUM_CELL)["traffic_file"]) as f:
        traffic = json.load(f)
    assert traffic["generator"] == "token_dataset"
    assert traffic["params"] == {"seq": 8192, "rows": 640}
    assert set(traffic["check"]["limits"]) <= {
        "loss_abs_diff", "grad_norm_rel_diff", "grad_rel_err"}


def test_mellum_train_flops_at_the_issues_hand_worked_size():
    """ISSUE 46's reckoning: 6 x (8 x (21.23 M attention + 0.15 M router + 2
    held experts x 6.19 M) + 56.6 M head) = 1.96 GFLOP of products, and 3 x
    (2 x 67.1 + 6 x 15.7) = 0.69 GFLOP of attention a token at 8,192."""
    cfg = load_config_file(MELLUM_FILE)
    assert family.held_share(cfg) == 0.25
    assert family.layer_matmul_params(cfg) == 21_233_664 + 147_456 + 2 * 6_193_152
    products = 6 * (8 * (21_233_664 + 147_456 + 2 * 6_193_152) + 2304 * 24576)
    assert round(products / 1e9, 2) == 1.96
    # a full layer's causal forward: 4 x 32 x 128 x (8192 x 8193 / 2) / 8192
    full = 4 * 4096 * 8193 / 2
    # a window layer's: query i sees min(i + 1, 1024) keys
    pairs = sum(min(i + 1, 1024) for i in range(8192))
    assert family.window_pairs(cfg, 8192) == pairs == 7_864_832
    window = 4 * 4096 * pairs / 8192
    assert (round(full / 1e6, 1), round(window / 1e6, 1)) == (67.1, 15.7)
    attention = 3 * (2 * full + 6 * window)
    assert round(attention / 1e9, 2) == 0.69
    got = family.train_flops_per_token(cfg, 8192)
    assert got == pytest.approx(products + attention, rel=1e-12)
    assert round(got * 16384 / 1e12) == 43  # TFLOP a step
    # a sequence inside the window: every layer is a causal one
    short = family.train_flops_per_token(cfg, 512)
    assert short == pytest.approx(products + 3 * 8 * 4 * 4096 * 513 / 2)
    # a tool that sizes another depth changes the count alone
    deeper = family.train_flops_per_token({**cfg, "num_hidden_layers": 12}, 8192)
    assert deeper == pytest.approx(
        got + 6 * 4 * family.layer_matmul_params(cfg)
        + 3 * (full + 3 * window))


def test_mellum_kernel_operation_counts_at_hand_worked_sizes():
    cfg = {"sliding_window": 4, "hidden_size": 8, "moe_intermediate_size": 3,
           "num_experts_per_tok": 2, "num_experts": 4, "n_router_outputs": 16,
           "deployment": {"moe_tokens": 32}}
    # 6 queries, a window of 4: 1 + 2 + 3 + 4 + 4 + 4 pairs
    assert family.window_pairs(cfg, 6) == 18
    assert family.window_pairs(cfg, 3) == 6  # inside the window: causal
    # forward: 2 products x 2 flops x batch 2 x 5 heads x width 7 x 18 pairs
    assert family.flash_window_fwd_flops(cfg, 2, 5, 6, 7) == 4 * 2 * 5 * 7 * 18
    # reverse: 5 products x 2 flops for the PAIR of kernels, half to each
    each = family.flash_window_bwd_kernel_flops(cfg, 2, 5, 6, 7)
    assert 2 * each == 10 * 2 * 5 * 7 * 18
    # a chunk of 32 tokens x 2 choices x a quarter = 16 expected held
    # assignments; a product 2 x 8 x 3 flops each; nine needed of eleven run
    one = family.experts_grouped_flops(cfg, 999)
    assert family.GROUPED_PRODUCTS_RUN * one == pytest.approx(9 * 2 * 8 * 3 * 16)


def _mellum_metric(name):
    spec = mf.metric_file(name)
    return mf.load_plugin("readers", spec["reader"]), spec["params"]


def test_mellum_metrics_read_a_hand_made_trace():
    """The seven metric files this family brought, on the operation names the
    chip-less compile of the step gives (``tests/test_chip_compile.py``)."""
    cfg = load_config_file(MELLUM_FILE)
    tile = "{3,2,1,0:T(8,128)(2,1)}"
    ops = {
        # seconds, calls
        f"flash_window_fwd.8 = (bf16[2,32,8192,128]{tile}, f32[2,32,8192,1]"
        "{3,2,1,0:T(8,128)}) custom-call(%a, %b, %c)": (0.06, 6),
        f"flash_window_bwd_dq.9 = bf16[2,32,8192,128]{tile} custom-call(%a)": (0.09, 6),
        "flash_window_bwd_dkv.9 = (f32[2,32,8192,128]{3,2,1,0:T(8,128)}, "
        "f32[2,32,8192,128]{3,2,1,0:T(8,128)}) custom-call(%a)": (0.12, 6),
        f"attn_full.42 = bf16[2,32,8192,128]{tile} custom-call(%a)": (0.03, 2),
        "attn_full.41 = (f32[2,32,8192,128]{3,2,1,0:T(8,128)}, "
        "f32[2,32,8192,128]{3,2,1,0:T(8,128)}) custom-call(%a)": (0.03, 2),
        f"attn_full.43 = (bf16[2,32,8192,128]{tile}, f32[2,32,8192,1]"
        "{3,2,1,0:T(8,128)}) custom-call(%a, %b, %c)": (0.02, 2),
        "ragged-dot-none.3 = f32[16384,896]{1,0:T(8,128)} custom-call(%a)": (0.10, 160),
        "ragged-dot-none.12 = f32[16,2304,896]{2,1,0:T(8,128)} custom-call(%a)": (0.06, 64),
        "ragged-dot-none.2 = f32[16384,2304]{1,0:T(8,128)} custom-call(%a)": (0.06, 128),
        "gather.5 = bf16[16384,2304]{1,0:T(8,128)(2,1)} gather(%x, %i)": (0.03, 96),
        "sort.7 = (s32[32768]{0}, s32[32768]{0}) sort(%k, %v)": (0.01, 32),
        # rows onto tokens; the expert stacks' gradient float32 -> bfloat16;
        # and the embedding's scatter, whose 16,384 are the step's tokens
        "fusion.922 = f32[4096,2304]{1,0} fusion(s32[16384]{0} %i, "
        "f32[16384,2304]{1,0} %add.1490)": (0.03, 32),
        "select_add_fusion.15 = bf16[16,2304,896]{2,1,0} fusion(bf16[16,2304,"
        "896]{2,1,0} %g, f32[16,2304,896]{2,1,0} %h)": (0.02, 32),
        "fusion.39 = bf16[24576,2304]{1,0} fusion(s32[16384]{0} %i, "
        "bf16[16384,2304]{1,0} %b)": (0.01, 1),
        "fusion.77 = (bf16[8,2304,4096]{2,1,0}, bf16[8,2304,4096]{2,1,0}, "
        "bf16[8,2304,4096]{2,1,0}) fusion(%p, %g, %m, %n)": (0.02, 1),
        "fusion.5 = bf16[2,8192,4096]{2,1,0} fusion(%y, %w)": (0.31, 8),
    }
    ctx = {"cfg": cfg, "device_report": {"kind": "TPU v5 lite"},
           "trace": {"op_self_s": {k: v[0] for k, v in ops.items()},
                     "op_count": {k: v[1] for k, v in ops.items()},
                     "module_s": {"jit_step_fn(123)": 1.0}}}

    def read(name):
        reader, params = _mellum_metric(name)
        return reader.read(ctx, params)

    assert read("experts_train_share") == pytest.approx(22.0)
    assert read("experts_glue_train_share") == pytest.approx(9.0)
    assert read("attn_train_share") == pytest.approx(35.0)
    # the full layers' dQ and dK/dV, not their forward
    full = 4 * family.flash_full_bwd_kernel_flops(cfg, 2, 32, 8192, 128)
    assert full == pytest.approx(2 * 10 * 2 * 32 * 128 * 8192 * 8193 / 2)
    assert read("flash_full_bwd_roofline.train") == pytest.approx(
        100 * full / 197e12 / 0.06)
    peak = 197e12
    fwd = 6 * family.flash_window_fwd_flops(cfg, 2, 32, 8192, 128)
    assert read("flash_window_fwd_roofline.train") == pytest.approx(
        100 * fwd / peak / 0.06)
    bwd = 12 * family.flash_window_bwd_kernel_flops(cfg, 2, 32, 8192, 128)
    assert bwd == pytest.approx(2.5 * fwd)
    assert read("flash_window_bwd_roofline") == pytest.approx(
        100 * bwd / peak / 0.21)
    grouped = (160 + 64 + 128) * family.experts_grouped_flops(cfg)
    assert read("experts_grouped_roofline") == pytest.approx(
        100 * grouped / peak / 0.22)
    # 32 chunk-layers of a step, 11 calls each: what 9 products of 8,192
    # expected assignments need
    assert grouped == pytest.approx(32 * 9 * 2 * 2304 * 896 * 8192)
    # a program without any of it (the parent's): every reader is silent
    ctx["trace"] = {"op_self_s": {"fusion.1 = f32[8] fusion(%a)": 1.0},
                    "op_count": {"fusion.1 = f32[8] fusion(%a)": 3},
                    "module_s": {"jit_step_fn(1)": 1.0}}
    for name in ("experts_train_share", "experts_glue_train_share",
                 "attn_train_share", "flash_full_bwd_roofline.train",
                 "flash_window_fwd_roofline.train", "flash_window_bwd_roofline",
                 "experts_grouped_roofline"):
        assert read(name) is None, name


def test_mellum_cell_reports_what_the_issue_lists():
    manifest = mf.load_manifest()
    e2e = {m["name"] for m in mf.metrics_for(manifest, MELLUM_CELL, "end_to_end")}
    assert e2e == {"train_tokens_per_s_chip", "setup_s"}
    per = {m["name"] for m in mf.metrics_for(manifest, MELLUM_CELL, "per_layer")}
    listed = {
        "trainer_ready_s", "train_mfu", "input_wait_per_step",
        "device_idle_share.train", "peak_hbm.train", "report_wait_per_report",
        "report_stall_per_step", "train_step_device", "experts_train_share",
        "experts_glue_train_share", "attn_train_share",
        "flash_full_bwd_roofline.train", "flash_window_bwd_roofline",
        "flash_window_fwd_roofline.train", "experts_grouped_roofline"}
    # every name listed is required; a later PR's entry is one more (PR 56)
    assert per >= listed
    # the accepted training cell reports what it did, and none of the new
    new = {n for n in listed if "experts" in n or "window" in n
           or n in ("attn_train_share", "flash_full_bwd_roofline.train")}
    dense = {m["name"] for m in mf.metrics_for(manifest, "train_4k", "per_layer")}
    assert dense >= (listed - new) | {"flash_fwd_roofline", "flash_bwd_roofline"}
    assert not dense & new


def test_mellum_reference_agrees_with_the_program_and_not_with_fp8():
    """The ``train`` surface at the rehearsal widths, as the runner's
    ``compare`` reads it: the program (float32 there) sits on the reference,
    and the control in fp8 does not."""
    from benchmarks.runners.train import compare, make_checkers

    cfg = load_config_file(MELLUM_FILE, rehearse=True)
    config = family.program_config(cfg)
    assert config.period == ("sliding_attention",) * 3 + ("full_attention",)
    params = family.make_weights(config, 3_000_000_019)
    seqs = np.random.default_rng(0).integers(0, 256, (1, 129), dtype=np.int32)
    checkers = make_checkers(family, cfg, config)
    with jax.default_matmul_precision("highest"):
        sound = compare(checkers, "program", params, seqs[:, :-1], seqs[:, 1:])
        control = compare(checkers, "control", params, seqs[:, :-1], seqs[:, 1:])
    assert sound["grad_rel_err"] < 1e-4 and sound["loss_abs_diff"] < 1e-4
    assert control["grad_rel_err"] > 100 * sound["grad_rel_err"]
    assert control["grad_rel_err"] > 0.02


def test_mellum_step_runs_at_the_learning_rate_its_file_states():
    """``deployment.learning_rate`` reaches the step: ``at_stated_rate``
    gives the program's ``default_optimizer`` at that peak under the runner's
    schedule, which updates the state the runner's own ``init`` made; a
    runner whose optimizer is another (its rate passed, its warm-up moved,
    another chain) is refused loudly; a file that states no rate gets the
    runner's optimizer itself."""
    import jax.numpy as jnp
    import optax

    from ray_tpu.train.step import default_optimizer

    cfg = load_config_file(MELLUM_FILE, rehearse=True)
    assert load_config_file(MELLUM_FILE)["deployment"]["learning_rate"] == 1e-5
    config = family.program_config(cfg)
    runners = default_optimizer(warmup_steps=10, total_steps=1000)
    stated = family.at_stated_rate(config, runners)
    params = {"w": jnp.linspace(-1.0, 1.0, 8), "b": jnp.ones((3,))}
    state = base_state = runners.init(params)
    peak = 0.0
    for i in range(12):  # across the end of the warm-up
        grads = jax.tree.map(lambda p: jnp.cos(p * (i + 1)), params)
        got, state = stated.update(grads, state, params)
        base, base_state = runners.update(grads, base_state, params)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(base)):
            np.testing.assert_allclose(a, b * (1e-5 / 3e-4), rtol=1e-5, atol=1e-12)
        peak = max(peak, float(jnp.max(jnp.abs(got["w"]))))
    assert 0.5e-5 < peak < 1.5e-5  # AdamW's step is about its rate
    for other in (default_optimizer(lr=1e-4, warmup_steps=10, total_steps=1000),
                  default_optimizer(warmup_steps=100, total_steps=1000),
                  optax.adamw(3e-4)):
        with pytest.raises(RuntimeError, match="no longer"):
            family.at_stated_rate(config, other)
    silent = {**cfg, "deployment": {k: v for k, v in cfg["deployment"].items()
                                    if k != "learning_rate"}}
    assert family.at_stated_rate(family.program_config(silent), runners) is runners
