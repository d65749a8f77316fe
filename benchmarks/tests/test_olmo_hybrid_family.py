"""The olmo_hybrid family behind the benchmark's seam (this test names the
family on purpose): its configuration file against the published one and the
catalog row, the parameter count's arithmetic, its ``train`` surface at the
rehearsal widths (program against reference, the fp8 control apart), the
operations its metrics count at hand-worked sizes, and its metrics' readers
on a hand-made context. Names here are ``olmo_hybrid_*`` so that
``tests/test_benchmark_tracing_readers.py`` can import them beside the other
families' tests."""
import json
import os

import jax
import numpy as np
import pytest

from benchmarks.families import olmo_hybrid as family
from benchmarks.harness import manifest as mf
from benchmarks.harness.weights import load_config_file

OLMO_HYBRID_FILE = os.path.join(mf.ROOT, "benchmarks", "configs",
                                "olmo-hybrid-7b-train.json")
OLMO_HYBRID_CELL = "train_gdn_32k"
OLMO_HYBRID_NEW = {"gdn_fwd_roofline", "gdn_bwd_roofline", "gdn_train_share",
                   "gdn_proj_train_share"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
L, F = "linear_attention", "full_attention"


def test_olmo_hybrid_configuration_is_the_catalog_row_but_for_the_share():
    """Every key of the published config is in the configuration file with
    its value but the three ``reduced`` lists, each with the published count
    beside it; the share, the floors and what the config is silent on are
    stated; the widths reproduce the issue's parameter arithmetic."""
    cfg = load_config_file(OLMO_HYBRID_FILE)
    with open(os.path.join(mf.ROOT, "benchmarks", "published",
                           cfg["published"] + ".json")) as f:
        published = json.load(f)
    assert published["source"] == cfg["source"]
    published = published["config"]
    assert published["model_type"] == "olmo_hybrid"
    changed = {k for k, v in published.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "layer_types", "vocab_size"}
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (4, 25088)
    assert cfg["layer_types"] == published["layer_types"][:4] == [L, L, L, F]
    assert published["layer_types"] == [L, L, L, F] * 8
    assert cfg["share"]["published"] == {
        "vocab_size": 100352, "num_hidden_layers": 32}
    assert cfg["share"]["chips_sharing_a_layer"] == 4
    # the floors: a whole period and >= 4 layers, >= 1/8 of the vocabulary
    assert cfg["num_hidden_layers"] % 4 == 0 and cfg["num_hidden_layers"] >= 4
    assert cfg["vocab_size"] * 8 >= 100352 and cfg["vocab_size"] * 4 == 100352
    # every width as published
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
            cfg["linear_conv_kernel_dim"], cfg["linear_allow_neg_eigval"]) \
        == (3840, 11008, 30, 30, 30, 30, 96, 192, 4, True)
    assert cfg["rope_parameters"] == {"rope_theta": None}
    for key in ("weights", "rope", "norms", "decay_init", "head_dim",
                "optimizer", "data", "torch_dtype"):
        assert cfg["assumed"][key].strip(), key
    assert "NONE" in cfg["assumed"]["rope"]
    dep = cfg["deployment"]
    assert (dep["max_seq_len"], dep["batch_rows"], dep["fsdp"],
            dep["warmup_steps"], dep["check_rows"], dep["report_every"],
            dep["report_probe_steps"], dep["attention_impl"]) \
        == (32768, 1, 1, 3, 1, 4, 9, "flash")
    assert dep["report_every_why"].strip()
    config = family.program_config(cfg)
    assert config.period == (L, L, L, F) and config.head_dim == 128
    params = jax.eval_shape(lambda k: family.init_weights(config, k),
                            jax.random.key(0))

    def count(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    # a linear layer: the five wide projections (3840 x 23,040 in all), the
    # two narrow ones, the convolution's taps, A_log and dt_bias, the head
    # norm; the SwiGLU; the two norms of the block
    mixer = 3840 * (2880 + 2880 + 5760 + 5760) + 5760 * 3840 + 3840 * 60 \
        + 4 * 11520 + 30 + 30 + 192
    mlp = 3 * 3840 * 11008
    assert (mixer, mlp) == (88_750_332, 126_812_160)
    linear = mixer + mlp + 2 * 3840
    full = 4 * 3840 * 3840 + mlp + 4 * 3840
    assert (linear, full) == (215_570_172, 185_809_920)
    assert count(params["linear"]) == 3 * linear
    assert count(params["full"]) == full
    assert count(params) == 3 * linear + full + 2 * 25088 * 3840 + 3840 \
        == 1_025_200_116
    assert cfg["hbm_reckoning"]["4_layers"]["params"] == 1_025_200_116
    # whole, as published: eight periods and the whole vocabulary, "7B"
    whole = 8 * (3 * linear + full) + 2 * 100352 * 3840 + 3840
    assert round(whole / 1e9, 2) == 7.43


def test_olmo_hybrid_published_file_is_the_catalog_row_key_by_key():
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "Olmo-Hybrid-7B")
    with open(os.path.join(mf.ROOT, "benchmarks", "published",
                           "olmo-hybrid-7b.json")) as f:
        published = json.load(f)
    assert published["source"] == row["source_url"]
    assert set(published["config"]) == set(row["config"])
    for key, value in row["config"].items():
        assert published["config"][key] == value, key
    manifest = mf.load_manifest()
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "olmo-hybrid-7b-train")
    assert entry["source"] == row["source_url"]
    cell = next(w for w in manifest["workloads"]
                if w["name"] == OLMO_HYBRID_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmo-hybrid-7b-train", "pretrain_32k", 1)
    with open(mf.resolve_cell(manifest, OLMO_HYBRID_CELL)["traffic_file"]) as f:
        traffic = json.load(f)
    assert traffic["generator"] == "token_dataset"
    assert traffic["params"] == {"seq": 32768, "rows": 160}
    assert traffic["rehearsal"] == {"seq": 128, "rows": 64}
    assert set(traffic["check"]["limits"]) <= {
        "loss_abs_diff", "grad_norm_rel_diff", "grad_rel_err"}
    assert traffic["check"]["limits_from"].strip()


def test_olmo_hybrid_flops_at_hand_worked_sizes():
    """ISSUE 57's reckoning: 6 x 928.8 M (the period's and the head's
    matrices) and 12 x 3,840 x 16,384.5 of the full layer's causal pairs a
    token at 32,768; and the delta rule's chunk products by hand."""
    cfg = load_config_file(OLMO_HYBRID_FILE)
    linear = family.layer_matmul_params(cfg, L)
    full = family.layer_matmul_params(cfg, F)
    assert linear == 3840 * (23040 + 60) + 3 * 3840 * 11008 == 215_516_160
    assert full == 4 * 3840 * 3840 + 3 * 3840 * 11008 == 185_794_560
    products = 6 * (3 * linear + full + 3840 * 25088)
    assert round(products / 6e6, 1) == 928.7
    attention = 3 * 4 * 30 * 128 * 32769 / 2
    # a head's chunk of 64 rows: Kb K^T and Q K^T (2 x 2 x 64 x 64 x 96), the
    # state read twice and written once (3 x 2 x 64 x 96 x 192), T and the
    # pair matrix against the writes (2 x 2 x 64 x 64 x 192)
    chunk = 4 * 64 * 64 * 96 + 6 * 64 * 96 * 192 + 4 * 64 * 64 * 192
    assert family.gdn_chunk_flops(cfg) == chunk == 11_796_480
    rule = 3 * 3 * 30 * chunk / 64
    got = family.train_flops_per_token(cfg, 32768)
    assert got == pytest.approx(products + attention + rule, rel=1e-12)
    assert round(products / 1e9, 2) == 5.57 and round(attention / 1e9, 3) == 0.755
    assert round(rule / 1e6, 1) == 49.8
    assert round(got * 32768 / 1e12) == 209  # TFLOP a step
    # one call of each kernel at the cell's shape; the reverse pass's two
    # kernels share twice the forward's
    fwd = family.gdn_chunk_fwd_flops(cfg, 1, 30, 32768)
    assert fwd == 30 * 512 * chunk
    assert family.gdn_chunk_bwd_flops(cfg, 1, 30) == fwd
    assert family.gdn_chunk_bwd_flops(cfg, 1, 10) == fwd / 3
    # a tool that sizes another depth changes the count alone: a second
    # period is three more linear layers and a full one
    deeper = family.train_flops_per_token({**cfg, "num_hidden_layers": 8}, 32768)
    assert deeper == pytest.approx(
        2 * got - 6 * 3840 * 25088, rel=1e-12)
    assert family.flash_full_bwd_kernel_flops(cfg, 1, 30, 32768, 128) \
        == 5 * 30 * 128 * (32768 * 32769 // 2)


def _olmo_hybrid_metric(name):
    spec = mf.metric_file(name)
    return mf.load_plugin("readers", spec["reader"]), spec["params"]


def test_olmo_hybrid_metrics_read_a_hand_made_trace():
    """The four metric files this family brought, and the three accepted ones
    its cell joined, on the operation names the chip-less compile of the step
    gives (``tests/test_chip_compile.py``)."""
    cfg = load_config_file(OLMO_HYBRID_FILE)
    tile = "{3,2,1,0:T(8,128)(2,1)}"
    ops = {
        # seconds, calls
        f"gdn_chunk_fwd.3 = bf16[1,30,32768,192]{tile} custom-call(%q, %k, "
        "%v, %gb)": (0.06, 3),
        "gdn_chunk_bwd_states.9 = f32[1,10,512,192,96]{4,3,2,1,0:T(8,128)} "
        "custom-call(%j, %q, %k, %v, %gb)": (0.05, 9),
        f"gdn_chunk_bwd.9 = (bf16[1,10,32768,96]{tile}, bf16[1,10,32768,96]"
        f"{tile}, bf16[1,10,32768,192]{tile}, f32[1,512,1,24,64]"
        "{4,3,2,1,0:T(8,128)}) custom-call(%j, %q, %k, %v)": (0.10, 9),
        f"attn_full.42 = bf16[1,30,32768,128]{tile} custom-call(%a)": (0.06, 1),
        "attn_full.41 = (f32[1,30,32768,128]{3,2,1,0:T(8,128)}, "
        "f32[1,30,32768,128]{3,2,1,0:T(8,128)}) custom-call(%a)": (0.08, 1),
        f"attn_full.43 = (bf16[1,30,32768,128]{tile}, f32[1,30,32768,1]"
        "{3,2,1,0:T(8,128)}) custom-call(%a, %b, %c)": (0.05, 1),
        # the mixer's own shapes: the fused q/k/v product, the gate, a
        # reverse product into w_qkv's gradient, the heads-first copy
        "fusion.648 = bf16[1,32768,11520]{2,1,0} fusion(%x, %w)": (0.04, 6),
        "fusion.12 = bf16[1,32768,5760]{2,1,0} fusion(%x, %w)": (0.03, 6),
        "fusion.77 = f32[3840,11520]{1,0} fusion(bf16[1,32768,3840]{2,1,0} "
        "%x, bf16[1,32768,11520]{2,1,0} %d)": (0.05, 3),
        "copy.3 = bf16[1,30,32768,96]{3,2,1,0} copy(%q)": (0.01, 12),
        # the MLP's and the optimizer's: no mixer's shape
        "fusion.5 = bf16[1,32768,11008]{2,1,0} fusion(%y, %w)": (0.30, 8),
        "fusion.91 = (bf16[3,3840,11008]{2,1,0}, bf16[3,3840,11008]{2,1,0}, "
        "bf16[3,3840,11008]{2,1,0}) fusion(%p, %g, %m, %n)": (0.02, 1),
    }
    ctx = {"cfg": cfg, "device_report": {"kind": "TPU v5 lite"},
           "trace": {"op_self_s": {k: v[0] for k, v in ops.items()},
                     "op_count": {k: v[1] for k, v in ops.items()},
                     "module_s": {"jit_step_fn(123)": 1.0}}}

    def read(name):
        reader, params = _olmo_hybrid_metric(name)
        return reader.read(ctx, params)

    peak = 197e12
    fwd = family.gdn_chunk_fwd_flops(cfg, 1, 30, 32768)
    assert read("gdn_fwd_roofline") == pytest.approx(
        100 * 3 * fwd / peak / 0.06)
    # nine calls of each reverse kernel, a third of the heads each: three
    # layers' reverse passes, twice the forward's need
    assert read("gdn_bwd_roofline") == pytest.approx(
        100 * 3 * 2 * fwd / peak / 0.15)
    assert read("gdn_train_share") == pytest.approx(21.0)
    assert read("gdn_proj_train_share") == pytest.approx(13.0)
    assert read("attn_train_share") == pytest.approx(19.0)
    assert read("optimizer_train_share") == pytest.approx(2.0)
    full = 2 * family.flash_full_bwd_kernel_flops(cfg, 1, 30, 32768, 128)
    assert read("flash_full_bwd_roofline.train") == pytest.approx(
        100 * full / peak / 0.14)
    # a program without any of it (the parent's): every reader is silent
    ctx["trace"] = {"op_self_s": {"fusion.1 = f32[8] fusion(%a)": 1.0},
                    "op_count": {"fusion.1 = f32[8] fusion(%a)": 3},
                    "module_s": {"jit_step_fn(1)": 1.0}}
    for name in OLMO_HYBRID_NEW:
        assert read(name) is None, name


def test_olmo_hybrid_cell_reports_what_the_issue_lists():
    manifest = mf.load_manifest()
    e2e = {m["name"] for m in mf.metrics_for(manifest, OLMO_HYBRID_CELL,
                                              "end_to_end")}
    assert e2e == {"train_tokens_per_s_chip", "setup_s"}
    per = {m["name"] for m in mf.metrics_for(manifest, OLMO_HYBRID_CELL,
                                              "per_layer")}
    listed = {
        "trainer_ready_s", "train_mfu", "input_wait_per_step",
        "device_idle_share.train", "peak_hbm.train", "report_wait_per_report",
        "report_stall_per_step", "train_step_device", "optimizer_train_share",
        "attn_train_share", "flash_full_bwd_roofline.train"} | OLMO_HYBRID_NEW
    # every name listed is required; a later PR's entry is one more
    assert per >= listed
    # each new entry is this cell's alone, a file on a reader that is there
    for m in manifest["per_layer"]:
        if m["name"] in OLMO_HYBRID_NEW:
            assert m["workloads"] == [OLMO_HYBRID_CELL]
            assert m["moves"] == "train_tokens_per_s_chip"
            assert m["layer"] == mf.metric_file(m["name"])["layer"]
            assert mf.metric_file(m["name"])["reader"] in (
                "flops_roofline", "ops_share")
    # the accepted training cells report none of the new
    for other in ("train_4k", "train_moe_8k"):
        names = {m["name"] for m in mf.metrics_for(manifest, other, "per_layer")}
        assert not names & OLMO_HYBRID_NEW
    assert len(manifest["per_layer"]) <= 128


def test_olmo_hybrid_reference_agrees_with_the_program_and_not_with_fp8():
    """The ``train`` surface at the rehearsal widths, as the runner's
    ``compare`` reads it: the program (float32 there) sits on the reference,
    and the control in fp8 does not."""
    from benchmarks.runners.train import compare, make_checkers

    cfg = load_config_file(OLMO_HYBRID_FILE, rehearse=True)
    config = family.program_config(cfg)
    assert config.period == (L, L, L, F)
    assert (config.linear_key_head_dim, config.linear_value_head_dim) == (24, 48)
    params = family.make_weights(config, 3_000_000_019)
    seqs = np.random.default_rng(0).integers(0, 256, (1, 129), dtype=np.int32)
    checkers = make_checkers(family, cfg, config)
    with jax.default_matmul_precision("highest"):
        sound = compare(checkers, "program", params, seqs[:, :-1], seqs[:, 1:])
        control = compare(checkers, "control", params, seqs[:, :-1], seqs[:, 1:])
    assert sound["grad_rel_err"] < 1e-4 and sound["loss_abs_diff"] < 1e-4
    assert control["grad_rel_err"] > 100 * sound["grad_rel_err"]
    assert control["grad_rel_err"] > 0.02
