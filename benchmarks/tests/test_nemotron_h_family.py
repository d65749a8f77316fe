"""The Nemotron-H family behind the benchmark's seam (this test names the
family on purpose): its configuration file against the published one, its
surface, its reference against the program and against the control in fp8 /
bf16 at the rehearsal widths, the bytes its rooflines count, and its
metrics' readers on a recorded context. Names here are ``hybrid_*`` so that
``tests/test_benchmark_tracing_readers.py`` can import them beside the other
families' tests."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import nemotron_h as family
from benchmarks.harness import manifest as mf
from benchmarks.harness import reference as ref
from benchmarks.harness.weights import load_config_file
from benchmarks.readers import bytes_roofline, module_time, ops_share

CONFIG_FILE = os.path.join(mf.ROOT, "benchmarks", "configs",
                           "nemotron-3-nano-30b-a3b-serve.json")


@pytest.fixture(scope="module")
def hybrid_setup():
    cfg = load_config_file(CONFIG_FILE, rehearse=True)
    cfg = {**cfg, "torch_dtype": "float32"}
    config = family.program_config(cfg)
    params = family.make_weights(config, 3_000_000_019)
    tokens = np.random.default_rng(0).integers(1, 256, (2, 128), dtype=np.int32)
    return cfg, config, params, tokens


def test_hybrid_configuration_keeps_every_published_width():
    cfg = load_config_file(CONFIG_FILE)
    with open(os.path.join(mf.ROOT, "benchmarks", "published",
                           cfg["published"] + ".json")) as f:
        published = json.load(f)["config"]
    cut = {k for k, v in published.items() if cfg[k] != v}
    assert cut == {"num_hidden_layers", "hybrid_override_pattern",
                   "n_routed_experts", "vocab_size"} == set(cfg["reduced"])
    # the cut pattern is a prefix of the published one, a quarter of its depth
    assert published["hybrid_override_pattern"].startswith(
        cfg["hybrid_override_pattern"])
    assert len(cfg["hybrid_override_pattern"]) == cfg["num_hidden_layers"] == 13
    assert [cfg["hybrid_override_pattern"].count(k) for k in "ME*"] == [6, 5, 2]
    # the share: the router keeps its published width, this chip holds a half
    assert cfg["n_router_outputs"] == published["n_routed_experts"] == 128
    assert cfg["held_experts"] == [0, 64] and cfg["n_routed_experts"] == 64
    assert cfg["vocab_size"] * 2 == published["vocab_size"]
    config = family.program_config(cfg)
    assert (config.mamba_inner, config.conv_channels) == (4096, 6144)
    params = jax.eval_shape(lambda k: family.init_weights(config, k),
                            jax.random.key(0))
    count = sum(x.size for x in jax.tree.leaves(params))
    assert abs(count - 3.926e9) < 2e6  # ISSUE 29's arithmetic


def test_hybrid_family_gives_the_serve_surface(hybrid_setup):
    cfg, config, params, _ = hybrid_setup
    for name in ("program_config", "init_weights", "make_weights",
                 "reference_logits", "make_gap_fn", "make_greedy_fn",
                 "make_engine", "set_weights", "serve_programs"):
        assert callable(getattr(family, name)), name
    sized = family.serve_programs(config, cfg["deployment"])
    assert [p[0] for p in sized["programs"]] == [
        "decode", "prefill_4x128", "prefill_4x512"]
    assert set(sized["state"]._fields) == {"k", "v", "ssm", "conv"}
    again = family.make_weights(config, 3_000_000_019)
    assert all(bool(jnp.array_equal(a, b)) for a, b in
               zip(jax.tree.leaves(params), jax.tree.leaves(again)))


def test_hybrid_served_tokens_agree_with_the_reference_in_float32(hybrid_setup):
    """Through the engine the family builds (pages and slot state), in
    float32: every emitted token is the reference's own choice."""
    cfg, config, params, tokens = hybrid_setup
    engine = family.make_engine(config, params, cfg["deployment"])
    try:
        prompt = tokens[0][:70].tolist()
        out = engine.generate(tokens=prompt, max_tokens=20, eos_token=None,
                              timeout=600)["tokens"]
    finally:
        engine.stop()
    gaps = ref.teacher_forced_gaps(family.make_gap_fn(cfg), params, prompt,
                                   out, 128)
    assert len(out) == 20 and max(gaps) == 0.0


def _hybrid_served_like(cfg, params, prompt, steps, quant):
    return ref.greedy_decode(family.make_greedy_fn(cfg, quant), params, prompt,
                             steps, 128)


def test_hybrid_control_in_fp8_is_not_correct_and_bf16_is(hybrid_setup):
    """bf16 stands in for a sound program, fp8 is the control: the
    comparison that decides ``correct`` tells them apart."""
    cfg, _config, params, tokens = hybrid_setup
    gap_fn = family.make_gap_fn(cfg)
    sound, control = [], []
    for row in tokens:
        prompt = row[:48].tolist()
        for quant, into in (("bf16", sound), ("fp8", control)):
            into += ref.teacher_forced_gaps(
                gap_fn, params, prompt,
                _hybrid_served_like(cfg, params, prompt, 24, quant), 128)
    s, c = ref.summarize_gaps(sound), ref.summarize_gaps(control)
    exact = ref.teacher_forced_gaps(
        gap_fn, params, tokens[0][:48].tolist(),
        _hybrid_served_like(cfg, params, tokens[0][:48].tolist(), 8, None), 128)
    assert max(exact) == 0.0  # the reference agrees with itself
    assert c["mean_gap"] > 3 * max(s["mean_gap"], 1e-4)


def test_hybrid_bytes_by_hand():
    cfg = load_config_file(CONFIG_FILE)
    assert family.expert_bytes(cfg) == 2 * 2688 * 1856 * 2 == 19_955_712
    # 10 ticks of one expert layer, 128 rows, 200 experts touched a tick over
    # the 5 expert layers: 40 matrices pairs a layer-tick, and rows in and out
    assert family.moe_decode_bytes(cfg, 10, 128, 200.0) == pytest.approx(
        10 * (40 * 19_955_712 + 128 * 2 * 2688 * 2))
    # 6 layer-ticks over 128 slots: 64 x 64 x 128 float32 read and written
    assert family.ssm_update_bytes(cfg, 6, 128) == 6 * 2 * 128 * 2_097_152


# ------------------------------------------------- the new metrics' readers
@pytest.fixture
def hybrid_ctx():
    """A recorded context: ``data/hybrid_trace.json`` says what of."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "hybrid_trace.json")) as f:
        rec = json.load(f)
    polls = [(t, s) for t, s in rec["polls"]]
    # the record predates the reducer's tables of whole calls and of a
    # program's operations (PR 32): its calls are taken as whole, and its
    # prefill program ran 8 rows a call (the engine before PR 30)
    trace = rec["trace"]
    trace["module_whole_s"] = trace["module_s"]
    trace["module_whole_count"] = trace["module_count"]
    trace["module_ops"] = {name: {
        "nemotron_h_prefill.2 = bf16[8,32,512,128]{3,2,1,0:T(8,128)(2,1)S(1)} "
        "custom-call(bf16[8,32,512,128]{3,2,1,0:T(8,128)(2,1)S(1)} %x, ": 0.001}
        for name in trace["module_s"] if name.startswith("jit_nemotron_h_prefill")}
    return {"trace": trace, "cfg": load_config_file(CONFIG_FILE),
            "device_report": {"kind": "TPU v5 lite"},
            "marks": {"polls": polls, "open": polls[0][0] - 1,
                      "close": polls[-1][0] + 1}}


def _hybrid_read(ctx, name):
    return mf.read_metric(name, ctx)


def test_hybrid_readers_on_a_recorded_context(hybrid_ctx):
    trace = hybrid_ctx["trace"]
    decode = trace["module_s"]["jit_nemotron_h_decode_steps(10832319702325659996)"]
    # 6 calls of 8 steps took 0.874 s; 7 prefill calls of 8 rows 1.285 s
    assert _hybrid_read(hybrid_ctx, "decode_device_per_step") \
        == pytest.approx(1e3 * decode / 48)
    assert _hybrid_read(hybrid_ctx, "prefill_device_per_call") \
        == pytest.approx(1e3 * 1.285057821 / (7 * 8))
    # one fusion a layer and tick holds both expert products: 5 x 48 of them
    moe, n_moe = ops_share.ops_seconds_and_count(
        trace, mf.metric_file("moe_decode_share")["params"]["ops"])
    assert n_moe == 5 * 48 and 1e3 * moe / n_moe == pytest.approx(1.69, abs=0.01)
    assert _hybrid_read(hybrid_ctx, "moe_decode_share") \
        == pytest.approx(100 * moe / decode)
    # the state is updated by one fusion and read again by a second: 6 x 48 each
    ssm, n_ssm = ops_share.ops_seconds_and_count(
        trace, mf.metric_file("ssm_decode_share")["params"]["ops"])
    assert n_ssm == 2 * 6 * 48
    assert _hybrid_read(hybrid_ctx, "ssm_decode_share") \
        == pytest.approx(100 * ssm / decode)
    assert 35 < 100 * ssm / decode < 45 < 100 * moe / decode < 50
    # neither pattern takes a prefill operation (ragged-dot, the state scatter)
    prefill_only = {k: v for k, v in trace["op_self_s"].items()
                    if "24576" in k or "ragged" in k}
    assert prefill_only
    for name in ("moe_decode_share", "ssm_decode_share"):
        pattern = mf.metric_file(name)["params"]["ops"]
        assert ops_share.ops_seconds_and_count(
            {"op_self_s": prefill_only, "op_count": {}}, pattern)[0] == 0


def test_hybrid_rooflines_count_what_the_calls_need(hybrid_ctx):
    cfg, peak = hybrid_ctx["cfg"], 819e9
    first, last = hybrid_ctx["marks"]["polls"][0][1], hybrid_ctx["marks"]["polls"][-1][1]
    touched = (last["moe_experts_touched"] - first["moe_experts_touched"]) \
        / (last["decode_steps"] - first["decode_steps"])
    assert 150 < touched < 5 * 64  # over the five expert layers of a tick
    moe, n_moe = ops_share.ops_seconds_and_count(
        hybrid_ctx["trace"], mf.metric_file("moe_decode_roofline")["params"]["ops"])
    want = 100 * family.moe_decode_bytes(cfg, n_moe, 128, touched) / peak / moe
    assert _hybrid_read(hybrid_ctx, "moe_decode_roofline") == pytest.approx(want)
    ssm, n_ssm = ops_share.ops_seconds_and_count(
        hybrid_ctx["trace"], mf.metric_file("ssm_update_roofline")["params"]["ops"])
    want = 100 * family.ssm_update_bytes(cfg, n_ssm / 2, 128) / peak / ssm
    assert _hybrid_read(hybrid_ctx, "ssm_update_roofline") == pytest.approx(want)
    assert 40 < _hybrid_read(hybrid_ctx, "moe_decode_roofline") < 60
    assert 45 < want < 65
    # the engine's counters alone
    assert _hybrid_read(hybrid_ctx, "held_assignment_share") == pytest.approx(
        100 * (last["moe_assignments_held"] - first["moe_assignments_held"])
        / (last["moe_assignments"] - first["moe_assignments"]))
    assert 4 < _hybrid_read(hybrid_ctx, "expert_load_max_over_mean") < 8


def test_hybrid_readers_read_nothing_from_a_program_without_the_family(hybrid_ctx):
    """The parent commit's trace has no such program, operation or counter:
    every new reader returns None and raises nothing."""
    bare = {"trace": {"op_self_s": {"fusion.1 = bf16[64,4096]{1,0} fusion()": 1.0},
                      "op_count": {"fusion.1 = bf16[64,4096]{1,0} fusion()": 3},
                      "module_s": {"jit_paged_decode_steps(1)": 2.0},
                      "module_count": {"jit_paged_decode_steps(1)": 4}},
            "cfg": hybrid_ctx["cfg"], "device_report": {"kind": "TPU v5 lite"},
            "marks": {"open": 0.0, "close": 9.0, "polls": [
                (t, {"decode_steps": 10 * t, "iters": t}) for t in (1.0, 2.0, 3.0)]}}
    for name in ("decode_device_per_step", "prefill_device_per_call",
                 "moe_decode_share", "ssm_decode_share", "moe_decode_roofline",
                 "ssm_update_roofline", "expert_load_max_over_mean",
                 "held_assignment_share"):
        assert _hybrid_read(bare, name) is None, name
        assert _hybrid_read({"cfg": bare["cfg"]}, name) is None, name
    # the operations are there, the counters are not
    hybrid_ctx["marks"]["polls"] = [
        (t, {k: v for k, v in s.items() if not k.startswith("moe_")})
        for t, s in hybrid_ctx["marks"]["polls"]]
    assert _hybrid_read(hybrid_ctx, "moe_decode_roofline") is None
    assert module_time.read(bare, {"module": "^jit_paged_decode",
                                   "steps_key": "decode_chunk",
                                   "cut_at_edges": False}) \
        == pytest.approx(1e3 * 2.0 / (4 * 8))
    assert bytes_roofline.read(bare, {"ops": "nothing", "bytes": "ssm_update_bytes"}) is None
