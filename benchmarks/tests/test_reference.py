"""The Llama family's plain reference against ``models/llama.py`` at a tiny
size (this test names the family on purpose), and the control (the reference
in fp8) coming out as NOT correct."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import llama
from benchmarks.harness import reference as ref
from benchmarks.harness.weights import seed_key

TINY = {
    "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 256, "rope_theta": 1000000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "deployment": {"max_seq_len": 128, "attention_impl": "reference", "remat": None},
}


@pytest.fixture(scope="module")
def setup():
    config = llama.program_config(TINY)
    params = llama.make_weights(config, 3_000_000_019)
    tokens = np.random.default_rng(0).integers(0, 256, (2, 128), dtype=np.int32)
    return config, params, tokens


def test_reference_matches_the_program_in_float32(setup):
    from ray_tpu.models.llama import llama_forward

    config, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        prog = llama_forward(params, jnp.asarray(tokens), config)
    mine = jnp.stack([llama.reference_logits(params, t, TINY, block=32) for t in tokens])
    assert float(jnp.max(jnp.abs(prog - mine))) < 2e-4


def test_reference_loss_and_gradient_match_the_program(setup):
    from ray_tpu.models.llama import llama_loss

    config, params, tokens = setup
    t, y = jnp.asarray(tokens[:, :-1][:, :96]), jnp.asarray(tokens[:, 1:][:, :96])
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(lambda p: llama_loss(p, t, y, config))(params)
    lr, gr = jax.value_and_grad(
        lambda p: llama.reference_loss(p, t, y, TINY, block=32))(params)
    assert abs(float(lp) - float(lr)) < 1e-4
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-3 * (1 + float(jnp.max(jnp.abs(b))))


def test_weights_are_seeded_and_seeds_over_31_bits_differ(setup):
    config, params, _ = setup
    again = llama.make_weights(config, 3_000_000_019)
    assert all(bool(jnp.array_equal(a, b)) for a, b in
               zip(jax.tree.leaves(params), jax.tree.leaves(again)))
    other = jax.jit(lambda k: llama.init_weights(config, k))(seed_key(3_000_000_019 - 2 ** 31))
    assert not bool(jnp.array_equal(params["lm_head"], other["lm_head"]))


def _served_like(params, prompt, steps, quant):
    fn = llama.make_greedy_fn(TINY, quant)
    return ref.greedy_decode(fn, params, prompt, steps, 128)


def test_control_in_fp8_is_not_correct_and_bf16_is(setup):
    """The comparison that decides ``correct`` has to fail for the precision
    below the configuration's: bf16 stands in for a sound program here, fp8
    for the control. The limits are the ones a run at this size would set."""
    _config, params, tokens = setup
    gap_fn = llama.make_gap_fn(TINY)
    sound, control = [], []
    for row in tokens:
        prompt = row[:48].tolist()
        sound += ref.teacher_forced_gaps(
            gap_fn, params, prompt, _served_like(params, prompt, 24, "bf16"), 128)
        control += ref.teacher_forced_gaps(
            gap_fn, params, prompt, _served_like(params, prompt, 24, "fp8"), 128)
    s, c = ref.summarize_gaps(sound), ref.summarize_gaps(control)
    exact = ref.summarize_gaps(ref.teacher_forced_gaps(
        gap_fn, params, tokens[0][:48].tolist(),
        _served_like(params, tokens[0][:48].tolist(), 8, None), 128))
    assert exact["max_gap"] == 0.0  # the reference agrees with itself
    assert c["mean_gap"] > 3 * max(s["mean_gap"], 1e-4)


def test_teacher_forcing_reads_the_right_positions(setup):
    _config, params, tokens = setup
    prompt = tokens[0][:40].tolist()
    out = _served_like(params, prompt, 6, None)
    wrong = list(out)
    wrong[3] = (wrong[3] + 1) % 256
    gaps = ref.teacher_forced_gaps(llama.make_gap_fn(TINY), params, prompt, wrong, 128)
    assert gaps[0] == gaps[1] == gaps[2] == 0.0 and gaps[3] > 0.0
