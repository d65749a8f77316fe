"""The Nemotron-H family at tiny widths on the CPU, seeded weights: the
program (``models/nemotron_h.py`` over ``ops/ssm.py`` and ``ops/moe.py``,
through pages AND per-slot state) against the plain float32 reference
(``benchmarks/families/nemotron_h_reference.py``, which imports nothing of
the program). LOGITS are compared, not sampled tokens.

Tolerance: in float32 the program and the reference differ only in the order
of their sums (chunked against token-by-token recurrence, grouped against
dense expert products, paged against full attention): ``TOL`` = 2e-4 on
logits of magnitude 3, a few hundred float32 roundings. A bfloat16 state, or
the reference in fp8, is off by 1e-2 and more and must fail it."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import nemotron_h_reference as ref
from prefill_groups import GROUPS, check_rows_follow_the_group
from ray_tpu.models import nemotron_h as nh
from ray_tpu.ops import moe, ssm

TOL = 2e-4
PAGE = 16


def ref_cfg(config, held=None):
    """The reference's configuration (the source's key names) of ``config``."""
    return dict(
        hybrid_override_pattern=config.pattern,
        mamba_num_heads=config.mamba_num_heads,
        mamba_head_dim=config.mamba_head_dim,
        ssm_state_size=config.ssm_state_size, n_groups=config.n_groups,
        conv_kernel=config.conv_kernel,
        num_attention_heads=config.num_attention_heads,
        num_key_value_heads=config.num_key_value_heads,
        head_dim=config.head_dim,
        held_experts=list(held or config.held_experts),
        num_experts_per_tok=config.num_experts_per_tok,
        routed_scaling_factor=config.routed_scaling_factor,
        norm_eps=config.norm_eps)


@pytest.fixture(scope="module")
def tiny():
    config = nh.NemotronHConfig.tiny(dtype=jnp.float32,
                                     attention_impl="reference")
    params = jax.jit(lambda k: nh.init_params(config, k))(jax.random.key(7))
    return config, params


def prefill_rows(config, params, cache, prompts, slots, bucket, page=PAGE):
    """One 8-row prefill of ``prompts`` into ``slots``; row r owns pages
    1 + 8 r ... Returns (logits of the rows, cache, table [slots + 1, 8])."""
    n_slots = cache.ssm.shape[1] - 1
    tokens = np.zeros((8, bucket), np.int32)
    pages = np.zeros((8, bucket // page), np.int32)
    lengths = np.ones((8,), np.int32)
    rows = np.full((8,), n_slots, np.int32)
    table = np.zeros((n_slots, 8), np.int32)
    for r, (p, slot) in enumerate(zip(prompts, slots)):
        mine = np.arange(1 + 8 * r, 9 + 8 * r)
        tokens[r, :len(p)] = p
        pages[r] = mine[:bucket // page]
        lengths[r], rows[r], table[slot] = len(p), slot, mine
    prefill = nh.make_paged_prefill_fn(config, page)
    logits, cache = prefill(params, cache, tokens, pages, lengths, rows)
    return logits[:len(prompts)], cache, table


@pytest.mark.parametrize("variant,passes", [
    ("float32_state", True), ("bfloat16_state", False), ("fp8_reference", False)])
def test_prefill_then_decode_through_pages_and_state_equals_the_reference(
        tiny, variant, passes):
    """Three prompts of different lengths in one padded prefill, then 24
    decode ticks each through the page pool and the slot state: every logit
    row equals the reference's full forward over the whole sequence."""
    config, params = tiny
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 256, n).tolist() for n in (37, 5, 64)]
    slots, ticks = [2, 0, 3], 24
    cache = nh.init_cache(config, 4, 33, PAGE)
    if variant == "bfloat16_state":
        cache = cache._replace(ssm=cache.ssm.astype(jnp.bfloat16))
    logits, cache, table = prefill_rows(config, params, cache, prompts, slots, 64)
    got = [[np.asarray(logits[r])] for r in range(3)]
    seqs = [list(p) for p in prompts]
    tokens = np.zeros((4,), np.int32)
    positions = np.zeros((4,), np.int32)
    active = np.zeros((4,), bool)
    for r, slot in enumerate(slots):
        active[slot], positions[slot] = True, len(prompts[r])
    step = jax.jit(lambda c, t, p: nh.paged_decode_one(
        params, c, t, p, active, table, config, PAGE, False)[:2])
    for _ in range(ticks):
        for r, slot in enumerate(slots):
            tokens[slot] = int(np.argmax(got[r][-1]))
            seqs[r].append(int(tokens[slot]))
        logits, cache = step(cache, tokens, positions)
        for r, slot in enumerate(slots):
            got[r].append(np.asarray(logits[slot]))
        positions = positions + active
    quant = "fp8" if variant == "fp8_reference" else None
    worst = 0.0
    for r, p in enumerate(prompts):
        want = ref.reference_logits(params, jnp.asarray(seqs[r], jnp.int32),
                                    ref_cfg(config), quant)
        want = np.asarray(want)[len(p) - 1:]
        worst = max(worst, float(np.abs(want - np.stack(got[r])).max()))
    assert (worst < TOL) == passes, worst


def test_padded_batched_prefill_gives_each_row_its_unpadded_run(tiny):
    """Rows of true lengths 37, 5, 64 and 1 under one 64 bucket: each row's
    first-token logits, Mamba state and kept convolution rows are those of
    the row prefilled alone at exactly its length (pages of one row, so no
    padding at all)."""
    config, params = tiny
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 256, n).tolist() for n in (37, 5, 64, 1)]
    cache = nh.init_cache(config, 4, 33, PAGE)
    logits, cache, _ = prefill_rows(config, params, cache, prompts,
                                    [0, 1, 2, 3], 64)
    for r, p in enumerate(prompts):
        n = len(p)
        alone = nh.init_cache(config, 1, 8 * 64 + 1, 1)
        one = nh.make_paged_prefill_fn(config, 1)
        pages = np.zeros((8, n), np.int32)
        pages[0] = 1 + np.arange(n)
        toks = np.zeros((8, n), np.int32)
        toks[0] = p
        lens = np.ones((8,), np.int32)
        lens[0] = n
        rows = np.array([0] + [1] * 7, np.int32)
        want, alone = one(params, alone, toks, pages, lens, rows)
        np.testing.assert_allclose(logits[r], want[0], atol=TOL)
        np.testing.assert_allclose(cache.ssm[:, r], alone.ssm[:, 0], atol=1e-5)
        np.testing.assert_allclose(cache.conv[:, r], alone.conv[:, 0], atol=1e-5)


@pytest.mark.parametrize("length", [1, 15, 16, 37, 130])
def test_chunked_scan_equals_the_token_scan(length):
    """``mamba2_prefill`` (chunks of 16, padded to a bucket of 144 with
    garbage past the true length, a nonzero state to start from) equals
    ``mamba2_step`` applied token by token to the real positions."""
    b, s, h, p, g, n = 2, 144, 4, 8, 2, 16
    ks = jax.random.split(jax.random.key(length), 7)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 2.0)
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    bm = jax.random.normal(ks[3], (b, s, g, n))
    cm = jax.random.normal(ks[4], (b, s, g, n))
    d = jax.random.normal(ks[5], (h,))
    state0 = jax.random.normal(ks[6], (b, h, p, n))
    lengths = jnp.array([length, max(1, length - 1)], jnp.int32)
    y, state = ssm.mamba2_prefill(x, dt, a, bm, cm, d, state0, lengths, chunk=16)
    want_state, ys = state0, []
    for t in range(length):
        moves = (t < lengths).astype(jnp.float32)[:, None]
        yt, want_state = ssm.mamba2_step(x[:, t], dt[:, t] * moves, a,
                                         bm[:, t], cm[:, t], d, want_state)
        ys.append(yt)
    np.testing.assert_allclose(state, want_state, atol=2e-5, rtol=2e-5)
    for row in range(b):
        real = int(lengths[row])
        np.testing.assert_allclose(y[row, :real], jnp.stack(ys, 1)[row, :real],
                                   atol=2e-5, rtol=2e-5)


def _layer(tiny):
    config, params = tiny
    lp = params["layers"][config.pattern.index("E")]
    y = jax.random.normal(jax.random.key(11), (40, config.hidden_size))
    return config, lp, y


@pytest.mark.parametrize("impl", ["ragged", "dense"])
def test_the_shares_add_up_to_the_uncut_layer(tiny, impl):
    """Experts 0-3 held on one chip and 4-7 on the other, the shared expert
    counted once: the two partial sums and the shared expert add up to the
    reference's whole layer over all 8 experts (model-configs guide, 4)."""
    config, lp, y = _layer(tiny)
    whole = jax.jit(lambda k: nh.init_params(
        nh.NemotronHConfig.tiny(dtype=jnp.float32, n_routed_experts=8,
                                held_experts=(0, 8)), k))(jax.random.key(7))
    lp = whole["layers"][config.pattern.index("E")]
    parts = []
    for lo, hi in ((0, 4), (4, 8)):
        held = {k: v[lo:hi] for k, v in lp["experts"].items()}
        parts.append(moe.routed_experts(
            y, lp["router"], held, held=(lo, hi), top_k=2, scale=2.5, impl=impl))
    shared = moe.relu2_mlp(y, lp["shared"]["w_up"], lp["shared"]["w_down"])
    want = ref._experts(lp, y, ref_cfg(config, held=(0, 8)), None)
    np.testing.assert_allclose(parts[0] + parts[1] + shared, want, atol=2e-5)
    # and a share alone is the reference given the same share
    half = {**lp, "experts": {k: v[4:8] for k, v in lp["experts"].items()}}
    want = ref._experts(half, y, ref_cfg(config, held=(4, 8)), None)
    np.testing.assert_allclose(parts[1] + shared, want, atol=2e-5)


@pytest.mark.parametrize("impl", ["ragged", "dense"])
def test_no_token_is_dropped_when_every_token_chooses_one_expert(tiny, impl):
    """A bias that sends all 40 tokens to experts 2 and 3: both get 40 rows
    (a capacity would have cut them), and the result is each token's own
    two products, weighted."""
    config, lp, y = _layer(tiny)
    router = {"w": lp["router"]["w"],
              "bias": jnp.zeros((8,)).at[jnp.array([2, 3])].set(10.0)}
    out, counts = moe.routed_experts(
        y, router, lp["experts"], held=(0, 4), top_k=2, scale=2.5, impl=impl,
        counted=jnp.ones((40,), bool))
    assert counts.tolist()[:4] == [80, 80, 2, 40]  # "ragged" counts its blocks too
    scores = jax.nn.sigmoid(y @ lp["router"]["w"])[:, 2:4]
    w = 2.5 * scores / scores.sum(-1, keepdims=True)
    want = sum(w[:, i:i + 1] * moe.relu2_mlp(
        y, lp["experts"]["w_up"][2 + i], lp["experts"]["w_down"][2 + i])
        for i in range(2))
    np.testing.assert_allclose(out, want, atol=2e-5)


def _generate_all(engine, prompts, max_tokens):
    outs = [None] * len(prompts)

    def go(i):
        outs[i] = engine.generate(prompts[i], max_tokens=max_tokens,
                                  timeout=600)["tokens"]

    threads = [threading.Thread(target=go, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return outs


def test_a_slot_reused_after_retirement_gives_a_fresh_engines_run(tiny):
    """Seven requests over two slots, so every slot is reused with another
    request's state and pages still in it: each answer is what a fresh
    engine gives that request alone (prefill overwrites the slot's state;
    nothing is cleared at retirement)."""
    from ray_tpu.serve.llm import LLMEngine

    config, params = tiny
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 256, n).tolist() for n in (30, 7, 90, 3, 55, 64, 12)]
    kw = dict(decode_chunk=4, max_seq_len=256, prefill_buckets=[64, 128],
              page_size=PAGE)
    busy = LLMEngine(config, params, num_slots=2, **kw)
    try:
        got = _generate_all(busy, prompts, 13)
        stats = busy.stats()
    finally:
        busy.stop()
    assert stats["admitted"] == stats["retired"] == 7 and stats["state_slots"] == 2
    fresh = LLMEngine(config, params, num_slots=2, **kw)
    try:
        for p, tokens in zip(prompts, got):
            assert fresh.generate(p, max_tokens=13, timeout=600)["tokens"] == tokens
    finally:
        fresh.stop()


@pytest.fixture(scope="module")
def hybrid_engine(tiny):
    from ray_tpu.serve.llm import LLMEngine

    config, params = tiny
    eng = LLMEngine(config, params, num_slots=12, decode_chunk=4,
                    max_seq_len=128, prefill_buckets=[64], page_size=PAGE)
    eng.generate([5, 6, 7], max_tokens=5, timeout=600)  # meets the 64 bucket
    yield eng
    eng.stop()


@pytest.mark.parametrize("n,calls", GROUPS)
def test_prefill_rows_follow_the_group(hybrid_engine, n, calls):
    """As ``test_engine_tracing.py`` holds for Llama, through the one path
    both families take: the group's row count, pad rows into the trash page
    AND the trash state row, each request's answer its answer alone (the
    scan stops a row at its own length; the expert product is dropless), no
    compile after the bucket has been met."""
    check_rows_follow_the_group(hybrid_engine, n, calls, prompt_len=50)


def test_one_process_drives_the_engine_with_each_family(tiny):
    """The same loop, admission and counters for both families; what only
    one of them has reads zero for the other."""
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import LLMDeployment, LLMEngine, model_presets

    config, params = tiny
    hybrid = LLMEngine(config, params, num_slots=3, decode_chunk=4,
                       max_seq_len=128, prefill_buckets=[64], page_size=PAGE)
    llama = LLMEngine(LlamaConfig.tiny(dtype=jnp.float32, remat=None,
                                       attention_impl="reference"),
                      num_slots=3, decode_chunk=4, max_seq_len=128,
                      prefill_buckets=[64], page_size=PAGE)
    try:
        prompt = list(range(1, 20))
        a = hybrid.generate(prompt, max_tokens=9, timeout=600)["tokens"]
        b = llama.generate(prompt, max_tokens=9, timeout=600)["tokens"]
        assert len(a) == len(b) == 9
        want = ref.make_greedy_fn(ref_cfg(config))
        seq = np.zeros((32,), np.int32)
        seq[:19] = prompt
        for i, tok in enumerate(a):
            assert int(want(params, seq, np.int32(19 + i))) == tok
            seq[19 + i] = tok
        hs, ls = hybrid.stats(), llama.stats()
    finally:
        hybrid.stop()
        llama.stop()
    assert hs["state_slots"] == 3 and ls["state_slots"] == 0
    # a slot: float32 state [H, P, N] and 3 rows of the convolution's input,
    # a Mamba layer; 3 slots and the trash row
    per_slot = config.count("M") * 4 * (
        config.mamba_inner * config.ssm_state_size + 3 * config.conv_channels)
    assert hs["state_bytes"] == 4 * per_slot and ls["state_bytes"] == 0
    assert hs["kv_bytes_per_token"] == config.count("*") * 2 * 2 * 32 * 4
    assert ls["kv_bytes_per_token"] == 2 * 2 * 2 * 32 * 2  # L, k+v, heads, D, bf16
    # 8 decoded tokens (the first comes from prefill), in chunks of 4, top-2
    assert hs["moe_assignments"] == 2 * 8 * config.count("E")
    assert 0 < hs["moe_experts_touched"] <= hs["moe_assignments_held"] \
        <= hs["moe_assignments"]
    # one slot: an expert holds one token at most, in 8 ticks of one layer
    assert 0 < hs["moe_expert_load_max"] <= 8 * config.count("E")
    assert hs["moe_experts_touched"] == hs["moe_assignments_held"]
    assert ls["moe_assignments"] == ls["moe_expert_load_max"] == 0
    assert set(model_presets()) >= {"tiny", "nemotron_h_tiny"}
    with pytest.raises(ValueError, match="unknown model"):
        LLMDeployment(model="nemotron_h_huge")


def test_a_llama_replica_imports_no_other_family():
    """``_model_of`` finds a Llama configuration's programs without importing
    ``models/nemotron_h.py``, ``ops/ssm.py`` or ``ops/moe.py`` (they cost a
    Mistral replica's set-up nothing), and finds another family's module
    where its configuration class lives."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from ray_tpu.models.llama import LlamaConfig\n"
        "from ray_tpu.serve.llm import _model_of, model_presets\n"
        "assert _model_of(LlamaConfig.tiny()).__name__ == "
        "'ray_tpu.models.paged_decode'\n"
        "model_presets()\n"
        "loaded = [m for m in ('ray_tpu.models.nemotron_h', 'ray_tpu.ops.ssm', "
        "'ray_tpu.ops.moe') if m in sys.modules]\n"
        "assert not loaded, loaded\n"
        "hybrid = model_presets()['nemotron_h_tiny']()\n"
        "assert _model_of(hybrid).__name__ == 'ray_tpu.models.nemotron_h'\n")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
