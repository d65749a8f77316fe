"""Compile the main path's programs for a v5e that is described, not attached.

The TPU compiler is installed with jax and compiles for a topology
description (``jax.experimental.topologies``), so what Mosaic or XLA:TPU
would refuse on the chip is refused here, on the CPU, in tier-1: a kernel
that cannot be partitioned, a slice off the tiling, a program over the
device's memory. Nothing runs, so nothing here is a device number.

Shapes are the ones ``chip_smoke.py`` uses at ``LlamaConfig.llama_1b``
widths; depth is cut where it only repeats a scanned layer.
"""

import dataclasses
import functools
import json
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from ray_tpu.models import paged_decode as pd
from ray_tpu.models.llama import LlamaConfig, llama_init
from ray_tpu.ops import grouped_matmul
from ray_tpu.ops.attention import (DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, _flash_bwd,
                                   flash_attention)

B, S, HQ, HKV, D = 8, 2048, 16, 4, 128
SLOTS, PAGE, POOL_PAGES, TABLE_PAGES, CHUNK = 64, 64, 64 * 8 + 1, 32, 32


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu in this installation
        pytest.skip(f"cannot describe a v5e topology: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep it out
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # the grouped expert product as a TPU compiles it, not interpreted as
    # this host's default backend would have it
    monkeypatch = pytest.MonkeyPatch()
    monkeypatch.setattr(grouped_matmul, "INTERPRET", False)
    yield topo
    monkeypatch.undo()
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _llama_1b(layers, **kw):
    return dataclasses.replace(
        LlamaConfig.llama_1b(max_seq_len=S, **kw), num_layers=layers)


def _mistral_7b(layers):
    """Mistral-7B-v0.3's published widths, ``layers`` of its 32 layers."""
    return LlamaConfig(
        vocab_size=32768, hidden_size=4096, intermediate_size=14336,
        num_layers=layers, num_heads=32, num_kv_heads=8, head_dim=128,
        max_seq_len=4096, rope_theta=1e6, attention_impl="flash")


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("passes,kernels", [("forward", 1),
                                            ("forward_backward", 2)])
def test_flash_attention_compiles(v5e, passes, kernels):
    one = SingleDeviceSharding(v5e.devices[0])
    q = jax.ShapeDtypeStruct((B, S, HQ, D), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((B, S, HKV, D), jnp.bfloat16, sharding=one)

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    fn = flash_attention if passes == "forward" \
        else jax.grad(loss, argnums=(0, 1, 2))  # the forward + ONE backward pass
    assert _compiled_text(fn, q, kv, kv).count("tpu_custom_call") == kernels


def _decode_shapes(v5e, pool_pages=POOL_PAGES, config=None):
    """(config, arguments of the engine's decode program as shapes)."""
    one = SingleDeviceSharding(v5e.devices[0])
    config = config or _llama_1b(2, attention_impl="flash")
    params = _on(one, jax.eval_shape(lambda k: llama_init(config, k),
                                     jax.random.key(0)))
    cache = _on(one, jax.eval_shape(
        lambda: pd.init_paged_cache(config, pool_pages, PAGE)))
    ints = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one)
    active = jax.ShapeDtypeStruct((SLOTS,), jnp.bool_, sharding=one)
    table = jax.ShapeDtypeStruct((SLOTS, TABLE_PAGES), jnp.int32, sharding=one)
    key = _on(one, jax.eval_shape(lambda: jax.random.key(0)))
    return config, (params, cache, ints, ints, active, table, key)


def _prefill_shapes(v5e, bucket, pool_pages=POOL_PAGES, rows=8, config=None):
    one = SingleDeviceSharding(v5e.devices[0])
    config = config or _llama_1b(2, attention_impl="flash")
    params = _on(one, jax.eval_shape(lambda k: llama_init(config, k),
                                     jax.random.key(0)))
    cache = _on(one, jax.eval_shape(
        lambda: pd.init_paged_cache(config, pool_pages, PAGE)))
    tokens = jax.ShapeDtypeStruct((rows, bucket), jnp.int32, sharding=one)
    pages = jax.ShapeDtypeStruct((rows, bucket // PAGE), jnp.int32, sharding=one)
    lengths = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one)
    return config, (params, cache, tokens, pages, lengths)


def test_paged_decode_chunk_holds_the_kernel(v5e):
    """The engine's decode program, built the way the engine builds it on a
    TPU (use_kernel=True), without patching what jax thinks the backend is."""
    config, args = _decode_shapes(v5e)
    decode = pd.make_paged_decode_fn(config, CHUNK, PAGE, use_kernel=True)
    lowered = decode.lower(*args)
    assert "tpu_custom_call" in lowered.as_text()
    assert "tpu_custom_call" in lowered.compile().as_text()
    gather = pd.make_paged_decode_fn(config, CHUNK, PAGE, use_kernel=False)
    assert "tpu_custom_call" not in gather.lower(*args).as_text()


def _mosaic_calls(text):
    """Names of a compiled program's Mosaic kernel instructions."""
    return re.findall(
        r"%?([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)


def _scoped_vmem_asks(text, name):
    """What each Mosaic call whose name starts ``name`` asks of VMEM, bytes
    (its ``vmem_limit_bytes``, as the compiled instruction carries it)."""
    return [int(size) for size in re.findall(
        r"%?" + name + r"[\w.\-]* = [^\n]*custom_call_target=\"tpu_custom_call\""
        r"[^\n]*?\"scoped_memory_configs\":\[\{[^}]*\"size\":\"(\d+)\"", text)]


# (rows, q heads, KV heads, the q heads a grid step walks): Olmo-Hybrid's
# context, 30 heads of a group of one; Mistral-7B's own, a group of 4 whose
# dQ (136 MB) is over ``BWD_DQ_VMEM_BYTES`` and goes in two parts of two;
# Mellum's group of 8 at the same rows, four parts of two
CONTEXT_CASES = [(65536, 30, 30, 1), (32768, 32, 8, 2), (32768, 32, 4, 2)]


@pytest.mark.parametrize("rows,hq,hkv,heads", CONTEXT_CASES)
def test_flash_backward_alone_compiles_at_a_models_context(v5e, rows, hq, hkv,
                                                           heads):
    """The one backward pass by itself at one row of a model's context. The
    pair of kernels it replaced (PR 58) could not compile 65,536 rows (q, dO
    and two lane-padded float32 columns of a head whole in VMEM: 200 MB): K,
    V, q and dO stream a block a pair, and what is held whole is the dQ of
    the q heads a grid step walks, float32 scratch and the bfloat16 output
    block twice. That is the whole group where it fits
    ``BWD_DQ_VMEM_BYTES`` and an equal part of it where it does not, so the
    ask does not grow with the group: 80.5 MB at 65,536 rows x 1 head and
    at 32,768 x 2, of a v5e's 128 MiB, and Mosaic takes it."""
    shape = functools.partial(jax.ShapeDtypeStruct,
                              sharding=SingleDeviceSharding(v5e.devices[0]))
    q = shape((1, rows, hq, 128), jnp.bfloat16)
    kv = shape((1, rows, hkv, 128), jnp.bfloat16)
    lse = shape((1, hq, rows, 1), jnp.float32)

    def backward(q, k, v, out, lse, g):
        return _flash_bwd(q, k, v, out, lse, g, True, 128 ** -0.5,
                          DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K, False)

    text = _compiled_text(backward, q, kv, kv, q, lse, q)
    assert len(_mosaic_calls(text)) == 1
    asks = _scoped_vmem_asks(text, "")
    assert asks == [heads * 128 * (rows * (4 + 2 * 2) + 4 * 512 * 2)
                    + 16 * 2 ** 20]
    assert asks[0] < 100 * 2 ** 20
    # a group in parts: (batch, KV heads x parts, rows, 128) float32 leave
    # the call and are summed over the parts; a whole group: bfloat16
    parts = hq // hkv // heads
    assert (f"f32[1,{hkv * parts},{rows},128]" in text) == (parts > 1)


def _metric_params(name):
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks", "metrics",
                        name + ".json")
    with open(path) as f:
        return json.load(f)["params"]


def test_the_fused_backward_is_read_whole_or_not_at_all(v5e):
    """A profile names an operation by its instruction's text. The readers
    that found the PAIR of backward kernels count half of the five products
    a match (``flash_bwd_roofline``, ``flash_full_bwd_roofline.train``,
    ``flash_window_bwd_roofline``; ``attn_train_share`` names the windowed
    pair): against the ONE pass they must read nothing, because half the
    products over the whole pass's time is half of the truth. The first
    result is bfloat16 (dK), so none of them matches; PR 58's two entries
    match exactly the backward."""
    one = SingleDeviceSharding(v5e.devices[0])
    q = jax.ShapeDtypeStruct((2, 4096, 32, 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((2, 4096, 8, 128), jnp.bfloat16, sharding=one)

    def results(window):
        """(the forward's, the backward's) result text, as a profile has it
        after the call's name."""
        def loss(q, k, v):
            return flash_attention(q, k, v, window=window).astype(jnp.float32).sum()
        text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
        found = [line.split(" = ", 1)[1].split("custom-call(")[0]
                 for line in text.splitlines() if "tpu_custom_call" in line]
        assert len(found) == 2, found
        return found

    # under the names the steps' scopes give the calls (the two families'
    # step tests below hold those), the operands with their types (the
    # compiled text leaves those out: ``matches`` tries a first operand of
    # either kind, an array and a scalar-prefetch table)
    full, windowed = results(None), results(1024)

    def matches(metric, key, op):
        hits = {re.search(_metric_params(metric)[key], op + "custom-call(" + first)
                is not None
                for first in ("bf16[2,32,4096,128]{3,2,1,0} %x, ", "s32[144]{0} %t, ")}
        assert len(hits) == 1, (metric, op)
        return hits.pop()

    forward, backward = "checkpoint.21 = " + full[0], "checkpoint.22 = " + full[1]
    assert full[1].startswith("(bf16[2,8,4096,128]"), backward
    assert matches("flash_fwd_roofline", "pattern", forward)
    assert not matches("flash_fwd_roofline", "pattern", backward)
    for key in ("pattern", "count_pattern"):
        assert not matches("flash_bwd_roofline", key, backward)
        assert matches("flash_bwd_fused_roofline", key, backward)
        assert not matches("flash_bwd_fused_roofline", key, forward)
    backward = "attn_full.41 = " + full[1]
    assert not matches("flash_full_bwd_roofline.train", "pattern", backward)
    assert matches("attn_train_share", "ops", backward)
    forward = "flash_window_fwd.9 = " + windowed[0]
    backward = "flash_window_bwd.9 = " + windowed[1]
    assert not matches("flash_window_bwd_roofline", "pattern", backward)
    assert not matches("attn_train_share", "ops", backward)
    assert matches("flash_window_bwd_fused_train_share", "ops", backward)
    assert not matches("flash_window_bwd_fused_train_share", "ops", forward)


def _without_combines(calls):
    """(the calls that are not ``ops/rows_to_tokens.py``'s kernel, those that
    are): it keeps its name, ``rows-to-tokens``, in a compiled program."""
    combines = [c for c in calls if c.startswith("rows-to-tokens")]
    return [c for c in calls if c not in combines], combines


def _without_token_writes(calls):
    """(the calls that are not ``ops/token_rows.py``'s kernel, those that
    are): it keeps its name, ``token_rows_write``, in a compiled program."""
    writes = [c for c in calls if c.startswith("token_rows_write")]
    return [c for c in calls if c not in writes], writes


def _pool_scatters(text):
    """The scatters of a compiled program, inside a fusion or not, whose
    result (and so whose operand) is a page pool (``[n_kv, pages, 64, D]`` in
    bfloat16): XLA's token or prompt write, which ``token_rows_write``
    replaced in the decode programs that run the kernels (PR 53)."""
    return re.findall(r"= bf16\[\d+,\d+,64,\d+\]\S* scatter\(.*", text)


def _row_scatters(text, h):
    """The scatters of a compiled program whose operand is float32 rows of
    ``h``: XLA's row scatter-add of an expert block onto its tokens, which
    ``rows-to-tokens`` replaced (PR 48)."""
    return re.findall(rf"= f32\[\d+,{h}\]\S* scatter\(.*", text)


@pytest.mark.parametrize("family", ["llama", "hybrid"])
def test_decode_attention_kernel_compiles_and_keeps_its_name(v5e, family):
    """The repo's kernel (``ops/paged_attention.py``) inside the decode
    program of each family at its benchmark cell's head counts and slots
    (32/8 heads, 64 slots; 32/2 heads, 128 slots): Mosaic takes it, and the
    compiled instruction is named ``paged_attention.N``. The benchmark's
    readers find the operation, and the Llama decode program by it, under
    ``^paged_attention`` in a profile (PERF.md 3, Kernels)."""
    if family == "llama":
        config, args = _decode_shapes(v5e, config=_mistral_7b(2))
        decode = pd.make_paged_decode_fn(config, 8, PAGE, use_kernel=True)
        text = decode.lower(*args).compile().as_text()
    else:
        text = _hybrid_decode(v5e, 128)[1].as_text()
    calls, writes = _without_token_writes(_mosaic_calls(text))
    assert calls and all(c.startswith("paged_attention") for c in calls), calls
    # the Llama-shaped family writes a tick's rows through the row writer
    # (PR 53); the others keep their scatters, and their programs' text
    assert len(writes) == (1 if family == "llama" else 0), writes
    assert bool(_pool_scatters(text)) == (family != "llama")


def test_prefill_bucket_compiles(v5e):
    config, args = _prefill_shapes(v5e, 512)
    prefill = pd.make_paged_prefill_fn(config, PAGE)
    assert "tpu_custom_call" in prefill.lower(*args).compile().as_text()


def test_one_row_prefill_of_the_largest_bucket_compiles(v5e):
    """The engine compiles a bucket's prefill program at each row count of
    ``serve.llm.PREFILL_ROWS`` (PR 30). This is the smaller, 1 x 2048, at
    Mistral-7B-v0.3's published widths (two of its layers: the rest repeat
    them). Its temporaries, noted from this compile (no device number):
    93,622,784 bytes since PR 45 (135,153,152 until then: the head ran over
    every position), where the 8-row program of the same bucket, which the
    engine ran for every group before PR 30, took 2.42 GB (PERF.md 4)."""
    config = _mistral_7b(2)
    _, args = _prefill_shapes(v5e, 2048, rows=1, config=config)
    compiled = pd.make_paged_prefill_fn(config, PAGE).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2.42e9 / 8
    assert mem.alias_size_in_bytes == _pool_bytes(args[1])


def test_four_row_prefill_keeps_the_flash_call_and_no_logits_of_the_bucket(v5e):
    """The 4 x 2048 program (PR 45): the head runs over each prompt's last
    row, so no ``[4, 2048, vocabulary]`` logits are in it, lowered or
    compiled; attention is still ONE flash call over the bucket whose output
    and first operand are ``bf16[4, ...]``, the line the benchmark's
    ``prefill_device_per_call`` takes a call's rows from (``rows_from`` in
    its metric file: a profile prints an operand's shape in front of its
    name; a ragged call's scalar prefetch would put ``s32[4]`` first)."""
    config = _mistral_7b(2)
    _, args = _prefill_shapes(v5e, 2048, rows=4, config=config)
    lowered = pd.make_paged_prefill_fn(config, PAGE).lower(*args)
    compiled = lowered.compile().as_text()
    assert "4x2048x32768xf32" not in lowered.as_text()
    assert "f32[4,2048," not in compiled
    flash = [line for line in compiled.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(flash) == 1
    assert re.search(r"= bf16\[4,32,2048,128\]\S* custom-call\(", flash[0])
    assert "operand_layout_constraints={bf16[4,32,2048,128]" in flash[0]


def _pool_bytes(cache):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))


def _pool_sized_moves(text, elements):
    """Lines of a compiled program whose ``copy``, ``dynamic-slice`` or
    ``dynamic-update-slice`` (inside a fusion or not) yields ``elements`` or
    more: a pool layer, or the pool, moved or re-laid."""
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]+)\]\S* "
                     r"(?:copy|dynamic-slice|dynamic-update-slice)\(", line)
        if m and math.prod(int(d) for d in m.group(1).split(",")) >= elements:
            found.append(line.strip()[:160])
    return found


def _compile_decode(v5e, pool_pages):
    """(compiled decode program, the pool's bytes, one layer's elements)."""
    config, args = _decode_shapes(v5e, pool_pages)
    decode = pd.make_paged_decode_fn(config, CHUNK, PAGE, use_kernel=True)
    layer = config.num_kv_heads * pool_pages * PAGE * config.head_dim_
    return decode.lower(*args).compile(), _pool_bytes(args[1]), layer


def test_decode_program_never_moves_a_pool_layer(v5e):
    """The pool is one array in the kernel's own layout and the token write
    updates it in place: what a decode step costs follows what it touches,
    not ``total_pages``. With the pool a 5-D scan carry the temporaries grew
    by 1.3 bytes a pool byte (slice, re-layout and write-back of a layer,
    for K and for V, a layer a step)."""
    small, small_pool, small_layer = _compile_decode(v5e, POOL_PAGES)
    big, big_pool, _ = _compile_decode(v5e, 2 * POOL_PAGES - 1)
    grown = (big.memory_analysis().temp_size_in_bytes
             - small.memory_analysis().temp_size_in_bytes)
    assert abs(grown) < 0.05 * (big_pool - small_pool)
    assert _pool_sized_moves(small.as_text(), small_layer) == []
    assert small.memory_analysis().alias_size_in_bytes == small_pool
    assert big.memory_analysis().alias_size_in_bytes == big_pool
    # K's and V's rows through ONE call of the row writer in the layer scan
    assert len(_without_token_writes(_mosaic_calls(small.as_text()))[1]) == 1
    assert _pool_scatters(small.as_text()) == []


def test_token_write_with_a_window_over_heads_relays_the_pool(v5e, monkeypatch):
    """Why ``_scatter_token_rows`` indexes the heads too: the shorter form
    gives the scatter a [n_kv, D] window, XLA:TPU then keeps n_kv next to D
    in the operand's layout, and the whole pool is re-laid around every
    write for the kernel, which reads row-major. Since PR 53 the scatter is
    the write only of a pool the row writer does not tile (and of a program
    without kernels): such a pool is feigned here, beside the attention
    kernel; the form as it stands moves nothing there."""

    def window_over_heads(pool, rows, pages, rownum):
        return pool.at[:, pages, rownum].set(
            rows.transpose(1, 0, 2).astype(pool.dtype))

    monkeypatch.setattr(pd, "_row_writer_fits", lambda pools, slots: False)
    compiled, _, layer = _compile_decode(v5e, POOL_PAGES)
    assert _pool_scatters(compiled.as_text())
    assert _pool_sized_moves(compiled.as_text(), layer) == []
    monkeypatch.setattr(pd, "_scatter_token_rows", window_over_heads)
    compiled, _, layer = _compile_decode(v5e, POOL_PAGES)
    assert _pool_sized_moves(compiled.as_text(), layer)


def test_prefill_temporaries_do_not_grow_with_the_pool(v5e):
    temps, pools = [], []
    for pool_pages in (POOL_PAGES, 2 * POOL_PAGES - 1):
        config, args = _prefill_shapes(v5e, 512, pool_pages)
        mem = pd.make_paged_prefill_fn(config, PAGE).lower(
            *args).compile().memory_analysis()
        pools.append(_pool_bytes(args[1]))
        assert mem.alias_size_in_bytes == pools[-1]
        temps.append(mem.temp_size_in_bytes)
    assert abs(temps[1] - temps[0]) < 0.05 * (pools[1] - pools[0])


def test_fsdp4_train_step_compiles_with_flash(v5e):
    """Failed to lower before the flash call sat in a shard_map: ``Mosaic
    kernels cannot be automatically partitioned``."""
    from ray_tpu.parallel.mesh import MeshConfig, batch_sharding_spec, make_mesh
    from ray_tpu.parallel.sharding import DEFAULT_LLM_RULES
    from ray_tpu.train.step import (
        TrainState, _state_shardings, default_optimizer, make_train_step,
        state_logical_axes,
    )

    mesh = make_mesh(MeshConfig(fsdp=4), devices=v5e.devices)
    config = _llama_1b(2, remat="save_attn", attention_impl="flash")
    opt = default_optimizer()
    shardings = _state_shardings(
        state_logical_axes(config, opt), mesh, DEFAULT_LLM_RULES)

    def init(key):
        params = llama_init(config, key)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=opt.init(params))

    state = jax.tree.map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        jax.eval_shape(init, jax.random.key(0)), shardings)
    batch = jax.ShapeDtypeStruct(
        (B, S), jnp.int32, sharding=NamedSharding(mesh, batch_sharding_spec()))
    compiled = make_train_step(config, opt, mesh=mesh).lower(
        state, batch, batch).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2  # the forward, the ONE backward
    assert "all-gather" in text  # fsdp: weights gathered per layer
    # each device holds a quarter of the state, not all of it
    whole = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device < 0.3 * whole


# --------------------------------------------------------------------------- #
# The second family through the engine (PR 29)
# --------------------------------------------------------------------------- #
LLAMA_TINY_DECODE_SHA = \
    "3e0c3bbed245a7be87be0ddfd5e9baf18ef9a373654b22d9037bac8ade43f895"


def test_llama_decode_program_is_what_it_was_before_the_second_family():
    """``LLMEngine`` builds its programs through ``_model_of`` since PR 29.
    The Llama decode program it lowers is the text the parent commit lowered
    (the hash was taken on both trees; at the benchmark's rehearsal widths
    decode and prefill were compared too, equal). It depends on the jax that
    lowers it, so another version skips. PR 34 moved the hash in place: tiny
    widths run the GATHER path, whose lengths are 0 for an inactive slot and
    whose output is zeros there since then (``_live_lengths``). PR 53 moved
    it again (until then 92f77e847eca75f025ee282bd1c8d85a9051395468b808526443d554492f9549):
    K's and V's rows go to ``_write_token_rows`` in one call, so V's slice
    is traced before K's scatter and no longer after it, and K's product is
    float32 into the rotary and rounded once after it (what XLA:TPU made of
    it before); the same two scatters on the same operands."""
    import hashlib

    from ray_tpu.serve.llm import LLMEngine

    if jax.__version__ != "0.9.0":
        pytest.skip(f"the hash was taken under jax 0.9.0, not {jax.__version__}")
    engine = LLMEngine(LlamaConfig.tiny(attention_impl="reference"),
                       num_slots=8, decode_chunk=4, max_seq_len=256,
                       prefill_buckets=[128])
    try:
        text = engine.decode_program_text()
    finally:
        engine.stop()
    assert hashlib.sha256(text.encode()).hexdigest() == LLAMA_TINY_DECODE_SHA


def _hybrid_decode(v5e, slots):
    """The hybrid family's decode program at published widths (one layer of
    each kind and a Mamba layer more, 8 experts held, a slice of the
    vocabulary, so that it compiles in seconds) for ``slots`` slots."""
    from ray_tpu.models import nemotron_h as nh

    one = SingleDeviceSharding(v5e.devices[0])
    # the expert layer as the benchmark's cell cuts it: 64 of the router's 128
    config = nh.NemotronHConfig(
        pattern="ME*M", n_routed_experts=64, held_experts=(0, 64),
        vocab_size=8192, attention_impl="flash")
    params = _on(one, jax.eval_shape(lambda k: nh.init_params(config, k),
                                     jax.random.key(0)))
    cache = _on(one, jax.eval_shape(
        lambda: nh.init_cache(config, slots, POOL_PAGES, PAGE)))
    ints = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
    active = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one)
    table = jax.ShapeDtypeStruct((slots, TABLE_PAGES), jnp.int32, sharding=one)
    key = _on(one, jax.eval_shape(lambda: jax.random.key(0)))
    decode = nh.make_paged_decode_fn(config, 8, PAGE, use_kernel=True)
    compiled = decode.lower(params, cache, ints, ints, active, table, key).compile()
    return config, compiled, cache


def test_hybrid_decode_updates_pool_and_state_in_place(v5e):
    """Pages and per-slot Mamba state ride one donated cache: the compiled
    program aliases all of it, holds the paged-attention kernel, and neither
    copies nor re-lays anything the size of one layer's state; its
    temporaries do not grow with the state when the slots double."""
    config, small, cache = _hybrid_decode(v5e, 64)
    _, big, big_cache = _hybrid_decode(v5e, 128)
    state_layer = 64 * config.mamba_inner * config.ssm_state_size
    text = small.as_text()
    assert "tpu_custom_call" in text
    copies = [line.strip()[:160] for line in text.splitlines() if (
        m := re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]+)\]\S* "
                      r"(?:copy|transpose)\(", line))
        and math.prod(int(d) for d in m.group(1).split(",")) >= state_layer]
    assert copies == []
    assert small.memory_analysis().alias_size_in_bytes >= _pool_bytes(cache)
    grown = (big.memory_analysis().temp_size_in_bytes
             - small.memory_analysis().temp_size_in_bytes)
    assert grown < 0.1 * (_pool_bytes(big_cache) - _pool_bytes(cache))


# --------------------------------------------------------------------------- #
# PR 35: a window in the shared kernels, and the third family
# --------------------------------------------------------------------------- #
def _without_locations(text):
    """Lowered text with every Mosaic kernel's serialized module replaced by
    its assembly WITHOUT source locations: the bytecode holds file:line of
    the kernel's source, which moves whenever a line is added above it."""
    import base64

    from jax._src import tpu_custom_call  # noqa: F401 - registers the dialect
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    def assembly(found):
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True  # "stable_mosaic"
        tpu.register_dialect(ctx)
        with ctx:
            module = ir.Module.parse(base64.b64decode(found.group(1)))
            return module.operation.get_asm(enable_debug_info=False)

    return re.sub(r"\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22", assembly, text)


# sha256 of ``_without_locations(lowered text)``, taken on the parent commit
# of PR 35 (a7e571a) and equal on its tree, under jax 0.9.0. The hybrid pair
# was taken again on the parent commit of PR 37 (fe76e13) with this file's
# configuration of that PR, which holds 64 experts of 128 as the benchmark's
# cell does (it held 8 of 128, a share ``ops/moe.py`` now compacts), and is
# equal on PR 37's tree
ACCEPTED_PROGRAMS_SHA = {
    # taken again on PR 53's tree, which means to change this program and no
    # other: the Llama-shaped family's decode tick writes its K and V rows
    # through ``ops/token_rows.py``'s kernel and no longer through
    # ``_scatter_token_rows``' two scatters, and K's product reaches the
    # rotary in float32 (the hash until then:
    # 4faf3387d777c75cad44fe924f5ee1365a0fbabc2bb83479003e2a9b50fd3725).
    # Every other family's decode program calls the scatter as it did and
    # stands as it was
    "llama_decode": 
        "b4f022d54764f597905375d8ff080be8d043dfba860da3f0aed9980c3d81cfd2",
    # taken again on PR 45's tree, which means to change this program: the
    # row-wise work walks a prompt in pieces and skips those past its length,
    # the head runs over the last row (the hash of PR 35's parent
    # until then: 52db68d74f15ab0f4793dc6ead0131a2a4ead966c0a9916a16433e6430790d9c)
    "llama_prefill":
        "56b64beacc9154f146f8b1a8da0aa5e62f924fd7ae92fae43f0858d9b65dbdaf",
    "hybrid_decode": 
        "f10679f1eeeb5ec1bfe2bc568c804d4679e7872e9bb464c87d2a1e95c255fedc",
    # the four programs that run a grouped expert product were taken again on
    # PR 47's tree, which means to change them: the product is
    # ``ops/grouped_matmul.py``'s Pallas kernel and no longer ``lax.ragged_dot``
    # (the hashes until then: hybrid_prefill 7aa2520cbdeae4f4b4180246a934686474e3e9e6e42299a5f5bf1400c3b313c7,
    # laguna_decode 373b08f3cc3b766fdc496f79d802db901e990b84f58b47925d5908068f7daca8,
    # laguna_prefill 3e289e0cde0ba08fe7ac7fa176851ce1125cd6f0259b31ee1a7bef23c84592e7,
    # kimi_k2_prefill b7f872f96286a46d66093e5ada82ae7e7fab520ab104a9138ca1cddfe0ea87b4).
    # The decode programs that multiply every held expert (``_dense``) and
    # everything without experts stand as they were
    "hybrid_prefill":
        "2db757e59e520551b8312ed20a85fef6376b7ce87e999ac39870e0045691a163",
    # taken again on PR 58's tree, which MEANS to change it and
    # ``train_4k_step`` below and no other: the flash backward is ONE Pallas
    # pass (``_flash_bwd_kernel``) and no longer the dQ and the dK/dV kernel
    # with the float32 per-head dK/dV summed outside; the forward inside
    # both is the kernel it was, as every served program's is (the hash
    # until then: f7e7ab589c6105498b819b990dbda5cda3c09b4307791981cd966bc05680969c)
    "flash_fwd_bwd":
        "43ac267fc8bd1eead36af6cc5c6be13dbb9f627b917bae31669384afee440013",
    # the window family, taken on the parent commit of PR 38 (e8140a7), whose
    # ring arithmetic PR 38 moved into models/paged_decode.py for the fourth
    # family to share
    "laguna_decode":
        "17cf42f26931dfe7f6026da714cbeb46ac8eb1314c9260d1e0921801a0e6efba",
    # the two served programs that run the COMPACTED product (a share under
    # a half: ``_compacted``) were taken again on PR 48's tree, which means
    # to change them: a block's rows reach their tokens through
    # ``ops/rows_to_tokens.py``'s kernel and no longer through XLA's row
    # scatter-add, and the forward's loop runs its body at least once (the hashes
    # until then: laguna_prefill 4a8333ce8d08809ce98370b6027536d0ac8ff3857b423b6b5eed91bd8703cd94,
    # kimi_k2_prefill be933e12300872caca90f81967c21985f71455d5df3d4c93eedb6099a1a49dfc).
    # ``hybrid_prefill`` (a half share) and ``laguna_decode`` (192 choices
    # under one block) run uncompacted and stand as they were, as does
    # everything else
    "laguna_prefill":
        "2a61cf13059123931f5c4a544a16545afbdd24d8954b11a76a6b302629b50a39",
    # taken on the parent commit of PR 46 (4d75b23) and equal on its tree:
    # the other two served families that call ``ops/moe.py`` or the flash
    # forward (a window, ``lengths``, a v width of its own), and ``train_4k``'s
    # step through the seam of ``train/step.py`` (Mistral-7B-v0.3, 4 layers,
    # 4 x 4096, ``save_attn``, the benchmark's optimizer)
    "phi4flash_decode":
        "66943e95e9f195e64e3b2540d6cd0ba7b2b1fb8979479c6958074c1180a02811",
    "phi4flash_prefill":
        "0b697671ae9327ec76b3b5f0ce640f90d2da713260a796e00cf39ddcfcca239b",
    "kimi_k2_decode":
        "e2f027d18fa901b132225c84a6c94ceab87d85f9b128477801a75be1e7ee7b51",
    "kimi_k2_prefill":
        "ca8fb536ba4b158f31a25d6746ec298545da3e50b5cb41a57cd46be15b5e2ee8",
    # taken again on PR 58's tree with ``flash_fwd_bwd`` above: the step
    # differentiates through the flash call (the hash of PR 46's parent until
    # then: 93be954fac40c8e84e755380ac97bd779def24c763d3786568e814f9cc165399)
    "train_4k_step":
        "7820e1cf7d10c97fd8a7af2caf0b0cacf0a9579521bc852b6b1fc75657994839",
}


def _train_step_lowered(v5e, config, rows, seq):
    """``train/step.py``'s step for ``config`` (its family's loss, found from
    the configuration's module) under the benchmark's optimizer, one chip,
    lowered for ``rows`` x ``seq`` tokens."""
    from ray_tpu.train.step import (
        TrainState, _family, default_optimizer, make_train_step)

    one = SingleDeviceSharding(v5e.devices[0])
    opt = default_optimizer(warmup_steps=10, total_steps=1000)

    def init(key):
        params = _family(config).init_params(config, key)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=opt.init(params))

    state = _on(one, jax.eval_shape(init, jax.random.key(0)))
    tokens = jax.ShapeDtypeStruct((rows, seq), jnp.int32, sharding=one)
    return make_train_step(config, opt).lower(state, tokens, tokens)


def _accepted_program(v5e, name):
    """The lowered text of one program of a family the benchmark had before
    the third, as its engine builds it on a TPU."""
    from ray_tpu.models import nemotron_h as nh

    one = SingleDeviceSharding(v5e.devices[0])
    shape = functools.partial(jax.ShapeDtypeStruct, sharding=one)
    if name.startswith("laguna"):
        return _laguna_lowered(
            v5e, bucket=8192 if name == "laguna_prefill" else None)[0]
    if name.startswith("phi4flash"):
        return _phi4flash_lowered(
            v5e, bucket=4096 if name == "phi4flash_prefill" else None)[0]
    if name.startswith("kimi_k2"):
        return _kimi_k2_lowered(
            v5e, bucket=8192 if name == "kimi_k2_prefill" else None)[0]
    if name == "train_4k_step":
        return _train_step_lowered(
            v5e, dataclasses.replace(_mistral_7b(4), remat="save_attn"), 4, 4096)
    if name == "llama_decode":
        config, args = _decode_shapes(v5e, config=_mistral_7b(2))
        return pd.make_paged_decode_fn(config, 8, PAGE, use_kernel=True).lower(*args)
    if name == "llama_prefill":
        config, args = _prefill_shapes(v5e, 2048, rows=1, config=_mistral_7b(2))
        return pd.make_paged_prefill_fn(config, PAGE).lower(*args)
    if name == "flash_fwd_bwd":
        q = shape((2, 4096, 32, 128), jnp.bfloat16)
        kv = shape((2, 4096, 8, 128), jnp.bfloat16)
        return jax.jit(jax.grad(
            lambda q, k, v: flash_attention(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))).lower(q, kv, kv)
    # the expert layer as the benchmark's cell cuts it: 64 of the router's 128
    config = nh.NemotronHConfig(
        pattern="ME*M", n_routed_experts=64, held_experts=(0, 64),
        vocab_size=8192, attention_impl="flash")
    params = _on(one, jax.eval_shape(lambda k: nh.init_params(config, k),
                                     jax.random.key(0)))
    cache = _on(one, jax.eval_shape(
        lambda: nh.init_cache(config, 128, POOL_PAGES, PAGE)))
    ints, key = shape((128,), jnp.int32), _on(one, jax.eval_shape(
        lambda: jax.random.key(0)))
    if name == "hybrid_decode":
        return nh.make_paged_decode_fn(config, 8, PAGE, use_kernel=True).lower(
            params, cache, ints, ints, shape((128,), jnp.bool_),
            shape((128, TABLE_PAGES), jnp.int32), key)
    four = shape((4,), jnp.int32)
    return nh.make_paged_prefill_fn(config, PAGE).lower(
        params, cache, shape((4, 512), jnp.int32),
        shape((4, 512 // PAGE), jnp.int32), four, four)


@pytest.mark.parametrize("name", sorted(ACCEPTED_PROGRAMS_SHA))
def test_accepted_programs_lower_to_the_parents_text(v5e, name):
    """``ops/paged_attention.py`` gained ``starts``, ``ops/attention.py`` a
    ``window``, ``ops/rope.py`` a partial head and YaRN, ``ops/moe.py`` a
    scoring and an expert form (PR 35), and a block that compacts a small
    share's assignments (PR 37: a half share's block holds every assignment);
    ``ops/ssm.py`` gained the selective scan beside the Mamba-2 pair and
    ``models/paged_decode.py`` the ring arithmetic that was Laguna's (PR 38).
``ops/attention.py``'s
    ``custom_vjp`` took the window, its backward kernels a window's loop
    bounds, ``ops/moe.py``'s compacted product became a ``custom_vjp`` with a
    reverse pass, and ``train/step.py`` asks the configuration's module for
    the loss it steps (PR 46). ``ops/moe.py``'s grouped products became
    ``ops/grouped_matmul.py``'s kernel (PR 47), which MEANS to change the four
    programs that run one; their hashes were taken again there. The compacted
    product's sums of rows onto tokens became ``ops/rows_to_tokens.py``'s
    kernel (PR 48), which means to change the two served programs that run
    the compacted product (Laguna's and Kimi's prefill) and no other. The
    flash backward became ONE pass (PR 58), which means to change the two
    programs that differentiate, ``flash_fwd_bwd`` and ``train_4k_step``.
    Called as the accepted families call
    them, they trace to what they were: the decode and prefill programs of
    the Llama-shaped and the hybrid family, and the flash forward and
    backward of training, lower to the text the parent commit lowered, with
    the Pallas kernels inside compared as assembly without source lines."""
    import hashlib

    if jax.__version__ != "0.9.0":
        pytest.skip(f"the hashes were taken under jax 0.9.0, not {jax.__version__}")
    text = _without_locations(_accepted_program(v5e, name).as_text())
    assert "tpu_custom_call" in text
    assert hashlib.sha256(text.encode()).hexdigest() == ACCEPTED_PROGRAMS_SHA[name]


def _laguna_lowered(v5e, slots=24, bucket=None):
    """The third family at Laguna-XS.2's published widths, a full and a
    sliding layer (query groups of 6 and 8, both rotary schemes, one expert
    layer of 32 held experts of the router's 256, a slice of the vocabulary):
    its decode program over ``slots`` slots, or its one-row prefill program
    of ``bucket``, lowered; and its cache."""
    from ray_tpu.models import laguna as lg

    one = SingleDeviceSharding(v5e.devices[0])
    shape = functools.partial(jax.ShapeDtypeStruct, sharding=one)
    config = lg.LagunaConfig(
        vocab_size=8192, layer_types=(lg.FULL, lg.SLIDING),
        mlp_layer_types=("dense", "sparse"),
        num_attention_heads_per_layer=(48, 64), num_experts=32,
        held_experts=(0, 32), max_seq_len=25600, attention_impl="flash")
    pages = 25600 // PAGE
    params = _on(one, jax.eval_shape(lambda k: lg.init_params(config, k),
                                     jax.random.key(0)))
    cache = _on(one, jax.eval_shape(
        lambda: lg.init_cache(config, slots, slots * pages + 1, PAGE)))
    if bucket:
        lowered = lg.make_paged_prefill_fn(config, PAGE).lower(
            params, cache, shape((1, bucket), jnp.int32),
            shape((1, bucket // PAGE), jnp.int32), shape((1,), jnp.int32),
            shape((1,), jnp.int32))
    else:
        ints = shape((slots,), jnp.int32)
        lowered = lg.make_paged_decode_fn(config, 8, PAGE, use_kernel=True).lower(
            params, cache, ints, ints, shape((slots,), jnp.bool_),
            shape((slots, pages), jnp.int32),
            _on(one, jax.eval_shape(lambda: jax.random.key(0))))
    return lowered, cache


def _laguna(v5e, slots=24, bucket=None):
    lowered, cache = _laguna_lowered(v5e, slots, bucket)
    return lowered.compile(), cache


def test_laguna_decode_holds_both_kernels_and_moves_no_pool(v5e):
    """Mosaic takes the paged-attention kernel at a query group of 6 and, with
    ``starts``, at 8 over a ring of 9 pages; a profile tells the two calls
    apart by name (the benchmark's ``full_attn_decode_roofline`` and
    ``window_attn_decode_roofline`` read them so); pages and rings ride one
    donated cache that the program aliases and never copies."""
    compiled, cache = _laguna(v5e)
    calls = [c for c in _mosaic_calls(compiled.as_text())
             if "ragged" not in c]  # the grouped expert products' kernel
    # (192 choices under one block run uncompacted: no ``rows-to-tokens``)
    assert sorted(c.split(".")[0] for c in calls) == [
        "paged_attention", "paged_attention_window"], calls
    assert compiled.memory_analysis().alias_size_in_bytes == _pool_bytes(cache)
    moved = [line for line in _pool_sized_moves(compiled.as_text(),
                                                cache.k_win.size)
             if re.search(r"= bf16\[8,\d+,64,128\]", line)]  # a pool's shape
    assert moved == []


def test_laguna_prefill_of_the_longest_bucket_compiles(v5e):
    """One row of 24,576 tokens: K and V of a (row, KV head) are 25 MB in
    VMEM, double buffered, so the flash forward asks for more than Mosaic's
    default scoped VMEM (``ops/attention.py`` ``KV_VMEM_DEFAULT_BYTES``); the
    windowed call is named ``flash_window_fwd``. The routed experts take
    4,096 tokens at a time and an eighth of their 32,768 choices is held:
    the grouped products and everything a model wide around them run over a
    block of 8,192 sorted rows, and nothing float32 is left that is as tall
    as the choices (the parent's weighting, un-sorting and summing passes:
    4.2 ms of a chunk-layer's 5.05; PERF.md 6, PR 37)."""
    from ray_tpu.models import laguna as lg
    from ray_tpu.ops import moe

    compiled, _ = _laguna(v5e, bucket=24576)
    text = compiled.as_text()
    calls = [c for c in _mosaic_calls(text) if "ragged" not in c]
    calls, combines = _without_combines(calls)
    assert any(c.startswith("flash_window_fwd") for c in calls), calls
    assert len(calls) == 2
    # since PR 48 a block's rows reach their tokens through
    # ``ops/rows_to_tokens.py``'s kernel (ONE in the sparse layer's loop
    # over its blocks), and no row scatter is left
    assert len(combines) == 1, combines
    assert not _row_scatters(text, 2048)
    choices = lg.MOE_PREFILL_TOKENS * 8
    block = moe._capacity(choices, 32, 256)
    assert block == 8192
    # since PR 47 ``ops/grouped_matmul.py``'s kernel, under the name the
    # benchmark's metrics read
    assert re.search(rf"ragged-dot-rows\S* = f32\[{block},2048\]", text)
    assert "ragged-dot-none" not in text
    assert not re.search(rf"f32\[{choices},\d+\]", text)
    assert not re.search(rf"bf16\[{choices},\d+\]", text)


# --------------------------------------------------------------------------- #
# PR 38: the fourth family, a selective scan and one layer's pages for eight
# --------------------------------------------------------------------------- #
def _phi4flash_lowered(v5e, slots=24, bucket=None):
    """The fourth family at Phi-4-mini-flash-reasoning's published widths and
    EIGHT of its 32 layers, one of every kind in the published order (scan,
    window, scan, window, scan, full, memory unit, cross), a slice of the
    vocabulary: its decode program over ``slots`` slots, or its one-row
    prefill program of ``bucket``, lowered; and its cache."""
    from ray_tpu.models import phi4flash as pf

    one = SingleDeviceSharding(v5e.devices[0])
    shape = functools.partial(jax.ShapeDtypeStruct, sharding=one)
    config = pf.Phi4FlashConfig(
        vocab_size=8192, num_hidden_layers=8, max_seq_len=16896,
        attention_impl="flash", scan_impl="pallas")
    pages = 16896 // PAGE
    params = _on(one, jax.eval_shape(lambda k: pf.init_params(config, k),
                                     jax.random.key(0)))
    cache = _on(one, jax.eval_shape(
        lambda: pf.init_cache(config, slots, slots * pages + 1, PAGE)))
    if bucket:
        lowered = pf.make_paged_prefill_fn(config, PAGE).lower(
            params, cache, shape((1, bucket), jnp.int32),
            shape((1, bucket // PAGE), jnp.int32), shape((1,), jnp.int32),
            shape((1,), jnp.int32))
    else:
        ints = shape((slots,), jnp.int32)
        lowered = pf.make_paged_decode_fn(config, 8, PAGE, use_kernel=True).lower(
            params, cache, ints, ints, shape((slots,), jnp.bool_),
            shape((slots, pages), jnp.int32),
            _on(one, jax.eval_shape(lambda: jax.random.key(0))))
    return lowered, cache


def _phi4flash(v5e, slots=24, bucket=None):
    lowered, cache = _phi4flash_lowered(v5e, slots, bucket)
    return lowered.compile(), cache


def test_phi4flash_decode_holds_its_kernels_and_moves_no_pool_or_state(v5e):
    """Mosaic takes the paged-attention kernel at packed rows (10 KV pairs
    of 128, a query group of 4) over the ONE layer's pages, once for the
    layer that keeps them and once for the cross layer that reads them, and
    with ``starts`` over the rings; and the one-token scan, a slot a grid
    step, over the array of every layer's state. A profile tells the three
    apart by name (the benchmark's ``shared_kv_decode_roofline``,
    ``window_attn_decode_roofline.phi`` and ``selective_scan_step_roofline``
    read them so). Pages, rings, scan state and convolution rows ride one
    donated cache that the program aliases and never copies: nothing the
    size of a layer's scan state (25 slots x 320 KB) is moved either."""
    compiled, cache = _phi4flash(v5e)
    calls = sorted(c.split(".")[0] for c in _mosaic_calls(compiled.as_text()))
    assert calls == ["paged_attention"] * 2 + ["paged_attention_window"] * 2 \
        + ["selective_scan_step"] * 3, calls
    # the whole cache and nothing else of size: the convolution's three rows
    # a slot are padded to a tile in the device's layout
    aliased = compiled.memory_analysis().alias_size_in_bytes
    assert 0 <= aliased - _pool_bytes(cache) < 1e6
    layer_state = cache.ssm.size // cache.ssm.shape[0]
    moved = [line for line in _pool_sized_moves(compiled.as_text(), layer_state)
             if re.search(r"= (bf16\[10,\d+,64,128\]|f32\[\d+,\d+,16,40,128\])",
                          line)]
    assert moved == []


def test_phi4flash_prefill_of_the_longest_bucket_has_no_attention_over_the_prompt(v5e):
    """One row of 16,384 tokens: the scan kernel (``selective_scan_fwd``, a
    call a piece of 4,096 rows inside a loop) and the windowed flash forward
    are there, and NO full causal flash call: the full layer projects K/V for
    every row and attends from the last row alone, so nothing quadratic in
    the prompt is left. The program fits beside the weights and the cache."""
    compiled, _ = _phi4flash(v5e, bucket=16384)
    calls = {c.split(".")[0] for c in _mosaic_calls(compiled.as_text())}
    assert calls == {"selective_scan_fwd", "flash_window_fwd"}, calls
    assert compiled.memory_analysis().temp_size_in_bytes < 4.5e9


# --------------------------------------------------------------------------- #
# PR 44: the fifth family, a latent page pool and two attention paths over it
# --------------------------------------------------------------------------- #
def _kimi_k2_lowered(v5e, bucket=None):
    """The fifth family at Kimi-K2.6's published widths, the leading dense
    layer and TWO of its expert layers (the scan's body is compiled once
    whatever their number), 12 of the router's 384 experts and a slice of the
    vocabulary, 16 slots of 25,088 as the benchmark's cell: its decode
    program, or its one-row prefill program of ``bucket``, lowered; and its
    cache."""
    from ray_tpu.models import kimi_k2 as km

    one = SingleDeviceSharding(v5e.devices[0])
    shape = functools.partial(jax.ShapeDtypeStruct, sharding=one)
    slots = 16
    config = km.KimiK2Config(
        vocab_size=8192, num_hidden_layers=3, n_routed_experts=12,
        held_experts=(0, 12), max_seq_len=25088, attention_impl="flash")
    pages = 25088 // PAGE
    params = _on(one, jax.eval_shape(lambda k: km.init_params(config, k),
                                     jax.random.key(0)))
    cache = _on(one, jax.eval_shape(
        lambda: km.init_cache(config, slots, slots * pages + 1, PAGE)))
    if bucket:
        lowered = km.make_paged_prefill_fn(config, PAGE).lower(
            params, cache, shape((1, bucket), jnp.int32),
            shape((1, bucket // PAGE), jnp.int32), shape((1,), jnp.int32))
    else:
        ints = shape((slots,), jnp.int32)
        lowered = km.make_paged_decode_fn(config, 8, PAGE, use_kernel=True).lower(
            params, cache, ints, ints, shape((slots,), jnp.bool_),
            shape((slots, pages), jnp.int32),
            _on(one, jax.eval_shape(lambda: jax.random.key(0))))
    return lowered, cache


def _kimi_k2(v5e, bucket=None):
    lowered, cache = _kimi_k2_lowered(v5e, bucket)
    return lowered.compile(), cache


def test_kimi_k2_decode_reads_one_latent_pool_and_expands_nothing(v5e):
    """Mosaic takes the paged-attention kernel's body over a LATENT pool: one
    KV head, a query group of 64, rows of 640 of which the first 512 are the
    values, fetched once; a profile tells it apart by name (the benchmark's
    ``latent_attn_decode_roofline`` reads it so). The pool rides the layer
    scan as the one donated cache, aliased and never copied, and the program
    holds no K or V expanded to the heads: nothing as tall as a slot's pages
    times 64 heads. It fits beside the weights and the pool."""
    compiled, cache = _kimi_k2(v5e)
    text = compiled.as_text()
    calls = sorted(c.split(".")[0] for c in _mosaic_calls(text))
    # the dense layer's call and the scanned expert layers' one
    assert calls == ["paged_attention_latent"] * 2, calls
    assert cache.k.shape == (1, 3 * (16 * 392 + 1), 64, 640)
    assert compiled.memory_analysis().alias_size_in_bytes == cache.k.size * 2
    moved = [line for line in _pool_sized_moves(text, cache.k.size // 3)
             if re.search(r"= bf16\[1,\d+,64,640\]", line)]  # the pool's shape
    assert moved == []
    assert not re.search(r"bf16\[16,\d+,64,(128|192|256)\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


def test_kimi_k2_prefill_of_the_longest_bucket_is_unabsorbed_and_fits(v5e):
    """One row of 24,576 tokens, the ONE prefill program the cell compiles:
    the flash forward at q / k of 192 and v of 128 (``flash_mla_fwd``), told
    the row's length (a scalar in front of the grid), 16 heads at a time; K
    and V of a head are 19 MB in VMEM, double buffered, more than Mosaic's
    default scoped VMEM. The rows are walked in pieces of 2,048, a branch a
    piece, so nothing float32 is as tall as the prompt times the dense MLP's
    18,432; and the program's temporaries fit beside 8.4 GB of weights and
    a pool of 3.1 GB."""
    from ray_tpu.models import kimi_k2 as km

    compiled, _ = _kimi_k2(v5e, bucket=24576)
    text = compiled.as_text()
    calls = [c for c in _mosaic_calls(text) if "ragged" not in c]
    calls, combines = _without_combines(calls)
    assert calls and all(c.startswith("flash_mla_fwd") for c in calls), calls
    # ONE in the scanned expert layer's loop over its blocks
    assert len(combines) == 1, combines
    assert not _row_scatters(text, 7168)
    assert re.search(r"flash_mla_fwd\S* = bf16\[1,16,24576,128\]", text)
    assert km.PREFILL_ROWS == 2048
    assert re.search(r"f32\[2048,18432\]", text)
    assert not re.search(r"f32\[24576,18432\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 3.5e9


# --------------------------------------------------------------------------- #
# PR 52: the sixth family, delta-rule state beside latent pages
# --------------------------------------------------------------------------- #
def _ling_lowered(v5e, bucket=None):
    """The sixth family at Ling-3.0-flash's published widths, ONE group of
    three (a dense KDA layer, a KDA layer with experts, the MLA layer with
    experts), 128 of the router's 512 experts and a slice of the vocabulary,
    24 slots of 33,280 as the benchmark's cell: its decode program, or its
    one-row prefill program of ``bucket``, lowered; and its cache."""
    from ray_tpu.models import ling_hybrid as lh

    one = SingleDeviceSharding(v5e.devices[0])
    shape = functools.partial(jax.ShapeDtypeStruct, sharding=one)
    slots = 24
    config = lh.LingHybridConfig(
        vocab_size=8192, num_hidden_layers=3, layer_group_size=3,
        first_k_dense_replace=1, num_experts=128, held_experts=(0, 128),
        max_seq_len=33280, attention_impl="flash", kda_impl="pallas")
    pages = 33280 // PAGE
    params = _on(one, jax.eval_shape(lambda k: lh.init_params(config, k),
                                     jax.random.key(0)))
    cache = _on(one, jax.eval_shape(
        lambda: lh.init_cache(config, slots, slots * pages + 1, PAGE)))
    if bucket:
        lowered = lh.make_paged_prefill_fn(config, PAGE).lower(
            params, cache, shape((1, bucket), jnp.int32),
            shape((1, bucket // PAGE), jnp.int32), shape((1,), jnp.int32),
            shape((1,), jnp.int32))
    else:
        ints = shape((slots,), jnp.int32)
        lowered = lh.make_paged_decode_fn(config, 8, PAGE, use_kernel=True).lower(
            params, cache, ints, ints, shape((slots,), jnp.bool_),
            shape((slots, pages), jnp.int32),
            _on(one, jax.eval_shape(lambda: jax.random.key(0))))
    return lowered, cache


def test_ling_decode_moves_state_in_place_beside_one_latent_pool(v5e):
    """Mosaic takes the one-token delta-rule kernel (``kda_step``, a call a
    KDA layer) and the latent paged-attention kernel (a call the MLA layer)
    in ONE program over ONE donated cache: the float32 state of every slot,
    the convolutions' rows and the latent pool are all aliased, and nothing
    the size of a layer's state or of the pool is copied."""
    lowered, cache = _ling_lowered(v5e)
    compiled = lowered.compile()
    text = compiled.as_text()
    calls = sorted(c.split(".")[0] for c in _mosaic_calls(text))
    assert calls == ["kda_step", "kda_step", "paged_attention_latent"], calls
    assert cache.k.shape == (1, 24 * 520 + 1, 64, 640)
    assert cache.kda.shape == (2, 25, 32, 128, 128)
    held = sum(x.size * x.dtype.itemsize for x in cache)
    assert compiled.memory_analysis().alias_size_in_bytes == held
    assert not re.search(r"= f32\[2,25,32,128,128\]\S* copy\(", text)
    assert not re.search(r"= bf16\[1,12481,64,640\]\S* copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2e8


def test_ling_prefill_of_the_longest_bucket_walks_its_pieces_and_fits(v5e):
    """One row of 32,768 tokens, the ONE prefill program the cell compiles:
    the chunked delta-rule kernel (``kda_chunk_fwd``) over a piece of 2,048
    rows of 32 heads inside each KDA layer's loop, the flash forward at q / k
    of 192 and v of 128 told the row's length in the MLA layer, the grouped
    expert product; nothing of a KDA layer is as tall as the bucket times its
    five projections, and the temporaries fit beside 8.8 GB of weights and
    1.3 GB of pool and state."""
    from ray_tpu.models import ling_hybrid as lh

    lowered, _ = _ling_lowered(v5e, bucket=32768)
    compiled = lowered.compile()
    text = compiled.as_text()
    names = {c.split(".")[0] for c in _mosaic_calls(text)}
    assert {"kda_chunk_fwd", "flash_mla_fwd"} <= names, names
    assert re.search(r"kda_chunk_fwd\S* = \(bf16\[1,32,2048,128\]", text)
    assert re.search(r"flash_mla_fwd\S* = bf16\[1,16,32768,128\]", text)
    assert lh.PREFILL_ROWS == 2048 and lh._pieces(32768) == 16
    assert re.search(r"bf16\[1,2048,20480\]", text)
    assert not re.search(r"\[(1,)?32768,20480\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 3e9


def test_kda_chunk_kernel_alone_compiles_at_the_cells_call(v5e):
    """The chunk kernel by itself at the call the cell makes of it (one
    piece of 2,048 rows of 32 heads in bfloat16, ``a`` and the state
    float32): Mosaic takes its pairs of heads side by side, the stacked rows
    and the masks (it ABORTS, with no Python error, on a form it refuses: a
    slice of an iota, an int32 ``%``), sixteen heads a grid step fit the
    kernel's 16 MB of VMEM, and the call keeps the name and the heads-first
    result the benchmark's readers find it by."""
    from ray_tpu.ops import kda

    shape = functools.partial(jax.ShapeDtypeStruct,
                              sharding=SingleDeviceSharding(v5e.devices[0]))
    b, s, h, d = 1, 2048, 32, 128
    rows = shape((b, s, h, d), jnp.bfloat16)
    text = _compiled_text(
        functools.partial(kda.kda_prefill, impl="pallas"), rows, rows, rows,
        shape((b, s, h, d), jnp.float32), shape((b, s, h), jnp.float32),
        shape((b, h, d, d), jnp.float32), shape((b,), jnp.int32))
    assert [c.split(".")[0] for c in _mosaic_calls(text)] == ["kda_chunk_fwd"]
    assert re.search(r"kda_chunk_fwd\S* = \(bf16\[1,32,2048,128\]\S*, "
                     r"f32\[1,32,128,128\]", text)


# --------------------------------------------------------------------------- #
# PR 46: the second trained family
# --------------------------------------------------------------------------- #
# an expert stack of Mellum's copied or transposed (the parent's reverse pass
# transposed one before each product over the matrices' last dimension, and
# XLA's grouped kernel had some re-laid)
STACK_COPY = r"= bf16\[16,(896,2304|2304,896)\]\S* (copy|transpose)\("


def test_mellum_expert_chunk_compiles_with_the_grouped_kernel(v5e):
    """One chunk of Mellum's expert layer at the real widths (4,096 tokens,
    16 of the router's 64 experts, 2304 x 896, bfloat16: a block of 16,384
    sorted rows), forward and ``jax.value_and_grad``: Mosaic takes the grouped
    product's three entry points inside its scoped VMEM (an expert's matrix
    whole, double buffered; the outer product's float32 result block, 8.3
    MB); each reaches the compiled program as ``ragged-dot-... = f32[``, one
    float32 result, which is what ``experts_grouped_roofline``,
    ``experts_train_share`` and ``experts_glue_train_share`` key on; the
    reverse pass is the first block and the loop over further ones, 2 + 3 + 3
    products each; and the program holds neither a [16, rows, width]
    convolution (XLA's lowering of a contraction over the stacks' last
    dimension) nor a copy of a transposed stack."""
    from ray_tpu.ops import moe

    one = SingleDeviceSharding(v5e.devices[0])
    shape = functools.partial(jax.ShapeDtypeStruct, sharding=one)
    tokens, h, f, held, width, k = 4096, 2304, 896, 16, 64, 8
    block = moe._capacity(tokens * k, held, width)
    assert block == 16384
    args = (shape((tokens, h), jnp.bfloat16),
            {"w": shape((h, width), jnp.bfloat16)},
            {"w_up": shape((held, h, f), jnp.bfloat16),
             "w_gate": shape((held, h, f), jnp.bfloat16),
             "w_down": shape((held, f, h), jnp.bfloat16)})

    def layer(x, router, experts):
        return moe.routed_experts(
            x, router, experts, held=(0, held), top_k=k, scale=1.0,
            impl="ragged", scoring="softmax", form="swiglu")

    def names(text):
        return sorted(c.split(".")[0] for c in _mosaic_calls(text))

    # ONE block body in the forward's loop: three products and (PR 48) one
    # sum of the rows onto their tokens, which takes the loop's carry
    forward = _compiled_text(layer, *args)
    assert names(forward) == ["ragged-dot-rows"] * 3 + ["rows-to-tokens"]
    text = _compiled_text(jax.value_and_grad(
        lambda *a: layer(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)),
        *args)
    # outside a scanned, rematerialised layer the first block's calls carry
    # the transformation in front of their name (``transpose_jvp_ragged-...``);
    # inside one, as the train step has them, they do not
    # (``test_mellum_train_step_compiles_and_fits_at_two_rows_of_8192``)
    kind = r"ragged-dot-(?:rows-t|rows|outer)|rows-to-tokens"
    assert sorted(re.search(kind, c).group() for c in names(text)) == \
        ["ragged-dot-outer"] * 6 + ["ragged-dot-rows"] * 7 \
        + ["ragged-dot-rows-t"] * 6 + ["rows-to-tokens"] * 3
    kind = r"ragged-dot-(?:rows-t|rows|outer)"
    results = re.findall(rf"%?[\w\-]*{kind}[\w.]* = (\S+?)\{{", forward + text)
    assert len(results) == 3 + 19 and set(results) == {
        f"f32[{block},{f}]", f"f32[{block},{h}]", f"f32[{held},{h},{f}]",
        f"f32[{held},{f},{h}]"}
    # the rows' sums onto the tokens, forward and reverse: the kernel under
    # its own name (what ``experts_glue_train_share`` counts it under: an
    # operand of [16384,2304]), and no row scatter-add left
    combines = re.findall(r"%?[\w\-]*rows-to-tokens[\w.]* = (\S+?)\{", forward + text)
    assert combines == [f"f32[{tokens},{h}]"] * (1 + 3)
    for program in (forward, text):
        assert not _row_scatters(program, h)
        assert "ragged-dot-none" not in program
        assert not re.search(r"= f32\[16,\d+,(2304|896)\]\S* convolution",
                             program)
        assert not re.search(STACK_COPY, program)


def test_token_rows_write_compiles_at_the_chat_cells_decode_shape(v5e):
    """``ops/token_rows.py`` alone at the shape the Llama-shaped family's
    decode program runs it at (K and V of 8 heads of 128 for 64 slots, the
    chat cell's pool): Mosaic takes the strided tile copies with a semaphore
    a slot, the select on packed bfloat16 rows and the aliased pools in HBM;
    the program keeps the kernel's name, aliases both pools and holds nothing
    else of size."""
    from ray_tpu.ops.token_rows import token_rows_write

    one = SingleDeviceSharding(v5e.devices[0])
    shape = functools.partial(jax.ShapeDtypeStruct, sharding=one)
    held = (shape((8, 8 * 1537, PAGE, 128), jnp.bfloat16),) * 2
    rows = (shape((64, 8, 128), jnp.bfloat16),) * 2
    ints = shape((64,), jnp.int32)
    compiled = jax.jit(token_rows_write, donate_argnums=(0,)).lower(
        held, rows, ints, ints, shape((64,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert [c.split(".")[0] for c in _mosaic_calls(text)] == [
        "token_rows_write"]
    assert _pool_scatters(text) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == _pool_bytes(held)
    assert mem.temp_size_in_bytes < 1e6


@pytest.mark.parametrize("cell,cap,h,t,blocks", [
    ("train_moe_8k", 16384, 2304, 4096, 2),
    ("serve_mla_longdoc", 1024, 7168, 2048, 4),
    ("serve_window_longctx", 8192, 2048, 4096, 2)])
def test_rows_to_tokens_compiles_at_the_cells_shapes(v5e, cell, cap, h, t,
                                                     blocks):
    """``ops/rows_to_tokens.py`` at the three cells that run the compacted
    product (Mellum's chunk, Kimi's piece, Laguna's chunk), weighted and
    onto a loop's carry as the forward calls it (a block of the carry's
    columns by one DMA from HBM into the result's block, whose memory the
    result takes) and plain as the reverse does: Mosaic takes a load, an
    add and a store at a dynamic sublane of a result block that stays in
    VMEM (18.9, 14.7 and 16.8 MB, double buffered), ``token`` and the factor
    in scalar memory (64 KB each at 16,384 rows); the program keeps the
    kernel's name, which no reader of ``^ragged-dot`` matches."""
    from ray_tpu.ops import rows_to_tokens as rt

    one = SingleDeviceSharding(v5e.devices[0])
    shape = functools.partial(jax.ShapeDtypeStruct, sharding=one)
    assert h // rt._tiling(cap, t, h, rt.ROW_TILE, rt.ROWS_A_TRIP,
                           rt.RESULT_BLOCK_BYTES)[1] == blocks
    rows, token = shape((cap, h), jnp.float32), shape((cap,), jnp.int32)
    carry = (shape((t, h), jnp.float32), shape((), jnp.bool_))
    for factor, onto in ((shape((cap,), jnp.float32), carry), (None, None)):
        text = _compiled_text(
            lambda rows, token, factor, onto: rt.rows_to_tokens(
                rows, token, t, factor, onto),
            rows, token, factor, onto)
        assert [c.split(".")[0] for c in _mosaic_calls(text)] == [
            "rows-to-tokens"], cell
        assert re.search(rf"rows-to-tokens\S* = f32\[{t},{h}\]", text)
        assert not _row_scatters(text, h)


def test_mellum_train_step_compiles_and_fits_at_two_rows_of_8192(v5e):
    """Mellum2-12B-A2.5B's published widths, 8 of 28 layers (two periods), 16
    of the router's 64 experts and a quarter of the vocabulary, 2 x 8,192
    tokens, the benchmark's optimizer: Mosaic takes the flash backward under
    a window (``flash_window_bwd``, ONE pass since PR 58, which holds the
    dQ of a KV head's eight q heads in VMEM, 32 MB of float32 scratch and 2
    x 16 of bfloat16 at 8,192 rows, over the default scoped limit) beside
    the full one; every grouped product of the
    expert layer, forward and reverse, is ``ops/grouped_matmul.py``'s kernel
    under a name that starts ``ragged-dot`` (three forward and eight in
    reverse a layer, the eight written twice: the first block and the loop
    over any further ones; since PR 47 none is XLA's, none is lowered to
    every expert over every row, and no expert stack is copied, transposed
    or re-laid for one); the scanned
    period holds ONE layer of each kind; and the program fits the chip beside
    6.47 GB of state."""
    from ray_tpu.models import mellum as ml

    config = ml.MellumConfig(
        vocab_size=24576, layer_types=ml.PERIOD * 2,
        mlp_layer_types=("sparse",) * 8, num_experts=16,
        held_experts=(0, 16), attention_impl="flash", moe_tokens=4096)
    compiled = _train_step_lowered(v5e, config, 2, 8192).compile()
    text = compiled.as_text()
    calls = [c.split(".")[0] for c in _mosaic_calls(text)]
    assert {"flash_window_fwd", "flash_window_bwd", "attn_full"} <= set(calls), \
        sorted(set(calls))
    assert calls.count("flash_window_bwd") == 1  # one sliding layer's body
    grouped = [c for c in calls if c.startswith("ragged-dot")]
    assert len(grouped) == 2 * (3 + 2 * 8), calls
    assert {c: grouped.count(c) for c in set(grouped)} == {
        "ragged-dot-rows": 2 * (3 + 2 * 2), "ragged-dot-rows-t": 2 * 2 * 3,
        "ragged-dot-outer": 2 * 2 * 3}
    # since PR 48 the rows reach their tokens through ``rows-to-tokens``,
    # forward (one block body in its loop) and reverse (the first block and
    # the loop), and no row scatter-add of float32 [*, 2304] is left
    assert calls.count("rows-to-tokens") == 2 * (1 + 2)
    assert not _row_scatters(text, 2304)
    assert not re.search(r"= f32\[16,\d+,(2304|896)\]\S* convolution", text)
    assert not re.search(STACK_COPY, text)
    mem = compiled.memory_analysis()
    state = mem.argument_size_in_bytes
    assert 6.4e9 < state < 6.6e9 and mem.alias_size_in_bytes > 6.4e9


# --------------------------------------------------------------------------- #
# PR 57: the third trained family, and the first recurrent layer with a
# reverse pass
# --------------------------------------------------------------------------- #
def test_gdn_chunk_kernels_alone_compile_at_the_cells_call(v5e):
    """The gated delta rule's chunk kernels by themselves at the call the
    cell makes of them (one row of 32,768 tokens, 30 heads, key 96 / value
    192 in bfloat16, ``g`` and ``beta`` float32): Mosaic takes ten heads a
    grid step in lockstep at 0.75 and 1.5 lane tiles, the rows of ``G`` and
    ``beta`` turned to columns by a product, the masks from whole iotas; the
    forward is ONE call under the name the benchmark's readers find it by; the
    reverse pass is two, a group of ten heads at a time (its chunks' states
    are a third of the heads': 0.5 GB and not 1.5)."""
    from ray_tpu.ops import gdn

    shape = functools.partial(jax.ShapeDtypeStruct,
                              sharding=SingleDeviceSharding(v5e.devices[0]))
    b, s, h = 1, 32768, 30
    qk, v = shape((b, s, h, 96), jnp.bfloat16), shape((b, s, h, 192), jnp.bfloat16)
    gate = shape((b, s, h), jnp.float32)
    fwd = functools.partial(gdn.gdn_chunk, impl="pallas")
    text = _compiled_text(fwd, qk, qk, v, gate, gate)
    assert [c.split(".")[0] for c in _mosaic_calls(text)] == ["gdn_chunk_fwd"]
    assert re.search(r"gdn_chunk_fwd\S* = bf16\[1,30,32768,192\]", text)

    def loss(*a):
        return fwd(*a).astype(jnp.float32).sum()

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                          qk, qk, v, gate, gate)
    assert sorted(c.split(".")[0] for c in _mosaic_calls(text)) == [
        "gdn_chunk_bwd", "gdn_chunk_bwd_states"]
    assert re.search(r"gdn_chunk_bwd_states\S* = f32\[1,10,512,192,96\]", text)
    assert not re.search(r"f32\[1,30,512,192,96\]", text)


def test_olmo_hybrid_train_step_compiles_and_fits_at_one_row_of_32768(v5e):
    """Olmo-Hybrid-7B's published widths, one period of its eight (L L L F)
    and a quarter of the vocabulary, 1 x 32,768 tokens, the benchmark's
    optimizer: the step holds the chunk kernels forward and reverse beside
    the full flash forward and its ONE backward pass (which asks for the dQ
    of one head, float32 scratch and the bfloat16 block twice, and 16 MB: 48
    MB at 32,768 rows, where the pair it replaced in PR 58 asked for 100.7 MB
    + 16), the
    scanned period ONE linear layer's body and one full layer's, and the
    program fits the chip beside 6.16 GB of state."""
    from ray_tpu.models import olmo_hybrid as oh

    config = oh.OlmoHybridConfig(
        vocab_size=25088, layer_types=oh.PERIOD, attention_impl="flash",
        gdn_impl="pallas", max_seq_len=32768)
    compiled = _train_step_lowered(v5e, config, 1, 32768).compile()
    text = compiled.as_text()
    calls = [c.split(".")[0] for c in _mosaic_calls(text)]
    assert {c: calls.count(c) for c in set(calls)} == {
        "gdn_chunk_fwd": 1, "gdn_chunk_bwd_states": 1, "gdn_chunk_bwd": 1,
        "attn_full": 2}, calls
    asks = _scoped_vmem_asks(text, "attn_full")
    assert len(asks) == 2 and max(asks) < 116.7e6, asks
    mem = compiled.memory_analysis()
    state = mem.argument_size_in_bytes
    assert 6.1e9 < state < 6.2e9 and mem.alias_size_in_bytes > 6.1e9
    # what the step NEEDS, the compiler's peak of live bytes, arguments and
    # temporaries at the worst instant (15.80 GB of the 16.91 a v5e has):
    # the linear layers' reverse pass, which PR 58 did not move by a page
    assert mem.peak_memory_in_bytes < 15.9e9
    # the step's own beside the new state it writes onto the old, as
    # ``temp_size_in_bytes`` has it: 6.82 GB until PR 58, 7.52 since, with the
    # same peak and 1.5 GB fewer buffers. It rose because the change FREES
    # the forward's lane-padded lse column (480 MiB for 3.9 MB of numbers)
    # a hundred program points sooner than the pair, which read it as a
    # column: with that column held to the backward call and nothing else
    # changed the figure reads 6.820 again, and no buffer AT the call moves
    # it by a page (dK/dV in float32, the results aliased onto q, K and V,
    # 70 to 117 MB of VMEM asked: 7.52490752 GB each). PERF.md 7, PR 58 (3)
    # has the readings and what a next issue sets this bound on
    assert mem.temp_size_in_bytes - mem.alias_size_in_bytes < 7.6e9
