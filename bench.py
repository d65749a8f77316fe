"""Benchmark harness: Llama train-step tokens/sec/chip on the attached TPU.

Prints ONE JSON line:
    {"ok": true, "metric": "...", "value": N, "unit": "...", "device": {...}, ...}

A bare ``jit`` loop over one fixed batch: it measures the train step, not
the runtime around it (``chip_smoke.py`` drives ``TpuTrainer``; one benchmark
with named cells is ROADMAP Speed item 1). The reference publishes no
LLM-scale numbers (BASELINE.md), so ``vs_baseline`` is measured throughput
relative to a 40%-MFU target for the chip.

There is no CPU branch: without a TPU, or on a device kind with no peak on
record, the script prints ``{"ok": false, ...}`` and exits non-zero.
"""

from __future__ import annotations

import json
import os
import sys
import time

# Peak dense bf16 FLOP/s per chip by device-kind substring (Google Cloud
# TPU documentation). A kind that is not here is an error, not a default.
PEAK_FLOPS = [
    ("v6", 918e12),
    ("trillium", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
]
MFU_TARGET = 0.40


def detect_chip():
    """(device_kind, peak FLOP/s) of the attached TPU; raises without one."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"bench.py measures a TPU and jax found {dev.platform!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
    kind = dev.device_kind.lower()
    for key, flops in PEAK_FLOPS:
        if key in kind:
            return kind, flops
    raise RuntimeError(f"no peak FLOP/s on record for device kind {kind!r}")


def profile_ops(config, state, batch: int, seq: int, repeats: int = 5):
    """Per-op timing decomposition of the train step (VERDICT r4 #5): where
    do the milliseconds go? Each component is timed as its own jitted
    program at the train step's exact shapes — an approximation (the real
    step lets XLA fuse across these boundaries, so components can sum to
    MORE than the whole), but it localizes the plateau: attention fwd+bwd
    vs embedding/FFN matmuls vs the vocab-projection+CE tail vs optimizer."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.llama import llama_hidden, llama_loss
    from ray_tpu.ops.attention import flash_attention, reference_attention

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, config.vocab_size, (batch, seq)),
                         jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    params = state.params if hasattr(state, "params") else state["params"]

    def timed(fn, *args):
        fn = jax.jit(fn)
        jax.block_until_ready(fn(*args))  # compile
        t0 = time.perf_counter()
        for _ in range(repeats):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / repeats

    # full fwd loss / fwd+bwd
    fwd_s = timed(lambda p: llama_loss(p, tokens, targets, config), params)
    fwdbwd_s = timed(
        jax.grad(lambda p: llama_loss(p, tokens, targets, config)), params)

    # attention alone at model shapes, all layers
    h, d = config.num_heads, config.hidden_size // config.num_heads
    hkv = config.num_kv_heads
    q = jnp.asarray(rng.standard_normal((batch, seq, h, d)), config.dtype)
    k = jnp.asarray(rng.standard_normal((batch, seq, hkv, d)), config.dtype)
    v = jnp.asarray(rng.standard_normal((batch, seq, hkv, d)), config.dtype)
    attn = (flash_attention if config.attention_impl in ("flash", "auto")
            else reference_attention)
    attn_fwd_s = timed(lambda q, k, v: attn(q, k, v, causal=True), q, k, v) \
        * config.num_layers
    attn_fb_s = timed(
        jax.grad(lambda q, k, v: attn(q, k, v, causal=True)
                 .astype(jnp.float32).sum(), argnums=(0, 1, 2)),
        q, k, v) * config.num_layers

    # vocab projection + CE tail (the model's fused seq-chunked path)
    from ray_tpu.models.llama import _lm_head
    from ray_tpu.ops.loss import fused_cross_entropy

    hidden = jnp.asarray(
        rng.standard_normal((batch, seq, config.hidden_size)), config.dtype)

    def ce_tail(hid, p):
        return fused_cross_entropy(hid, _lm_head(p, config), targets, None)

    ce_s = timed(jax.grad(ce_tail, argnums=0), hidden, params)

    # trunk without the CE tail (hidden states only), fwd
    trunk_s = timed(lambda p: llama_hidden(p, tokens, config).sum(), params)

    return {
        "repeats": repeats,
        "step_components_ms": {
            "full_fwd": round(fwd_s * 1e3, 2),
            "full_fwd_bwd": round(fwdbwd_s * 1e3, 2),
            "attention_fwd_all_layers": round(attn_fwd_s * 1e3, 2),
            "attention_fwd_bwd_all_layers": round(attn_fb_s * 1e3, 2),
            "trunk_fwd_no_ce": round(trunk_s * 1e3, 2),
            "ce_tail_fwd_bwd": round(ce_s * 1e3, 2),
        },
    }


def measure_object_transfer(size: int = 16 << 20) -> dict:
    """Data-plane sample for the perf trajectory: node-to-node object pull
    MB/s on a tiny same-host cluster (the control plane is tracked by
    ray_perf; this keeps the artifact honest about the DATA plane too).
    Runs in subprocess-spawned agents with JAX untouched; bounded seconds."""
    import numpy as np

    import ray_tpu
    from ray_tpu.cluster import Cluster
    from ray_tpu.core.rpc import SyncRpcClient

    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 1})
    try:
        node2 = cluster.add_node(num_cpus=1)
        cluster.wait_for_nodes(2, timeout=60)
        ray_tpu.init(address=cluster.gcs_address)
        payload = np.zeros(size, dtype=np.uint8)
        ref = ray_tpu.put(payload)
        agent2 = SyncRpcClient(node2.address)
        try:
            t0 = time.perf_counter()
            agent2.call("ensure_local", object_id=ref.id.hex(),
                        timeout_s=120.0, timeout=130.0)
            dt = time.perf_counter() - t0
            stats = agent2.call("transfer_stats")
        finally:
            agent2.close()
        return {
            "pull_mbps": round(size / dt / 1e6, 1),
            "bytes": size,
            "raw_transfer": bool((stats.get("pulls", 0) or 0) >= 1),
        }
    finally:
        try:
            ray_tpu.shutdown()
        finally:
            cluster.shutdown()


def main(large: bool = False) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.train.step import default_optimizer, make_train_state_factory, make_train_step
    from ray_tpu.utils.compile_cache import enable_compile_cache
    from ray_tpu.utils.device_report import device_report

    enable_compile_cache()
    kind, peak = detect_chip()

    if large:
        # LARGEST-FIT config for one 16GB v5e chip (RAY_TPU_BENCH_LARGE=1):
        # 1.75B params x ~8B/param of bf16 state (params + adam m/v) + grads
        # + activations at batch 2 ~= 15GB; 1.93B fails compile-time
        # allocation. BASELINE.json's 7B-class north star CANNOT fit one
        # v5e at any batch (7B x 8B/param = 56GB of state), so 7B training
        # is a multi-chip fsdp job by construction.
        config = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_layers=36, num_heads=16, num_kv_heads=4, max_seq_len=2048,
            remat="save_attn", attention_impl="flash",
        )
        batch, seq, steps, warmup = 2, 2048, 12, 2
    else:
        config = LlamaConfig.llama_1b(
            max_seq_len=2048, remat="save_attn", attention_impl="flash"
        )
        batch, seq, steps, warmup = 8, 2048, 20, 3

    opt = default_optimizer(warmup_steps=10, total_steps=1000)
    init = make_train_state_factory(config, opt)
    step = make_train_step(config, opt, donate=True)

    state = init(jax.random.key(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, config.vocab_size, (batch, seq)), jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)

    t0 = time.perf_counter()
    for _ in range(warmup):  # the first call compiles
        state, metrics = step(state, tokens, targets)
    jax.block_until_ready(metrics)
    warmup_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, tokens, targets)
    jax.block_until_ready(metrics)
    dt = time.perf_counter() - t0
    final_loss = float(metrics["loss"])

    tokens_per_step = batch * seq
    tokens_per_sec = tokens_per_step * steps / dt

    n_params = config.num_params
    # FLOPs/token: 6N for weights (fwd+bwd) + attention 12*L*h*s (causal ~1/2)
    flops_per_token = 6 * n_params + 6 * config.num_layers * config.hidden_size * seq
    mfu = tokens_per_sec * flops_per_token / peak
    target_tps = MFU_TARGET * peak / flops_per_token
    result = {
        "ok": True,
        "metric": ("llama_train_largest_fit_tokens_per_sec_per_chip"
                   if large else "llama_train_tokens_per_sec_per_chip"),
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tokens_per_sec / target_tps, 4),
        "mfu": round(mfu, 4),
        "chip": kind,
        "model_params": n_params,
        "batch": batch,
        "seq": seq,
        "loss": round(final_loss, 4),
        "warmup_and_compile_s": round(warmup_s, 2),
    }

    # opt-in: the profile compiles ~8 extra XLA programs (several minutes on
    # a cold cache), too slow for a default invocation
    if os.environ.get("RAY_TPU_BENCH_PROFILE", "0") == "1":
        prof = profile_ops(config, state, batch, seq)
        # optimizer alone (adamw over the full param tree)
        import optax

        grads = jax.tree.map(jnp.zeros_like, state.params)

        @jax.jit
        def opt_only(params, opt_state, grads):
            updates, new_opt = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), new_opt

        jax.block_until_ready(opt_only(state.params, state.opt_state, grads))
        t0 = time.perf_counter()
        reps = prof["repeats"]
        for _ in range(reps):
            out = opt_only(state.params, state.opt_state, grads)
        jax.block_until_ready(out)
        prof["step_components_ms"]["optimizer"] = round(
            (time.perf_counter() - t0) / reps * 1e3, 2)
        prof["step_components_ms"]["measured_full_step"] = round(
            dt / steps * 1e3, 2)
        result["per_op_profile"] = prof

    result["device"] = device_report()
    # data-plane sample (opt out: RAY_TPU_BENCH_TRANSFER=0) so the emitted
    # artifact tracks object-transfer throughput alongside the train step
    if os.environ.get("RAY_TPU_BENCH_TRANSFER", "1") != "0":
        result["object_transfer"] = measure_object_transfer()

    print(json.dumps(result))


if __name__ == "__main__":
    # RAY_TPU_BENCH_LARGE=1 measures the largest single-chip config instead
    # of the tuned flagship
    _large = os.environ.get("RAY_TPU_BENCH_LARGE") == "1"
    try:
        main(large=_large)
    except Exception as e:  # noqa: BLE001 - one failure line, then fail
        import traceback

        traceback.print_exc()
        print(json.dumps({
            "ok": False,
            "metric": ("llama_train_largest_fit_tokens_per_sec_per_chip"
                       if _large else "llama_train_tokens_per_sec_per_chip"),
            "error": f"{type(e).__name__}: {e}"[:400],
        }))
        sys.exit(1)
