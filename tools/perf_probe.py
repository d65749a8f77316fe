"""Perf probe: break the bench step into components to find the MFU gap.

Usage: python tools/perf_probe.py [matmul|attn|fwd|step|all]
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def _sync(out):
    jax.block_until_ready(out)


def timeit(fn, *args, steps=10, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / steps


def probe_matmul():
    """Raw MXU ceiling on this chip: big bf16 matmul chain."""
    n = 8192
    a = jnp.ones((n, n), jnp.bfloat16)
    b = jnp.ones((n, n), jnp.bfloat16)

    @jax.jit
    def chain(a, b):
        x = a
        for _ in range(8):
            x = (x @ b).astype(jnp.bfloat16)
        return x

    dt = timeit(chain, a, b)
    flops = 8 * 2 * n ** 3
    print(f"matmul {n}^3 x8: {dt*1e3:.1f} ms -> {flops/dt/1e12:.1f} TFLOP/s "
          f"({flops/dt/197e12*100:.1f}% of v5e peak)")


def probe_dispatch():
    """Per-call dispatch overhead of a tiny jitted program."""
    x = jnp.ones((8, 8), jnp.float32)
    f = jax.jit(lambda x: x + 1)
    dt = timeit(f, x, steps=50)
    print(f"tiny-op dispatch: {dt*1e3:.2f} ms/call")


def probe_attn():
    from ray_tpu.ops.attention import flash_attention, reference_attention

    b, s, hq, hkv, d = 8, 2048, 16, 4, 128
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.bfloat16)

    # causal flops (fwd): qk + pv, half masked
    fwd_flops = 2 * 2 * b * hq * s * s * d / 2

    f_fwd = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    dt = timeit(f_fwd, q, k, v)
    print(f"flash fwd: {dt*1e3:.1f} ms -> {fwd_flops/dt/1e12:.1f} TFLOP/s "
          f"({fwd_flops/dt/197e12*100:.1f}%)")

    r_fwd = jax.jit(lambda q, k, v: reference_attention(q, k, v, causal=True))
    dt = timeit(r_fwd, q, k, v)
    print(f"ref   fwd: {dt*1e3:.1f} ms -> {fwd_flops/dt/1e12:.1f} TFLOP/s "
          f"({fwd_flops/dt/197e12*100:.1f}%)")

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    g_flash = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))
    dt = timeit(g_flash, q, k, v)
    tot = fwd_flops * (1 + 2.5)
    print(f"flash fwd+bwd(grad): {dt*1e3:.1f} ms -> {tot/dt/1e12:.1f} TFLOP/s "
          f"({tot/dt/197e12*100:.1f}%)")

    def loss_ref(q, k, v):
        return reference_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))
    dt = timeit(g_ref, q, k, v)
    print(f"ref   fwd+bwd(grad): {dt*1e3:.1f} ms -> {tot/dt/1e12:.1f} TFLOP/s "
          f"({tot/dt/197e12*100:.1f}%)")


def probe_model(remat="nothing_saveable", attention_impl="flash", steps=8):
    from ray_tpu.models.llama import LlamaConfig, cross_entropy_loss, llama_forward
    from ray_tpu.train.step import default_optimizer, make_train_state_factory, make_train_step

    config = LlamaConfig.llama_1b(max_seq_len=2048, remat=remat, attention_impl=attention_impl)
    batch, seq = 8, 2048
    opt = default_optimizer(warmup_steps=10, total_steps=1000)
    init = make_train_state_factory(config, opt)
    state = init(jax.random.key(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, config.vocab_size, (batch, seq)), jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)

    n = config.num_params
    fwd_flops = 2 * n * batch * seq + 2 * config.num_layers * config.hidden_size * seq * batch * seq / 2 * 2 / seq  # ≈

    # forward only
    fwd = jax.jit(lambda p, t: cross_entropy_loss(llama_forward(p, t, config), targets))
    dt = timeit(fwd, state.params, tokens, steps=steps)
    print(f"[{remat}/{attention_impl}] fwd-only: {dt*1e3:.0f} ms "
          f"({2*n*batch*seq/dt/1e12:.1f} TF/s on 2N)")

    step = make_train_step(config, opt, donate=True)
    for _ in range(2):
        state, metrics = step(state, tokens, targets)
    jax.device_get(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, tokens, targets)
    jax.device_get(metrics["loss"])
    dt = (time.perf_counter() - t0) / steps
    tps = batch * seq / dt
    flops_per_token = 6 * n + 6 * config.num_layers * config.hidden_size * seq
    print(f"[{remat}/{attention_impl}] step: {dt*1e3:.0f} ms, {tps:.0f} tok/s, "
          f"MFU {tps*flops_per_token/197e12:.3f}")


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which in ("matmul", "all"):
        probe_dispatch()
        probe_matmul()
    if which in ("attn", "all"):
        probe_attn()
    if which in ("fwd", "step", "all"):
        probe_model()
