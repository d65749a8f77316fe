"""Host clock around ``next()`` of ``iter_jax_batches`` in the benchmark's
train loop; mean over the steps after warm-up. ms."""


def read(ctx, params):
    waits = (ctx.get("input_waits") or [])[ctx["cfg"]["deployment"]["warmup_steps"]:]
    return 1e3 * sum(waits) / len(waits) if waits else None
