"""Host clock around ``train.report`` in the benchmark's train loop: the
lockstep with the trainer, the Tune trial and the driver. Mean over the
reports after the one that ends warm-up. ms."""


def read(ctx, params):
    waits = (ctx.get("report_waits") or [])[1:]
    return 1e3 * sum(waits) / len(waits) if waits else None
