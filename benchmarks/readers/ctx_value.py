"""A number the runner observed directly: params {"key", "scale"}."""


def read(ctx, params):
    value = ctx.get(params["key"])
    if value is None:
        return None
    return value * params.get("scale", 1.0)
