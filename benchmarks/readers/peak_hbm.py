"""``memory_stats()["peak_bytes_in_use"]`` in the process that holds the
chip, fullest chip. GB."""


def read(ctx, params):
    peak = (ctx.get("device_report") or {}).get("memory_peak_bytes")
    return peak / 1e9 if peak else None
