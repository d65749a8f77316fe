"""A kernel's share of its roofline: the least time the chip could take for
what the calls need (operations over peak FLOP/s, or bytes over peak bytes/s)
over the kernel's device time in the trace. params {"pattern", "kind",
"count_pattern"}; %."""
import re

from benchmarks.harness import roofline


def _live_tokens(ctx):
    """Mean over the traced interval of the tokens in the cache of requests
    in flight, from the client's records."""
    marks = ctx.get("marks") or {}
    span = marks.get("traced")
    if not span:
        return None
    samples = []
    for k in range(5):
        t = span[0] + (span[1] - span[0]) * (k + 0.5) / 5
        live = 0
        for r in ctx["records"]:
            if r.arrivals and r.arrivals[0] <= t and (r.finished or t + 1) > t:
                live += r.prompt_len + sum(1 for a in r.arrivals if a <= t)
        samples.append(live)
    return sum(samples) / len(samples)


def read(ctx, params):
    trace = ctx.get("trace") or {}
    table, counts = trace.get("op_self_s", {}), trace.get("op_count", {})
    rx = re.compile(params["pattern"])
    seconds = sum(v for k, v in table.items() if rx.search(k))
    crx = re.compile(params.get("count_pattern", params["pattern"]))
    calls = sum(v for k, v in counts.items() if crx.search(k))
    if seconds <= 0 or not calls:
        return None
    cfg = ctx["cfg"]
    peaks = roofline.peaks_for(ctx["device_report"]["kind"])
    kind = params["kind"]
    if kind == "paged_attn":
        live = _live_tokens(ctx)
        if live is None:
            return None
        need = calls * roofline.paged_attention_bytes(
            live, cfg["deployment"]["num_slots"], cfg)
        least = need / peaks["hbm_bytes_per_s"]
    else:
        dep = cfg["deployment"]
        shape = (dep["batch_rows"], dep["max_seq_len"],
                 cfg["num_attention_heads"], cfg["head_dim"])
        fn = {"flash_fwd": roofline.flash_fwd_flops,
              "flash_bwd": roofline.flash_bwd_flops}[kind]
        least = calls * fn(*shape) / peaks["bf16_flops"]
    return 100.0 * least / seconds
