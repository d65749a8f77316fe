"""tokens/s/chip x FLOPs a token (forward and backward, no recompute, no
embedding gather, attention counted: the configuration's family counts them
for its own layer) over the chip's bf16 peak. %."""
from benchmarks.harness import roofline
from benchmarks.harness.manifest import family_of
from benchmarks.readers import train_token_rate


def read(ctx, params):
    rate = train_token_rate.read(ctx, {})
    if rate is None:
        return None
    peak = roofline.peaks_for(ctx["device_report"]["kind"])["bf16_flops"]
    seq = ctx["cfg"]["deployment"]["max_seq_len"]
    flops = family_of(ctx["cfg"]).train_flops_per_token(ctx["cfg"], seq)
    return 100.0 * rate * flops / peak
