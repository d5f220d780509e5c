"""Expert feed-forward's grouped matmuls on the 64 of 256 experts held here: least time by the chip's peaks for the assignments that landed on held experts over device time of the matmuls' events."""

from benchmarks.lib import moe_readers


def read(ctx):
    return moe_readers.expert_roofline_pct(ctx)
