"""Expert feed-forward's grouped matmuls: least time by the chip's peaks for what the router chose (weights of the experts that got a token once, two operations a weight an assignment) over device time of the matmuls' events."""

from benchmarks.lib import moe_readers


def read(ctx):
    return moe_readers.expert_roofline_pct(ctx)
