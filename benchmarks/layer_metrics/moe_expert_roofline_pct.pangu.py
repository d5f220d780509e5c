"""Expert feed-forward's grouped matmuls on the share of the experts held here: least time by the chip's peaks for the assignments that landed on held experts (weights of the held experts that got a token once, two operations a weight an assignment) over device time of the matmuls' events."""

from benchmarks.lib import moe_readers


def read(ctx):
    return moe_readers.expert_roofline_pct(ctx)
