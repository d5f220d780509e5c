"""Device time of the gated delta rule and the short convolution as a share of the traced window's busy time."""

from benchmarks.lib import hybrid_readers


def read(ctx):
    return hybrid_readers.linear_attn_device_pct(ctx)
