"""Model FLOP/s utilization of the traced window."""

from benchmarks.lib import readers


def read(ctx):
    return readers.train_mfu_pct(ctx)
