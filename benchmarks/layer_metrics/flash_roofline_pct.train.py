"""Flash attention forward + backward: least time by the chip's peaks over device time of the kernels' events."""

from benchmarks.lib import readers


def read(ctx):
    return readers.flash_roofline_pct(ctx)
