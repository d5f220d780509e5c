"""Share of the traced window in which no operation ran on the device."""

from benchmarks.lib import readers


def read(ctx):
    return readers.device_idle_pct(ctx)
