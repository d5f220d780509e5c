"""Device time a serve step of the expert layers' other operations: router, top-k, the sort into expert order, gathers, combine."""

from benchmarks.lib import moe_readers


def read(ctx):
    return moe_readers.overhead_ms_per_step(ctx)
