"""Device time a step of the expert layers' other operations (router, top-k, the sort into expert order, gathers, combine) that a result type tells: a floor."""

from benchmarks.lib import moe_readers


def read(ctx):
    return moe_readers.overhead_ms_per_step(ctx)
