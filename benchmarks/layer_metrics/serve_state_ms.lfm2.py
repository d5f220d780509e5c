"""Summed serve/state time (the state slot of each row of a dispatch looked up: here a convolution tail's) of the traced window per serve/step."""

from benchmarks.lib import hybrid_readers


def read(ctx):
    return hybrid_readers.state_ms_per_step(ctx)
