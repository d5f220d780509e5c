"""Median wall time of one serve_step, on the harness's clock."""

from benchmarks.lib import readers


def read(ctx):
    return readers.serve_step_ms(ctx)
