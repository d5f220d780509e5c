"""Share of the window's dispatches that ran at the prefill width."""

from benchmarks.lib import readers


def read(ctx):
    return readers.serve_mixed_step_pct(ctx)
