"""Share of the window's emitted steps that were launched with a step in flight, from the engine's step log."""

from benchmarks.lib import step_log_readers


def read(ctx):
    return step_log_readers.run_ahead_pct(ctx)
