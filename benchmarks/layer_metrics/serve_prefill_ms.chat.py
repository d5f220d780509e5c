"""Median admission-to-first-token of the requests whose first token the window emitted, from the engine's first-token log."""

from benchmarks.lib import step_log_readers


def read(ctx):
    return step_log_readers.prefill_ms(ctx)
