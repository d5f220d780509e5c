"""Median time the device had a step at the prefill width, from the engine's step log."""

from benchmarks.lib import step_log_readers


def read(ctx):
    return step_log_readers.emitted_ms(ctx, mixed=True)
