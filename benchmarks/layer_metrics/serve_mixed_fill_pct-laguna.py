"""Tokens the window's mixed steps carried over the tokens their program was compiled for, from the engine's step log."""

from benchmarks.lib import step_log_readers


def read(ctx):
    return step_log_readers.mixed_fill_pct(ctx)
