"""Mean decode rows an emitted step handed out, from the engine's step log."""

from benchmarks.lib import step_log_readers


def read(ctx):
    return step_log_readers.decode_rows_per_step(ctx)
