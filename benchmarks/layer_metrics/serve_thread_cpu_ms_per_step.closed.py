"""Mean CPU time of the serve loop's thread an emitted step (time.thread_time between two step rows), from the engine's step log."""

from benchmarks.lib import step_log_readers


def read(ctx):
    return step_log_readers.cpu_ms_per_step(ctx, "thread_cpu_s")
