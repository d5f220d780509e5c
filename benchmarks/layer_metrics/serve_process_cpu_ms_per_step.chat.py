"""Mean CPU time of the whole process an emitted step (time.process_time between two step rows), from the engine's step log."""

from benchmarks.lib import step_log_readers


def read(ctx):
    return step_log_readers.cpu_ms_per_step(ctx, "process_cpu_s")
