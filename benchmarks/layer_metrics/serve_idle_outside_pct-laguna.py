"""Share of the traced window in which the device was idle and the host was in no serve/step."""

from benchmarks.lib import span_readers


def read(ctx):
    return span_readers.idle_outside_steps_pct(ctx)
