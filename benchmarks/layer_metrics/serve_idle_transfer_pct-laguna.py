"""Share of the traced window in which the device was idle while the host was in serve/transfer."""

from benchmarks.lib import span_readers


def read(ctx):
    return span_readers.idle_inside_pct(ctx, span_readers.TRANSFER)
