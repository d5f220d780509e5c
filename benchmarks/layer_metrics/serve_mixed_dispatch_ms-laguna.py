"""Median duration of the serve/dispatch-w128 spans of the traced window."""

from benchmarks.lib import span_readers


def read(ctx):
    return span_readers.dispatch_ms(ctx, mixed=True)
