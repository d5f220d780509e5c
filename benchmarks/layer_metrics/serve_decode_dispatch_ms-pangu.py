"""Median duration of the serve/dispatch-w1 spans: a decode dispatch until its tokens are on the host."""

from benchmarks.lib import span_readers


def read(ctx):
    return span_readers.dispatch_ms(ctx, mixed=False)
