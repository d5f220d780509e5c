"""Median duration of the serve/dispatch-w<n> spans with n > 1: a mixed (prefill-width) dispatch until its tokens are on the host."""

from benchmarks.lib import span_readers


def read(ctx):
    return span_readers.dispatch_ms(ctx, mixed=True)
