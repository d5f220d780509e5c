"""Summed serve/admit time (can_alloc over both kinds of page, alloc) of the traced window per serve/step."""

from benchmarks.lib import span_readers


def read(ctx):
    return span_readers.admit_ms_per_step(ctx)
