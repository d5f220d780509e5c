"""Summed serve/admit time (prefix match over latent pages, can_alloc, alloc) of the traced window per serve/step."""

from benchmarks.lib import span_readers


def read(ctx):
    return span_readers.admit_ms_per_step(ctx)
