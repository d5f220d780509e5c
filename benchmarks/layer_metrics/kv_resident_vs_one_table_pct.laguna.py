"""Bytes the pool's pages in use held over what one table for all layers would have held for the same sequences: mean over the window's emitted steps, from the engine's step log."""

from benchmarks.lib import window_readers


def read(ctx):
    return window_readers.kv_resident_vs_one_table_pct(ctx)
