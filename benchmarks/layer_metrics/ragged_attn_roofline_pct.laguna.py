"""Ragged paged attention over sliding and global layers: least time by the chip's peaks for the model's work (a sliding layer's query has at most the window's keys; K/V bytes of the visible keys once) over device time of the kernel's events."""

from benchmarks.lib import window_readers


def read(ctx):
    return window_readers.ragged_attn_roofline_pct(ctx)
