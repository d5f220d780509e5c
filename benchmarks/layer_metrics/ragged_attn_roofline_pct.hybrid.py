"""Ragged paged attention in the full-attention layers: least time by the chip's peaks over device time of the kernel's events."""

from benchmarks.lib import readers


def read(ctx):
    return readers.ragged_attn_roofline_pct(ctx)
