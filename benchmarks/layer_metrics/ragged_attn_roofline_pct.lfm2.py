"""Ragged paged attention under grouped queries: least time by the chip's peaks (K/V bytes of the K/V heads, queries of the query heads) over device time of the kernel's events."""

from benchmarks.lib import moe_readers


def read(ctx):
    return moe_readers.ragged_attn_roofline_pct(ctx)
