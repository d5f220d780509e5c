"""Gated delta rule in the linear-attention layers: least time by the chip's peaks (state read and written once per row, q/k/v/o once) over device time of the op's events."""

from benchmarks.lib import hybrid_readers


def read(ctx):
    return hybrid_readers.gdn_roofline_pct(ctx)
