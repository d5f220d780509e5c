"""Device time of the ragged kernel's calls in the sliding layers (told by their result type's query cells) over the traced window's busy time."""

from benchmarks.lib import window_readers


def read(ctx):
    return window_readers.window_attn_device_pct(ctx)
