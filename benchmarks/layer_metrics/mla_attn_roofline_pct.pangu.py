"""Latent attention: least time by the chip's peaks for the rows of each traced step (the same work whichever form served them: pairs x heads x (192 + 128) x 2 operations, the context's 576-number entries once) over device time of both forms' events, absorption included."""

from benchmarks.lib import latent_readers


def read(ctx):
    return latent_readers.attn_roofline_pct(ctx)
