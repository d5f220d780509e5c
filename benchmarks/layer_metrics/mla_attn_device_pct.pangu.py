"""Device time of the latent attention's events (the Mosaic kernel of both forms and the absorption of W_kvb) over the traced window's busy time."""

from benchmarks.lib import latent_readers


def read(ctx):
    return latent_readers.attn_device_pct(ctx)
