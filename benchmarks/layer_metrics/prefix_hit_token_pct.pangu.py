"""Prompt tokens served from the prefix cache (latent pages) over prompt tokens submitted, in the window."""

from benchmarks.lib import readers


def read(ctx):
    return readers.prefix_hit_token_pct(ctx)
