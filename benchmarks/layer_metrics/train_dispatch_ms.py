"""Median of the trainer's own train_step/dispatch spans: host time to hand one update to the device."""

from benchmarks.lib import readers


def read(ctx):
    return readers.span_ms(ctx, 'train_step/dispatch')
