"""Host time per update in the train loop's data-wait and stage spans."""

from benchmarks.lib import readers


def read(ctx):
    return readers.spans_per_update_ms(ctx, ('train/data-wait', 'train/stage'))
