"""Summed serve/window-release time (window pages behind every running sequence handed back at the step boundary) of the traced window per serve/step."""

from benchmarks.lib import window_readers


def read(ctx):
    return window_readers.window_release_ms_per_step(ctx)
