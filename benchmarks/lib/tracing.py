"""The profiler around a window, and the harness's own spans.

Spans are ``jax.profiler.TraceAnnotation``: they land in the profiler's
own trace, on the clock of the device operations, so an idle gap can be
attributed to what the host was doing in it.  With no trace running an
annotation costs well under a microsecond.
"""

import jax

WINDOW_SPAN = "bench/window"
TRACE_SECONDS = 4  # a traced window: long enough for ~18 updates or ~100 steps
_open = None


def start(trace_dir):
    """Start the profiler (host spans on, the Python tracer off: it would
    record every Python call and slow the host it is measuring) and open
    the window's span."""
    global _open
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    _open = jax.profiler.TraceAnnotation(WINDOW_SPAN)
    _open.__enter__()


def stop():
    global _open
    if _open is not None:
        _open.__exit__(None, None, None)
        _open = None
    jax.profiler.stop_trace()


def span(name):
    return jax.profiler.TraceAnnotation(name)
