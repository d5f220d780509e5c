"""Readers of what a decoder with sliding-window layers adds to the serve
step: the ragged kernel's device time in layers of two kinds against the
least the MODEL's work allows (``kernels/windowed_attention.py``), the
sliding layers' share of the busy time, what the pool held against what
one table for all layers would have held (the engine's step log), and the
host time of the window kind's release (the ``serve/window-release``
span).

The kernel's events are found by the cell's ``kernel_events`` pattern
``ragged_paged_attention`` over the reduced trace's signatures, as every
ragged reader finds them.  The two kinds of layer run the SAME kernel; they
are told apart by the call's RESULT type (``hybrid_readers`` says why a
result type): ``[rows, query cells, kv heads x head_dim]`` with ``width x
heads / kv heads`` query cells, 6 a token in a global layer and 8 in a
sliding one at the published widths (``ragged_sliding`` / ``ragged_global``
in the cell's file, ``{sliding_cells}`` / ``{global_cells}`` filled in here
from ``family.dims`` and the engine's two widths).  A program without such
events, spans or log fields, as every commit before PR 43, gives every
reader here nothing to read: None, and the metric is left out of the
line."""

import re

import numpy as np

from ..kernels import roofline, windowed_attention
from . import hybrid_readers, span_readers, step_log_readers, trace as trace_lib
from .device import log

_guard = hybrid_readers._nothing_on_a_fault
RELEASE = ("serve/window-release",)


def event_pattern(cell, kernel):
    """The cell's ``kernel_events[kernel]`` with ``{sliding_cells}`` and
    ``{global_cells}`` (the query cells of a kernel row at the engine's
    two widths, as an alternation) and ``{lanes}`` filled in."""
    template = cell["workload"].get("kernel_events", {}).get(kernel)
    if template is None:
        return None
    dims = cell["family"].dims(cell["config"])
    widths = (1, cell["config"]["engine"]["prefill_chunk"])
    for key, heads in (("sliding_cells", dims.get("sliding_heads", 0)),
                       ("global_cells", dims["heads"])):
        group = heads // dims["kv_heads"]
        template = template.replace(
            "{%s}" % key, "|".join(str(w * group) for w in widths))
    template = template.replace(
        "{lanes}", str(dims["kv_heads"] * dims["head_dim"]))
    return re.compile(template)


def _kind_ns(ctx, kernel):
    """Device nanoseconds and events of the window's kernel calls of one
    kind of layer, by result type."""
    pattern = event_pattern(ctx["cell"], kernel)
    if pattern is None:
        return 0, 0
    ns, calls = trace_lib.kernel_ns(hybrid_readers._device_events(ctx),
                                    ctx["t0"], ctx["t1"], pattern)
    log(f"{kernel}: {calls} events matching {pattern.pattern!r}, "
        f"{ns / 1e9:.4f} s in the window")
    return ns, calls


@_guard
def ragged_attn_roofline_pct(ctx):
    """Share of its roofline the ragged kernel reached over layers of both
    kinds: the least time the chip could take for the model's work in each
    traced step (a sliding layer's query has at most ``window`` keys), over
    the device time of the kernel's events."""
    cell = ctx["cell"]
    dims = cell["family"].dims(cell["config"])
    if "window" not in dims:
        return None
    pattern = re.compile(
        cell["workload"]["kernel_events"]["ragged_paged_attention"])
    ns, calls = trace_lib.kernel_ns(
        ctx["trace"].devices[ctx["planes"][0]], ctx["t0"], ctx["t1"],
        pattern, ctx["trace"].signatures)
    if not calls or not ctx["rows"]:
        return None
    kinds = ((dims["global_layers"], dims["heads"], 0),
             (dims["sliding_layers"], dims["sliding_heads"], dims["window"]))
    least, by_kind = 0.0, [0.0, 0.0]
    for rows in ctx["rows"]:
        if not rows:
            continue
        for i, (layers, heads, window) in enumerate(kinds):
            f = windowed_attention.flops(rows, heads, dims["head_dim"],
                                         window)
            b = windowed_attention.bytes_moved(
                rows, heads, dims["kv_heads"], dims["head_dim"],
                ctx["pool_itemsize"], 4, window)
            s = roofline.least_seconds(f, b, ctx["peaks"])[0] * layers
            by_kind[i] += s
            least += s
    for kernel in ("ragged_global", "ragged_sliding"):
        _kind_ns(ctx, kernel)
    log(f"ragged kernel: {calls} events, {ns / 1e9:.4f} s over "
        f"{len(ctx['rows'])} steps; least {least:.4f} s (global layers "
        f"{by_kind[0]:.4f}, sliding layers {by_kind[1]:.4f})")
    return 100.0 * least / (ns / 1e9)


@_guard
def window_attn_device_pct(ctx):
    """The sliding layers' kernel events over the window's busy time."""
    ns, calls = _kind_ns(ctx, "ragged_sliding")
    if not calls or not ctx["busy_s"]:
        return None
    return 100.0 * (ns / 1e9) / ctx["busy_s"]


@_guard
def kv_resident_vs_one_table_pct(ctx):
    """What the pool held over what one table for all layers would have
    held for the same sequences, the mean over the window's emitted steps
    (the step log's ``resident_kv_bytes`` / ``..._one_table``)."""
    rows = step_log_readers._steps(ctx)
    if rows is None or "resident_kv_bytes_one_table" not in (
            rows.dtype.names or ()):
        return None
    held = rows["resident_kv_bytes"].astype(np.float64)
    one = rows["resident_kv_bytes_one_table"].astype(np.float64)
    some = one > 0
    if not some.any():
        return None
    log(f"kv residency: {int(some.sum())} steps, the pool held "
        f"{held[some].mean() / 1e9:.3f} GB on average (most "
        f"{held.max() / 1e9:.3f}), one table would have held "
        f"{one[some].mean() / 1e9:.3f} GB (most {one.max() / 1e9:.3f})")
    return 100.0 * float((held[some] / one[some]).mean())


@_guard
def window_release_ms_per_step(ctx):
    """Summed ``serve/window-release`` time of the window over the number
    of ``serve/step`` spans in it."""
    steps = span_readers._in_window(
        ctx, span_readers._named(ctx, span_readers.STEP))
    spans = span_readers._in_window(ctx, span_readers._named(ctx, RELEASE))
    if not steps or not spans:
        return None
    total = sum(dur for _, _, dur in spans)
    log(f"serve/window-release: {len(spans)} spans, {total / 1e6:.3f} ms in "
        f"all, longest {max(d for _, _, d in spans) / 1e6:.3f} ms, over "
        f"{len(steps)} serve/step spans")
    return total / 1e6 / len(steps)
