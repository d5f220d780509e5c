"""Readers of the serve engine's own spans (``serve/*``, the tree in
``unicore_tpu/serve/engine.py``'s docstring): which phase of
``serve_step`` the host was in while the device sat idle, and how long a
dispatch or an admission took.

Idle time goes to a phase by OVERLAP: the idle nanoseconds that lie
inside the union of the spans of the named phases.  (The breakdown's
rule, the innermost span open at a gap's middle, hands a gap inside
``serve/transfer`` to whatever runtime span is nested there.)  A program
without the spans, as every commit before PR 24, gives every reader here
nothing to read: None.  So does a trace or a context a reader cannot
make sense of: the fault is logged and the metric left out of the line,
and the cell's run goes on with the metrics it had before.
"""

import functools
import re
import statistics

from . import trace as trace_lib
from .device import log

STEP = ("serve/step",)
HOST = ("serve/schedule", "serve/plan", "serve/assemble", "serve/emit")
TRANSFER = ("serve/transfer",)
DISPATCH = ("serve/launch", "serve/fetch")
ADMIT = ("serve/admit",)
_DISPATCH_WIDTH = re.compile(r"^serve/dispatch-w(\d+)$")


def _nothing_on_a_fault(read):
    """A reader of spans that only this program records must not take
    the run of a cell down: whatever it cannot read is nothing to read."""
    @functools.wraps(read)
    def guarded(*args, **kwargs):
        try:
            return read(*args, **kwargs)
        except Exception as exc:  # noqa: BLE001 - the line goes without it
            log(f"{read.__name__}: {type(exc).__name__}: {exc}")
            return None
    return guarded


def overlap_ns(gaps, intervals):
    """Nanoseconds of the sorted, disjoint ``gaps`` that lie inside the
    union of ``intervals`` (any order, may nest or overlap)."""
    union = trace_lib.merge(intervals)
    total, i = 0, 0
    for a, b in gaps:
        while i < len(union) and union[i][1] <= a:
            i += 1
        j = i
        while j < len(union) and union[j][0] < b:
            total += min(b, union[j][1]) - max(a, union[j][0])
            j += 1
    return total


def _named(ctx, names):
    return [s for s in ctx["trace"].host if s[0] in names]


def _in_window(ctx, spans):
    """Those of ``spans`` that lie at least partly inside the traced
    window."""
    t0, t1 = ctx["t0"], ctx["t1"]
    return [s for s in spans if s[1] < t1 and s[1] + s[2] > t0]


def _idle_gaps(ctx):
    """The window's idle gaps on the first device, or None where the
    program recorded no ``serve/step`` in the window."""
    if not _in_window(ctx, _named(ctx, STEP)):
        return None
    ops = ctx["trace"].devices[ctx["planes"][0]]
    return trace_lib.idle_gaps(ops, ctx["t0"], ctx["t1"])


def _idle_ns_inside(ctx, gaps, names):
    """Nanoseconds of ``gaps`` inside the union of the spans called
    ``names`` (clipped to the window, since the gaps are)."""
    return overlap_ns(gaps, trace_lib.intervals_of(_named(ctx, names)))


@_nothing_on_a_fault
def idle_inside_pct(ctx, names):
    """Share of the traced window in which the device was idle and the
    host was inside a span called one of ``names``."""
    gaps = _idle_gaps(ctx)
    if gaps is None:
        return None
    ns = _idle_ns_inside(ctx, gaps, names)
    window = ctx["t1"] - ctx["t0"]
    log(f"device idle inside {'|'.join(names)}: {ns / 1e9:.4f} s of the "
        f"{window / 1e9:.4f} s window")
    return 100.0 * ns / window


@_nothing_on_a_fault
def idle_outside_steps_pct(ctx):
    """Share of the traced window in which the device was idle and the
    host was in no ``serve/step``: the harness's loop, or nothing due."""
    gaps = _idle_gaps(ctx)
    if gaps is None:
        return None
    inside = _idle_ns_inside(ctx, gaps, STEP)
    idle = sum(b - a for a, b in gaps)
    window = ctx["t1"] - ctx["t0"]
    log(f"device idle {idle / 1e9:.4f} s of the {window / 1e9:.4f} s window, "
        f"{(idle - inside) / 1e9:.4f} s of it outside every serve/step")
    return 100.0 * (idle - inside) / window


@_nothing_on_a_fault
def dispatch_ms(ctx, mixed):
    """Median duration of the ``serve/dispatch-w<n>`` spans of the window:
    those wider than one token per row (``mixed``) or those of width 1."""
    durs, widths = [], set()
    for name, _, dur in _in_window(ctx, ctx["trace"].host):
        m = _DISPATCH_WIDTH.match(name)
        if m is None:
            continue
        width = int(m.group(1))
        if (width > 1) == mixed:
            durs.append(dur)
            widths.add(width)
    if not durs:
        return None
    log(f"dispatch spans at width {sorted(widths)}: {len(durs)}, median "
        f"{statistics.median(durs) / 1e6:.3f} ms, longest "
        f"{max(durs) / 1e6:.3f} ms")
    return statistics.median(durs) / 1e6


@_nothing_on_a_fault
def admit_ms_per_step(ctx):
    """Summed ``serve/admit`` time of the window over the number of
    ``serve/step`` spans in it."""
    steps = _in_window(ctx, _named(ctx, STEP))
    if not steps:
        return None
    admits = [dur for _, _, dur in _in_window(ctx, _named(ctx, ADMIT))]
    log(f"serve/admit: {len(admits)} spans, {sum(admits) / 1e6:.3f} ms in "
        f"all, longest {max(admits, default=0) / 1e6:.3f} ms, over "
        f"{len(steps)} serve/step spans")
    return sum(admits) / 1e6 / len(steps)
