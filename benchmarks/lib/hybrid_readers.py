"""Readers of what a decoder with recurrent (linear-attention) layers
adds to the serve step: the device time of the gated delta rule and of
the short convolution (found by the cell's ``kernel_events`` patterns over
the shapes only they produce), and the host span ``serve/state``.  A
program without them, as every commit before PR 27, gives every reader
here nothing to read: None, and the metric is left out of the line."""

import functools
import os
import re

from ..kernels import gated_delta_rule, roofline
from . import span_readers, trace as trace_lib
from .device import log

STATE = ("serve/state",)
STATE_ITEMSIZE = 4  # the recurrent state is float32 whatever the weights
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")


def _nothing_on_a_fault(read):
    """As ``span_readers``' guard: a reader must not take the cell's run
    down; what it cannot read is logged and is nothing to read."""
    @functools.wraps(read)
    def guarded(*args, **kwargs):
        try:
            return read(*args, **kwargs)
        except Exception as exc:  # noqa: BLE001 - the line goes without it
            log(f"{read.__name__}: {type(exc).__name__}: {exc}")
            return None
    return guarded


def _known_by(text):
    """``name opcode result-type`` of a device event whose name is a whole
    HLO instruction (``%fusion.4 = f32[8]{0} fusion(f32[8]{0} %p), ...``),
    WITHOUT its operands: an operation is told by what it produces, never
    by what it consumes."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text
    m = _OPCODE.search(" " + rest)
    if not m:
        return head.lstrip("%")
    return f"{head.lstrip('%')} {m.group(1)} {(' ' + rest)[:m.start()].strip()}"


def event_pattern(cell, kernel):
    """The cell's ``kernel_events[kernel]`` with the sizes it names filled
    in from the configuration: ``{max_batch}`` the engine's rows,
    ``{linear_heads}`` and ``{linear_channels}`` (q, k and v side by side,
    what the short convolution runs over) from ``family.dims``.  A cell of
    another batch or another width is matched by the same file; None where
    the cell names no such kernel."""
    template = cell["workload"].get("kernel_events", {}).get(kernel)
    if template is None:
        return None
    dims = cell["family"].dims(cell["config"])
    heads = dims["linear_heads"]
    sizes = {"max_batch": cell["config"]["engine"]["max_batch"],
             "linear_heads": heads,
             "linear_channels": heads * (2 * dims["linear_key_dim"]
                                         + dims["linear_value_dim"])}
    for key, value in sizes.items():
        template = template.replace("{%s}" % key, str(value))
    return re.compile(template)


def _device_events(ctx):
    """``[(text, start_ns, duration_ns)]`` of the first device's
    operations, ``text`` being the event's name, opcode and RESULT TYPE.

    An XLA fusion cannot be named by the program, and the scope it was
    traced under (``jax.named_scope``) reaches neither the event's name
    nor its stats (read on the chip, PR 27), so the per-head operations of
    a linear-attention layer and its short convolution are told by the
    shapes only they produce.  The harness's reduced trace drops result
    types, so the run's own trace file is read once more here; where it
    is not to be found (a reduced trace handed in by a test) the reduced
    trace's signatures serve."""
    if "_hybrid_events" in ctx:
        return ctx["_hybrid_events"]
    plane = ctx["planes"][0]
    cell = ctx["cell"]
    trace_dir = os.path.join(cell.get("root_program", ""), ".bench_work",
                             cell["name"], "trace")
    events = None
    try:
        path = trace_lib.find_xplane(trace_dir)
    except (FileNotFoundError, OSError):
        path = None
    if path is not None:
        from jax.profiler import ProfileData

        events, texts = [], {}
        for p in ProfileData.from_file(path).planes:
            if p.name != plane:
                continue
            for line in p.lines:
                if line.name != trace_lib.OPS_LINE:
                    continue
                for ev in line.events:
                    if ev.name not in texts:
                        texts[ev.name] = _known_by(ev.name)
                    events.append((texts[ev.name], float(ev.start_ns),
                                   float(ev.duration_ns)))
        log(f"device events with their result types: {len(events)}, "
            f"{len(texts)} distinct")
    if not events:
        sig = ctx["trace"].signatures
        events = [(sig.get(name, name), start, dur)
                  for name, start, dur in ctx["trace"].devices[plane]]
    ctx["_hybrid_events"] = events
    return events


def _events_ns(ctx, kernel):
    """Device nanoseconds and event count of the window's events that
    match the cell's pattern for ``kernel``.  Rows served with nothing
    matched means the layout moved away from under the pattern: said
    loudly, and nothing to read."""
    pattern = event_pattern(ctx["cell"], kernel)
    if pattern is None:
        return 0, 0
    events = _device_events(ctx)
    ns, calls = trace_lib.kernel_ns(events, ctx["t0"], ctx["t1"], pattern)
    if not calls and any(ctx.get("rows") or ()):
        log(f"{kernel}: NO device event matches {pattern.pattern!r} though "
            f"the window served rows: the pattern no longer fits the "
            f"program's shapes")
        return 0, 0
    by_op = trace_lib.time_by_name(
        [(text.split(" ", 1)[0], start, dur) for text, start, dur in events
         if pattern.search(text)], ctx["t0"], ctx["t1"])
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:8]
    log(f"{kernel}: {calls} events matching {pattern.pattern!r}, "
        f"{ns / 1e9:.4f} s in the window; the largest (name, seconds) "
        f"{[(n, round(t / 1e9, 4)) for n, t in top]}")
    return ns, calls


@_nothing_on_a_fault
def gdn_roofline_pct(ctx):
    """Share of its roofline the gated delta rule reached: the least
    time the chip could take for the rows each traced step served, in
    every linear-attention layer, over the device time of the op's
    events."""
    ns, calls = _events_ns(ctx, "gated_delta_rule")
    if not calls or not ctx["rows"]:
        return None
    dims = ctx["cell"]["family"].dims(ctx["cell"]["config"])
    if not dims.get("linear_layers"):
        return None
    heads, dk, dv = (dims["linear_heads"], dims["linear_key_dim"],
                     dims["linear_value_dim"])
    least, bounds = 0.0, {"compute": 0, "memory": 0}
    for rows in ctx["rows"]:
        if not rows:
            continue
        f = gated_delta_rule.flops(rows, heads, dk, dv)
        b = gated_delta_rule.bytes_moved(rows, heads, dk, dv, STATE_ITEMSIZE,
                                         ctx["pool_itemsize"])
        s, bound = roofline.least_seconds(f, b, ctx["peaks"])
        bounds[bound] += 1
        least += s * dims["linear_layers"]
    log(f"gated delta rule: least {least:.4f} s over {len(ctx['rows'])} "
        f"steps; steps by bound {bounds}")
    return 100.0 * least / (ns / 1e9)


@_nothing_on_a_fault
def linear_attn_device_pct(ctx):
    """The gated delta rule's and the short convolution's device time as
    a share of the window's busy time: how large a part of the device's
    work the recurrent mechanism is."""
    rule, n_rule = _events_ns(ctx, "gated_delta_rule")
    conv, n_conv = _events_ns(ctx, "short_conv")
    if not (n_rule or n_conv) or not ctx["busy_s"]:
        return None
    return 100.0 * (rule + conv) / 1e9 / ctx["busy_s"]


def _window_spans(ctx, names):
    """The host spans called one of ``names`` that lie at least partly
    inside the traced window."""
    t0, t1 = ctx["t0"], ctx["t1"]
    return [s for s in ctx["trace"].host
            if s[0] in names and s[1] < t1 and s[1] + s[2] > t0]


@_nothing_on_a_fault
def state_ms_per_step(ctx):
    """Summed ``serve/state`` time (the state slot of each row of a
    dispatch looked up) of the window over the number of ``serve/step``
    spans."""
    steps = _window_spans(ctx, span_readers.STEP)
    spans = _window_spans(ctx, STATE)
    if not steps or not spans:
        return None
    total = sum(dur for _, _, dur in spans)
    log(f"serve/state: {len(spans)} spans, {total / 1e6:.3f} ms in all, over "
        f"{len(steps)} serve/step spans")
    return total / 1e6 / len(steps)
