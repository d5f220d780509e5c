"""Readers of what a decoder with sparse experts adds to the serve step:
the device time of the experts' grouped matmuls against the least the
router's choices allow, and the device time of everything else of the
expert layer (router, top-k, the sort into expert order, gathers,
combine).  The events are found by the cell's ``kernel_events`` patterns
over RESULT types (``hybrid_readers`` says why), the sizes filled in from
the configuration; what the router did comes from the program's own
record, ``unicore_tpu.ops.moe.routing_report()``.  A program without the
record or the shapes, as every commit before PR 31, gives every reader
here nothing to read: None, and the metric is left out of the line."""

import re

from ..kernels import moe_experts, ragged_paged_attention, roofline
from . import hybrid_readers, trace as trace_lib
from .device import log

_guard = hybrid_readers._nothing_on_a_fault
WEIGHT_ITEMSIZE = 4  # the configuration serves float32 weights


def event_pattern(cell, kernel):
    """The cell's ``kernel_events[kernel]`` with ``{experts}``,
    ``{top_k}``, ``{expert_width}`` and ``{hidden}`` filled in from
    ``family.dims``; None where the cell names no such kernel."""
    template = cell["workload"].get("kernel_events", {}).get(kernel)
    if template is None:
        return None
    dims = cell["family"].dims(cell["config"])
    for key, name in (("experts", "experts"), ("top_k", "experts_per_token"),
                      ("expert_width", "expert_width"), ("hidden", "hidden")):
        template = template.replace("{%s}" % key, str(dims[name]))
    return re.compile(template)


def _matching(ctx, kernel, but_not=None):
    """``(nanoseconds, events)`` of the window's device events that match
    the cell's pattern for ``kernel`` and not the one for ``but_not``."""
    pattern = event_pattern(ctx["cell"], kernel)
    if pattern is None:
        return 0, 0
    other = event_pattern(ctx["cell"], but_not) if but_not else None
    events = [ev for ev in hybrid_readers._device_events(ctx)
              if pattern.search(ev[0])
              and not (other is not None and other.search(ev[0]))]
    ns, calls = trace_lib.kernel_ns(events, ctx["t0"], ctx["t1"],
                                    re.compile(""))
    by_op = trace_lib.time_by_name(
        [(text.split(" ", 1)[0], start, dur) for text, start, dur in events],
        ctx["t0"], ctx["t1"])
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:8]
    log(f"{kernel}: {calls} events matching {pattern.pattern!r}, "
        f"{ns / 1e9:.4f} s in the window; the largest (name, seconds) "
        f"{[(n, round(t / 1e9, 4)) for n, t in top]}")
    return ns, calls


def _window_routing(ctx):
    """``[(assignments, experts_touched)]`` of the window's steps, each
    summed over the step's expert layers, from the program's record: a
    closed loop's window ends with the run, so its steps are the record's
    last ones.  None where the program keeps no such record."""
    try:
        from unicore_tpu.ops import moe
    except ImportError:
        return None
    steps = len(ctx.get("steps") or ())
    recent = moe.routing_report()
    if not steps or len(recent) < steps:
        return None
    return recent[-steps:]


@_guard
def expert_roofline_pct(ctx):
    """Share of their roofline the experts' matmuls reached: the least
    time the chip could take for what the router chose in each traced
    step, over the device time of the matmuls' events."""
    routing = _window_routing(ctx)
    if not routing:
        return None
    ns, calls = _matching(ctx, "moe_experts")
    if not calls:
        return None
    dims = ctx["cell"]["family"].dims(ctx["cell"]["config"])
    hidden, width = dims["hidden"], dims["expert_width"]
    least, bounds = 0.0, {"compute": 0, "memory": 0}
    for assignments, touched in routing:
        f = moe_experts.flops(assignments, hidden, width)
        b = moe_experts.bytes_moved(assignments, touched, hidden, width,
                                    WEIGHT_ITEMSIZE, ctx["pool_itemsize"])
        s, bound = roofline.least_seconds(f, b, ctx["peaks"])
        bounds[bound] += 1
        least += s
    carried = sum(q for rows in ctx["rows"] for q, _ in rows)
    log(f"expert matmuls: least {least:.4f} s over {len(routing)} steps "
        f"({sum(a for a, _ in routing)} assignments, "
        f"{sum(t for _, t in routing)} experts touched; the rows carried "
        f"{carried} tokens x {dims['experts_per_token']} x "
        f"{dims['expert_layers']} layers = "
        f"{carried * dims['experts_per_token'] * dims['expert_layers']}); "
        f"steps by bound {bounds}")
    return 100.0 * least / (ns / 1e9)


@_guard
def overhead_ms_per_step(ctx):
    """Device time a step of the expert layers' other operations (the
    cell's ``moe_overhead`` events that are not its ``moe_experts``
    events), over the window's steps."""
    steps = len(ctx.get("steps") or ())
    if not steps or _window_routing(ctx) is None:
        return None
    ns, calls = _matching(ctx, "moe_overhead", but_not="moe_experts")
    if not calls:
        return None
    return ns / 1e6 / steps


@_guard
def ragged_attn_roofline_pct(ctx):
    """As ``readers.ragged_attn_roofline_pct`` for grouped queries: the
    K/V bytes of the K/V heads, the queries' and outputs' of the query
    heads, the operations of the query heads."""
    cfg, wl = ctx["cell"]["config"], ctx["cell"]["workload"]
    pattern = re.compile(wl["kernel_events"]["ragged_paged_attention"])
    ns, calls = trace_lib.kernel_ns(
        ctx["trace"].devices[ctx["planes"][0]], ctx["t0"], ctx["t1"],
        pattern, ctx["trace"].signatures)
    if not calls or not ctx["rows"]:
        return None
    dims = ctx["cell"]["family"].dims(cfg)
    heads, kv_heads, d = dims["heads"], dims["kv_heads"], dims["head_dim"]
    least = 0.0
    for rows in ctx["rows"]:
        if not rows:
            continue
        f = ragged_paged_attention.flops(rows, heads, d)
        b = ragged_paged_attention.bytes_moved(
            [(0, c) for _, c in rows], kv_heads, d, ctx["pool_itemsize"], 4)
        b += ragged_paged_attention.bytes_moved(
            [(q, 0) for q, _ in rows], heads, d, ctx["pool_itemsize"], 4)
        least += roofline.least_seconds(f, b, ctx["peaks"])[0] * dims["layers"]
    log(f"ragged kernel: {calls} events, {ns / 1e9:.4f} s over "
        f"{len(ctx['rows'])} steps; least {least:.4f} s")
    return 100.0 * least / (ns / 1e9)
