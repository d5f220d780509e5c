"""Readers of what a decoder with latent attention adds to the serve step:
the device time of its attention (the Mosaic kernel both forms run, and
the XLA operations that fold ``W_kvb`` into the queries and out of the
outputs) against the least the rows of each traced step allow.  The events
are found by the cell's ``kernel_events`` patterns (``latent_attention``: a
Mosaic call by its target, over the reduced trace's signatures, as the
ragged kernel's readers find theirs; ``latent_absorb``: XLA operations by
RESULT type, ``hybrid_readers`` says why) with ``{heads}``, ``{latent}``,
``{v}`` and ``{lanes}`` filled in from ``family.dims``.  A program without such events, as
every commit before PR 35, gives every reader here nothing to read: None,
and the metric is left out of the line."""

import re

from ..kernels import latent_attention, roofline
from . import hybrid_readers, trace as trace_lib
from .device import log

_guard = hybrid_readers._nothing_on_a_fault
KERNELS = ("latent_attention", "latent_absorb")


def event_pattern(cell, kernel):
    template = cell["workload"].get("kernel_events", {}).get(kernel)
    if template is None:
        return None
    dims = cell["family"].dims(cell["config"])
    for key in ("heads", "latent", "v", "lanes"):
        template = template.replace("{%s}" % key, str(dims[key]))
    return re.compile(template)


def _attention_ns(ctx):
    """Device nanoseconds of the window's latent-attention events, and
    how many there were."""
    total, events = 0, 0
    by_signature = (ctx["trace"].devices[ctx["planes"][0]],
                    ctx["trace"].signatures)
    for kernel in KERNELS:
        pattern = event_pattern(ctx["cell"], kernel)
        if pattern is None:
            continue
        ops, signatures = by_signature if kernel == KERNELS[0] else (
            hybrid_readers._device_events(ctx), None)
        ns, calls = trace_lib.kernel_ns(ops, ctx["t0"], ctx["t1"], pattern,
                                        signatures)
        log(f"{kernel}: {calls} events matching {pattern.pattern!r}, "
            f"{ns / 1e9:.4f} s in the window")
        total, events = total + ns, events + calls
    return total, events


@_guard
def attn_roofline_pct(ctx):
    """Share of its roofline the latent attention reached: the least time
    the chip could take for the rows of each traced step (the same work
    whichever form served them), over the device time of both forms'
    events."""
    d = ctx["cell"]["family"].dims(ctx["cell"]["config"])
    if "latent" not in d:
        return None
    ns, calls = _attention_ns(ctx)
    if not calls or not ctx["rows"]:
        return None
    least, bounds = 0.0, {"compute": 0, "memory": 0}
    for rows in ctx["rows"]:
        if not rows:
            continue
        f = latent_attention.flops(rows, d["heads"], d["nope"], d["rope"],
                                   d["v"])
        b = latent_attention.bytes_moved(
            rows, d["heads"], d["latent"], d["nope"], d["rope"], d["v"],
            ctx["pool_itemsize"], 4)
        s, bound = roofline.least_seconds(f, b, ctx["peaks"])
        bounds[bound] += 1
        least += s * d["layers"]
    log(f"latent attention: {calls} events, {ns / 1e9:.4f} s over "
        f"{len(ctx['rows'])} steps; least {least:.4f} s; steps by bound "
        f"{bounds}")
    return 100.0 * least / (ns / 1e9)


@_guard
def attn_device_pct(ctx):
    """The latent attention's events over the window's busy time."""
    ns, calls = _attention_ns(ctx)
    if not calls or not ctx["busy_s"]:
        return None
    return 100.0 * (ns / 1e9) / ctx["busy_s"]
