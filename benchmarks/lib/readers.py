"""What the per-layer readers share.  A reader file under
``layer_metrics/`` names its metric and calls one of these with the run's
context; a reader that finds nothing to read returns None."""

import re
import statistics

from ..kernels import flash_attention, ragged_paged_attention, roofline
from . import trace as trace_lib
from .device import log


def device_idle_pct(ctx):
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])


def _first_plane_ops(ctx):
    return ctx["trace"].devices[ctx["planes"][0]]


def span_ms(ctx, name):
    """Median duration of the host spans called ``name``."""
    durs = [d for d in trace_lib.span_durations(ctx["trace"].host, name)
            if d > 0]
    return statistics.median(durs) / 1e6 if durs else None


def spans_per_update_ms(ctx, names):
    """Summed duration of the named host spans inside the window, per
    update."""
    t0, t1 = ctx["t0"], ctx["t1"]
    total, seen = 0.0, 0
    for name, start, dur in ctx["trace"].host:
        if name in names and t0 <= start < t1:
            total += dur
            seen += 1
    if not seen or not ctx["updates"]:
        return None
    return total / 1e6 / ctx["updates"]


def flash_roofline_pct(ctx):
    """Share of its roofline the flash kernels reached: the least time
    the chip could take for every forward and backward call of the
    traced updates, over the device time of the kernels' events."""
    cfg, wl = ctx["cell"]["config"], ctx["cell"]["workload"]
    pattern = re.compile(wl["kernel_events"]["flash_attention"])
    ns, calls = trace_lib.kernel_ns(_first_plane_ops(ctx), ctx["t0"],
                                    ctx["t1"], pattern,
                                    ctx["trace"].signatures)
    if not calls:
        return None
    rows = ctx["rows_per_update"] // ctx["chips"]  # this device's rows
    T = ctx["cell"]["traffic"]["seq_len"]
    dims = ctx["cell"]["family"].dims(cfg)
    heads, d, layers = dims["heads"], dims["head_dim"], dims["layers"]
    least = 0.0
    for backward in (False, True):
        f = flash_attention.flops(rows, heads, T, T, d, backward=backward)
        b = flash_attention.bytes_moved(rows, heads, T, T, d, 2,
                                        backward=backward, bias_itemsize=2)
        s, bound = roofline.least_seconds(f, b, ctx["peaks"])
        log(f"flash {'bwd' if backward else 'fwd'}: {f:.4g} flop, {b:.4g} "
            f"bytes, least {s * 1e6:.1f} us, {bound}-bound")
        least += s
    least *= layers * ctx["updates"]
    log(f"flash kernels: {calls} events, {ns / 1e9:.4f} s over "
        f"{ctx['updates']} updates; least {least:.4f} s")
    return 100.0 * least / (ns / 1e9)


def ragged_attn_roofline_pct(ctx):
    """Share of its roofline the ragged paged attention kernel reached,
    from the rows each traced step served."""
    cfg, wl = ctx["cell"]["config"], ctx["cell"]["workload"]
    pattern = re.compile(wl["kernel_events"]["ragged_paged_attention"])
    ns, calls = trace_lib.kernel_ns(_first_plane_ops(ctx), ctx["t0"],
                                    ctx["t1"], pattern,
                                    ctx["trace"].signatures)
    if not calls or not ctx["rows"]:
        return None
    dims = ctx["cell"]["family"].dims(cfg)
    heads, d, layers = dims["heads"], dims["head_dim"], dims["layers"]
    least, bounds = 0.0, {"compute": 0, "memory": 0}
    for rows in ctx["rows"]:
        if not rows:
            continue
        f = ragged_paged_attention.flops(rows, heads, d)
        b = ragged_paged_attention.bytes_moved(
            rows, heads, d, ctx["pool_itemsize"], 4)
        s, bound = roofline.least_seconds(f, b, ctx["peaks"])
        bounds[bound] += 1
        least += s * layers
    log(f"ragged kernel: {calls} events, {ns / 1e9:.4f} s over "
        f"{len(ctx['rows'])} steps; least {least:.4f} s; steps by bound "
        f"{bounds}")
    return 100.0 * least / (ns / 1e9)


def serve_step_ms(ctx):
    if not ctx["steps"]:
        return None
    return statistics.median(dt for _, dt, _ in ctx["steps"]) * 1e3


def serve_mixed_step_pct(ctx):
    if not ctx["steps"]:
        return None
    mixed = sum(1 for _, _, w in ctx["steps"] if w > 1)
    return 100.0 * mixed / len(ctx["steps"])


def prefix_hit_token_pct(ctx):
    if not ctx["prompt_tokens"]:
        return None
    return 100.0 * ctx["prefix_tokens_saved"] / ctx["prompt_tokens"]


def train_mfu_pct(ctx):
    """Operations the family says a real (non-pad) token requires, times
    real tokens per second, over chips times the peak."""
    cell = ctx["cell"]
    mean_len = ctx["tokens"] / (ctx["updates"] * ctx["rows_per_update"])
    flops_per_token = cell["family"].train_flops_per_token(
        cell["config"], mean_len)
    rate = ctx["tokens"] / ctx["window_s"]
    log(f"mfu: {flops_per_token / 1e9:.4f} GFLOP per real token, "
        f"{rate:.1f} tokens/s in the traced window, mean row {mean_len:.1f}")
    return 100.0 * flops_per_token * rate / (
        ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
