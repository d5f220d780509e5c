"""From a profiler trace to numbers: the reduction every PR shares.

The functions work on plain lists, so a small synthetic trace checks them
(``tests/bench/test_bench_trace.py``); :func:`read_xplane` is the thin
layer that fills those lists from the ``.xplane.pb`` jax writes.

    op    = (name, start_ns, duration_ns)   one operation on one device
    span  = (name, start_ns, duration_ns)   one host span (TraceAnnotation)
"""

import glob
import os
import re

# operations that only contain others: their time is their children's
CONTAINER = re.compile(r"^(while|conditional|call)([.\d]*)$", re.I)


class Trace:
    def __init__(self, devices, host, signatures=None):
        self.devices = devices  # {plane name: [op, ...]}
        self.host = host        # [span, ...]
        # {op name: "name opcode attributes"}: what a kernel is known by
        self.signatures = signatures or {}

    def span(self, name):
        """The first host span called ``name``, or None."""
        for s in self.host:
            if s[0] == name:
                return s
        return None


def _clip(intervals, t0, t1):
    for start, end in intervals:
        start, end = max(start, t0), min(end, t1)
        if end > start:
            yield start, end


def merge(intervals):
    """Sorted, non-overlapping union of ``(start, end)`` intervals."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def intervals_of(ops):
    return [(start, start + dur) for _, start, dur in ops if dur > 0]


def busy_ns(ops, t0, t1):
    """Nanoseconds of ``[t0, t1]`` in which some operation ran: the union
    of the operations' intervals, so nested or overlapping ones count
    once."""
    return sum(b - a for a, b in merge(_clip(intervals_of(ops), t0, t1)))


def idle_gaps(ops, t0, t1):
    """The intervals of ``[t0, t1]`` in which no operation ran."""
    gaps, at = [], t0
    for a, b in merge(_clip(intervals_of(ops), t0, t1)):
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if t1 > at:
        gaps.append((at, t1))
    return gaps


def time_by_name(ops, t0, t1, pattern=None):
    """``{name: ns}`` of the leaf operations inside ``[t0, t1]``;
    ``pattern`` (a compiled regex or a string) keeps matching names."""
    if isinstance(pattern, str):
        pattern = re.compile(pattern)
    out = {}
    for name, start, dur in ops:
        if CONTAINER.match(name):
            continue
        if pattern is not None and not pattern.search(name):
            continue
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            out[name] = out.get(name, 0) + (b - a)
    return out


def kernel_ns(ops, t0, t1, pattern, signatures=None):
    """Device nanoseconds of the operations whose name (or, where given,
    whose signature: name, opcode and attributes, never the operands)
    matches, and how many events that was."""
    if isinstance(pattern, str):
        pattern = re.compile(pattern)
    signatures = signatures or {}
    total, calls = 0, 0
    for name, start, dur in ops:
        if pattern.search(signatures.get(name, name)):
            a, b = max(start, t0), min(start + dur, t1)
            if b > a:
                total += b - a
                calls += 1
    return total, calls


def top_ops(ops, t0, t1, n=10):
    """The ``n`` operation names that took most device time, as
    ``[[name, seconds], ...]``."""
    by_name = time_by_name(ops, t0, t1)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def attribute_gaps(gaps, spans, n=10):
    """Idle seconds by what the host was doing: each gap goes to the
    innermost host span open at its middle (the one that started last),
    ``(no span)`` where none was.  ``[[name, seconds], ...]``, longest
    first."""
    by_name = {}
    for a, b in gaps:
        mid = (a + b) / 2.0
        owner, owner_start = "(no span)", None
        for name, start, dur in spans:
            if start <= mid < start + dur and (
                    owner_start is None or start >= owner_start):
                owner, owner_start = name, start
        by_name[owner] = by_name.get(owner, 0) + (b - a)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def span_durations(spans, name):
    return [dur for s_name, _, dur in spans if s_name == name]


# -- the xplane layer ---------------------------------------------------

OPS_LINE = "XLA Ops"
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")


def split_hlo(text):
    """``(name, signature)`` of a device event whose name is a whole HLO
    instruction (``%fusion.4 = f32[8]{0} fusion(f32[8]{0} %p), kind=...``):
    the instruction's own name, and that name with its opcode and its
    attributes but WITHOUT its operands, so that a kernel is never taken
    for an operation that merely consumes its result."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text, text
    name = head.lstrip("%")
    m = _OPCODE.search(" " + rest)
    if not m:
        return name, name
    depth, end = 0, None
    for i in range(m.end() - 1, len(rest) + 1):
        c = (" " + rest)[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    attrs = (" " + rest)[end + 1:] if end is not None else ""
    return name, f"{name} {m.group(1)}{attrs}"


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path):
    """A :class:`Trace` from the file jax's profiler wrote.  Device
    planes are ``/device:TPU:<n>``; their operations are the events of
    the ``XLA Ops`` line.  Host spans are the events of the host plane's
    lines that the Python tracer did not write (its names start with
    ``$``)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, signatures, seen = {}, [], {}, {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    text = ev.name
                    if text not in seen:
                        seen[text] = split_hlo(text)
                        signatures[seen[text][0]] = seen[text][1]
                    ops.append((seen[text][0], float(ev.start_ns),
                                float(ev.duration_ns)))
            devices[plane.name] = ops
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if name.startswith("$") or ev.duration_ns <= 0:
                        continue
                    host.append((name, float(ev.start_ns),
                                 float(ev.duration_ns)))
    return Trace(devices, host, signatures)


def describe_xplane(path):
    """Planes, lines and event counts: what to look at by hand before
    trusting a reduction on a new backend."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = list(line.events)
            first = min((e.start_ns for e in evs), default=None)
            out.append((plane.name, line.name, len(evs), first))
    return out
