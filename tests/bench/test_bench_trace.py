"""The reduction from a trace to numbers, on a small synthetic trace
whose answers can be worked out by hand."""

import re

import pytest

from benchmarks.lib import trace as T

# one device, a 100 ns window; operations (name, start, duration)
OPS = [
    ("fusion.1", 0, 10),
    ("flash_fwd", 10, 20),
    ("fusion.2", 25, 10),        # overlaps flash_fwd by 5
    ("all-reduce.1", 50, 10),    # alone: exposed
    ("all-reduce.2", 70, 10),    # half under fusion.3
    ("fusion.3", 75, 15),
    ("while.1", 0, 90),          # a container: covers everything to 90
]
LEAF = [op for op in OPS if not op[0].startswith("while")]


def test_busy_is_the_union_of_intervals():
    # 0-35, 50-60, 70-90
    assert T.busy_ns(LEAF, 0, 100) == 35 + 10 + 20
    assert T.busy_ns(LEAF, 20, 80) == 15 + 10 + 10
    assert T.busy_ns([], 0, 100) == 0
    # nested events count once
    assert T.busy_ns(OPS, 0, 100) == 90


def test_idle_gaps_are_the_complement():
    gaps = T.idle_gaps(LEAF, 0, 100)
    assert gaps == [(35, 50), (60, 70), (90, 100)]
    assert sum(b - a for a, b in gaps) == 100 - T.busy_ns(LEAF, 0, 100)


@pytest.mark.parametrize("pattern,ns,calls", [
    ("flash", 20, 1),
    (re.compile("fusion"), 35, 3),
    ("all-reduce", 20, 2),
    ("nothing", 0, 0),
])
def test_kernel_time_by_name(pattern, ns, calls):
    assert T.kernel_ns(OPS, 0, 100, pattern) == (ns, calls)


def test_kernel_time_is_clipped_to_the_window():
    assert T.kernel_ns(OPS, 15, 100, "flash") == (15, 1)


def test_top_ops_leave_containers_out():
    top = T.top_ops(OPS, 0, 100, n=3)
    assert [name for name, _ in top] == ["flash_fwd", "fusion.3", "fusion.1"] \
        or [name for name, _ in top][0] == "flash_fwd"
    assert all(not name.startswith("while") for name, _ in top)
    assert top[0][1] == pytest.approx(20e-9)


def test_gaps_go_to_the_innermost_open_host_span():
    spans = [("outer", 30, 50), ("inner", 58, 14), ("late", 95, 2)]
    got = dict(T.attribute_gaps(T.idle_gaps(LEAF, 0, 100), spans))
    assert got["outer"] == pytest.approx(15e-9)      # gap 35-50
    assert got["inner"] == pytest.approx(10e-9)      # gap 60-70
    assert got["late"] == pytest.approx(10e-9)       # gap 90-100, middle 95
    spans = [("outer", 30, 50)]
    got = dict(T.attribute_gaps(T.idle_gaps(LEAF, 0, 100), spans))
    assert got["(no span)"] == pytest.approx(10e-9)


def test_span_durations():
    spans = [("a", 0, 5), ("b", 1, 2), ("a", 10, 7)]
    assert T.span_durations(spans, "a") == [5, 7]


def test_a_recorded_trace_holds_the_harness_span(tmp_path):
    """The xplane layer on a real (CPU) trace: the window's annotation is
    found among the host spans, and the Python tracer wrote nothing."""
    import jax.numpy as jnp

    from benchmarks.lib import tracing

    tracing.start(str(tmp_path))
    (jnp.ones((64, 64)) @ jnp.ones((64, 64))).block_until_ready()
    tracing.stop()
    tr = T.read_xplane(T.find_xplane(str(tmp_path)))
    win = tr.span(tracing.WINDOW_SPAN)
    assert win is not None and win[2] > 0
    assert not any(name.startswith("$") for name, _, _ in tr.host)
    assert T.describe_xplane(T.find_xplane(str(tmp_path)))
