"""The readers of the serve engine's spans, on a small synthetic trace
whose answers can be worked out by hand: idle time goes to a phase by
overlap with its spans, whatever is nested inside them."""

import os

import pytest

from benchmarks.lib import span_readers as S
from benchmarks.lib import spec
from benchmarks.lib.trace import Trace

PLANE = "/device:TPU:0"
# one device, a window of 1000 ns; it idles in 100-300, 500-600, 800-1000
OPS = [("fusion.1", 0, 100), ("ragged_paged_attention.1", 300, 200),
       ("fusion.2", 600, 200)]
# two steps and the harness between them: (name, start, duration)
HOST = [
    ("bench/serve_step", 90, 540),
    ("serve/step", 100, 520),
    ("serve/schedule", 100, 50),          # idle 100-150
    ("serve/admit", 110, 30),
    ("serve/plan", 150, 10),              # idle 150-160
    ("serve/assemble", 160, 40),          # idle 160-200
    ("serve/transfer", 200, 90),          # idle 200-290
    ("serve/dispatch-w128", 290, 290),
    ("serve/launch", 290, 20),            # idle 290-300
    ("serve/fetch", 310, 270),            # idle 500-580
    ("serve/emit", 580, 40),              # idle 580-600
    ("bench/collect", 630, 100),
    ("serve/step", 900, 200),             # runs past the window's end
    ("serve/schedule", 900, 20),
    ("serve/admit", 905, 5),
    ("serve/plan", 920, 30),
    ("serve/transfer", 950, 100),         # clipped at the window's end
    ("serve/dispatch-w1", 1050, 40),
]
# what the runtime records inside the engine's spans
NESTED = [("DevicePutWithSharding", 210, 60), ("Linearize", 220, 30),
          ("np.asarray(jax.Array)", 320, 250), ("shard_args", 291, 5)]
WANT = {"host": 50 + 10 + 40 + 20 + 20 + 30, "transfer": 90 + 50,
        "dispatch": 10 + 80, "outside": 100}


def ctx_of(host, ops=OPS, t0=0, t1=1000):
    return {"trace": Trace({PLANE: ops}, host), "planes": [PLANE],
            "t0": t0, "t1": t1, "window_s": (t1 - t0) / 1e9}


def shares(ctx):
    return {"host": S.idle_inside_pct(ctx, S.HOST),
            "transfer": S.idle_inside_pct(ctx, S.TRANSFER),
            "dispatch": S.idle_inside_pct(ctx, S.DISPATCH),
            "outside": S.idle_outside_steps_pct(ctx)}


def test_overlap_of_gaps_with_a_union_of_intervals():
    gaps = [(100, 300), (500, 600), (800, 1000)]
    assert S.overlap_ns(gaps, []) == 0
    assert S.overlap_ns(gaps, [(0, 2000)]) == 500
    # nested and overlapping intervals count once; order does not matter
    assert S.overlap_ns(gaps, [(550, 900), (250, 520), (260, 270)]) \
        == 50 + 20 + 50 + 100
    assert S.overlap_ns(gaps, [(300, 500), (600, 800)]) == 0


def test_idle_goes_to_the_phase_it_overlaps():
    got = shares(ctx_of(HOST))
    assert got == {k: pytest.approx(v / 10.0) for k, v in WANT.items()}


def test_a_gap_half_in_transfer_and_half_outside_any_step_is_split():
    host = [("serve/step", 850, 50), ("serve/transfer", 850, 50)]
    ops = [("fusion.1", 0, 800), ("fusion.2", 900, 100)]
    got = shares(ctx_of(host, ops))
    assert got["transfer"] == pytest.approx(5.0)
    assert got["outside"] == pytest.approx(5.0)
    assert got["host"] == got["dispatch"] == 0.0


def test_runtime_spans_nested_in_a_phase_do_not_change_the_answer():
    assert shares(ctx_of(HOST + NESTED)) == shares(ctx_of(HOST))


def test_the_four_shares_sum_to_the_idle_share():
    ctx = ctx_of(HOST + NESTED)
    busy = 100 + 200 + 200
    assert sum(shares(ctx).values()) == pytest.approx(100.0 - busy / 10.0)


def test_the_window_clips_spans_and_gaps():
    got = shares(ctx_of(HOST, t0=250, t1=560))
    # idle 250-300 and 500-560: transfer 250-290, launch 290-300, fetch
    assert got["transfer"] == pytest.approx(100 * 40 / 310)
    assert got["dispatch"] == pytest.approx(100 * 70 / 310)
    assert got["host"] == got["outside"] == 0.0


def test_dispatch_durations_by_width():
    ctx = ctx_of(HOST + [("serve/dispatch-w128", 700, 100),
                         ("serve/dispatch-w64", 810, 50),
                         ("serve/dispatch-w1", 20, 30)], t1=1100)
    assert S.dispatch_ms(ctx, mixed=True) == pytest.approx(100 / 1e6)
    assert S.dispatch_ms(ctx, mixed=False) == pytest.approx(35 / 1e6)
    # a span that lies outside the window is not the window's
    assert S.dispatch_ms(ctx_of(HOST), mixed=False) is None


def test_admit_time_per_step():
    assert S.admit_ms_per_step(ctx_of(HOST)) == pytest.approx(35 / 2 / 1e6)
    only_first = ctx_of(HOST, t1=800)
    assert S.admit_ms_per_step(only_first) == pytest.approx(30 / 1e6)


def test_a_program_without_the_spans_gives_nothing_to_read():
    parent = ctx_of([s for s in HOST + NESTED
                     if not s[0].startswith("serve/")])
    assert set(shares(parent).values()) == {None}
    assert S.dispatch_ms(parent, mixed=True) is None
    assert S.dispatch_ms(parent, mixed=False) is None
    assert S.admit_ms_per_step(parent) is None
    # spans there, but none of a step inside this window
    assert set(shares(ctx_of(HOST, t0=650, t1=850)).values()) == {None}


NEW = {
    "serve_idle_host_pct": WANT["host"] / 10.0,
    "serve_idle_transfer_pct": WANT["transfer"] / 10.0,
    "serve_idle_dispatch_pct": WANT["dispatch"] / 10.0,
    "serve_idle_outside_pct": WANT["outside"] / 10.0,
    "serve_mixed_dispatch_ms": 290 / 1e6,
    "serve_decode_dispatch_ms": None,     # w1 lies past the window
    "serve_admit_ms": 35 / 2 / 1e6,
}


def new_metrics():
    return [m for m in spec.load_benchmark()["per_layer"]
            if m["name"].rsplit(".", 1)[0] in NEW]


@pytest.mark.parametrize("metric", new_metrics(), ids=lambda m: m["name"])
def test_each_new_reader_through_the_loader(metric):
    base = os.path.join(spec.ROOT, "benchmarks")
    read = spec.load_reader(metric["name"], base)
    want = NEW[metric["name"].rsplit(".", 1)[0]]
    got = read(ctx_of(HOST + NESTED))
    assert got == (want if want is None else pytest.approx(want))
    assert read(ctx_of(NESTED)) is None
    assert metric["source"] == "program_span" and metric["better"] == "lower"
    cell = spec.load_cell(metric["workloads"][0])
    assert metric["name"] in {m["name"] for m in cell["per_layer"]}


BROKEN = {
    "no window bounds": lambda c: [c.pop("t0"), c.pop("t1")],
    "a step that is no triple": lambda c: c["trace"].host.extend(
        [("serve/step",), ("serve/dispatch-w1",)]),
    "no trace at all": lambda c: c.pop("trace"),
}


@pytest.mark.parametrize("how", sorted(BROKEN))
@pytest.mark.parametrize("metric", new_metrics(), ids=lambda m: m["name"])
def test_a_context_a_reader_cannot_read_is_nothing_to_read(metric, how,
                                                           capsys):
    """The cell's run must not die of a reader this PR added: the fault
    is logged and the metric is left out of the line."""
    ctx = ctx_of(list(HOST + NESTED))
    BROKEN[how](ctx)
    base = os.path.join(spec.ROOT, "benchmarks")
    assert spec.load_reader(metric["name"], base)(ctx) is None
    assert "Error" in "".join(capsys.readouterr())


def test_the_twelve_new_metrics_are_listed():
    names = sorted(m["name"] for m in new_metrics())
    assert len(names) == 12
    assert [n for n in names if n.endswith(".chat")] == sorted(
        f"{b}.chat" for b in NEW if b != "serve_admit_ms")
    assert [n for n in names if n.endswith(".docs")] == sorted(
        f"{b}.docs" for b in NEW if b != "serve_decode_dispatch_ms")
