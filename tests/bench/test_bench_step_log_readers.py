"""The readers of the serve engine's own log (ISSUE 39,
``benchmarks/lib/step_log_readers.py``), every one through the loader, on
a synthetic log whose answers can be worked out by hand.

The logs are real rings (``unicore_tpu/serve/step_log.py``) put behind
``step_logs()`` the way an engine does at construction; a row's
``emitted_at`` is the ring's own reading of ``time.perf_counter``, so the
window is taken around the writes."""

import os
import time

import pytest

from benchmarks.lib import spec
from unicore_tpu.serve import step_log

BASE = os.path.join(spec.ROOT, "benchmarks")
CHAT, DOCS, HYBRID = "opt13b_chat", "opt13b_docs_batch", "olmo_hybrid_longdocs"

# (width, carried, capacity, decode rows, ran ahead, device_s) of the six
# steps of the window, ordinals 3-8
STEPS = [
    (1, 5, 32, 5, False, 0.010),
    (1, 6, 32, 5, True, 0.030),
    (128, 100, 256, 5, True, 0.050),
    (1, 4, 32, 4, False, 0.020),
    (128, 156, 256, 3, False, 0.040),
    (128, 64, 256, 2, True, 0.090),
]
# (admitted, first token, first step) on a clock of the test's own, far
# from perf_counter; the first and the last were emitted by steps outside
# the window
FIRSTS = [
    (1.001, 1.010, 2),
    (2.004, 2.054, 5),
    (3.010, 3.040, 7),
    (4.001, 4.071, 7),
    (5.500, 5.600, 9),
]
# what each metric reads of that window
WANT = {
    "serve_emitted_decode_ms": 20.0,          # median of 10, 30, 20
    "serve_emitted_mixed_ms": 50.0,           # median of 50, 40, 90
    "serve_mixed_fill_pct": 100.0 * 320 / 768,
    "serve_run_ahead_pct": 50.0,
    "serve_decode_rows_per_step": 4.0,        # 24 over 6
    "serve_prefill_ms": 50.0,                 # median of 50, 30, 70
}
CPU = ("serve_thread_cpu_ms_per_step", "serve_process_cpu_ms_per_step")


def _metrics():
    """The thirteen entries of the real ``BENCHMARK.json`` whose reader
    reads the log."""
    stems = set(WANT) | set(CPU)
    return [m for m in spec.load_benchmark()["per_layer"]
            if m["name"].rsplit(".", 1)[0] in stems]


def _write_step(log, ordinal, row):
    log.write(ordinal, *row)


@pytest.fixture
def logs():
    """Two rows before the window, the six of ``STEPS`` inside it, one
    after; behind ``step_logs()`` for the test, what was there after."""
    before = step_log.step_logs()
    steps, firsts = step_log.StepLog(), step_log.FirstTokenLog()
    for ordinal in (1, 2):
        _write_step(steps, ordinal, (1, 32, 32, 32, True, 9.0))
    t0 = time.perf_counter()
    for ordinal, row in enumerate(STEPS, 3):
        _write_step(steps, ordinal, row)
    t1 = time.perf_counter()
    _write_step(steps, 9, (128, 256, 256, 9, True, 9.0))
    for row in FIRSTS:
        firsts.write(*row)
    step_log.publish(steps, firsts)
    # three calls that launched: the first starts the window, the last
    # ends it
    half = (t1 - t0) / 2
    yield {"steps": [(t0, half / 2, 1), (t0 + half, half / 4, 128),
                     (t1 - half / 4, half / 4, 1)]}
    step_log._latest = before


def _read(metric):
    return spec.load_reader(metric["name"], BASE)


def test_there_are_thirteen_and_each_names_its_cells():
    metrics = _metrics()
    assert len(metrics) == 13
    for m in metrics:
        assert m["source"] == "program_counter"
        kind = m["name"].rsplit(".", 1)[1]
        assert m["moves"] == ("serve_itl_p95_ms" if kind == "chat"
                              else "serve_tokens_per_s")
        # a step of width 1 is what the hybrid cell never launches, and
        # a metric lists the cells where its reader finds something
        alone = m["name"] == "serve_emitted_decode_ms.closed"
        assert m["workloads"] == {"chat": [CHAT], "closed": (
            [DOCS] if alone else [DOCS, HYBRID])}[kind]
        assert m["layer"] == ("entry points" if m["name"].startswith(CPU)
                              else "serve engine + scheduler")
    named = {w for m in metrics for w in m["workloads"]}
    assert named == {CHAT, DOCS, HYBRID}


@pytest.mark.parametrize("metric", _metrics(), ids=lambda m: m["name"])
def test_the_value_of_each_reader_on_a_log_worked_out_by_hand(
        metric, logs, capsys):
    value = _read(metric)(logs)
    stem = metric["name"].rsplit(".", 1)[0]
    selected = logs["step_log_rows"][0]
    assert selected["ordinal"].tolist() == [3, 4, 5, 6, 7, 8]
    assert logs["step_log_rows"][1]["first_step"].tolist() == [5, 7, 7]
    if stem in WANT:
        assert value == pytest.approx(WANT[stem])
    else:
        # the ring read the clocks itself: the mean of what it stored,
        # the window's first row left out
        clock = "thread_cpu_s" if "thread" in stem else "process_cpu_s"
        assert value == pytest.approx(selected[clock][1:].mean() * 1e3)
        assert value >= 0.0
    said = "".join(capsys.readouterr())
    assert "6 rows selected (ordinals 3-8; 3 mixed, 3 ahead) over 3 " \
           "calls" in said
    assert "no trace" in said and "3 first tokens" in said
    # the five the device had longest, with their ordinals
    assert "[(8, 90.0), (5, 50.0), (7, 40.0), (4, 30.0), (6, 20.0)]" in said


@pytest.mark.parametrize("metric", _metrics(), ids=lambda m: m["name"])
def test_a_context_without_steps_is_nothing_to_read(metric, logs):
    assert _read(metric)({}) is None
    assert _read(metric)({"steps": []}) is None


@pytest.mark.parametrize("metric", _metrics(), ids=lambda m: m["name"])
def test_a_log_with_no_row_in_the_window_is_nothing_to_read(
        metric, logs, capsys):
    late = time.perf_counter() + 1.0
    assert _read(metric)({"steps": [(late, 0.5, 1)]}) is None
    assert "none of the ring's 9 rows" in "".join(capsys.readouterr())


@pytest.mark.parametrize("metric", _metrics(), ids=lambda m: m["name"])
def test_a_program_without_step_logs_is_nothing_to_read(
        metric, logs, monkeypatch, capsys):
    """The parent of PR 39: ``unicore_tpu.serve`` exports no
    ``step_logs``.  The reader says so and the line goes without it."""
    import unicore_tpu.serve as serve

    monkeypatch.delitem(serve._EXPORTS, "step_logs")
    assert _read(metric)(dict(logs)) is None
    assert "has no step_logs" in "".join(capsys.readouterr())


def test_rows_and_spans_are_counted_side_by_side(logs, capsys):
    """With a trace in the context the rows selected stand beside the
    window's ``serve/emit`` spans: within one they agree."""
    from benchmarks.lib import step_log_readers as R
    from benchmarks.lib.trace import Trace

    def said(emits):
        host = [("serve/emit", 100 * i, 10) for i in range(emits)]
        host += [("serve/emit", 5000, 10), ("serve/fetch", 0, 50)]
        ctx = dict(logs, trace=Trace({}, host), t0=0, t1=1000)
        assert R.run_ahead_pct(ctx) == pytest.approx(50.0)
        return "".join(capsys.readouterr())

    assert "spans in the traced window: 6 (agree)" in said(6)
    assert "spans in the traced window: 5 (agree)" in said(5)
    assert "spans in the traced window: 3 (DISAGREE)" in said(3)


def test_no_first_token_in_the_window_leaves_the_steps_readable(logs):
    from benchmarks.lib import step_log_readers as R

    steps, _ = step_log.step_logs()
    step_log.publish(steps, step_log.FirstTokenLog())
    ctx = dict(logs)
    assert R.prefill_ms(ctx) is None
    assert R.decode_rows_per_step(ctx) == pytest.approx(4.0)
    # a window of decode steps alone has no mixed step to read (the
    # hybrid cell's other way round: no step of width 1)
    decode, t0 = step_log.StepLog(), time.perf_counter()
    for ordinal, row in enumerate(STEPS[:2], 1):
        _write_step(decode, ordinal, row)
    step_log.publish(decode, step_log.FirstTokenLog())
    only = {"steps": [(t0, time.perf_counter() - t0, 1)]}
    assert R.emitted_ms(only, mixed=True) is None
    assert R.mixed_fill_pct(only) is None
    assert R.emitted_ms(only, mixed=False) == pytest.approx(20.0)


@pytest.mark.parametrize("clock", ["thread_cpu_s", "process_cpu_s"])
def test_the_cpu_mean_leaves_out_the_windows_first_row(clock, capsys):
    """The first row's advance reaches back to the row before the window
    (a traced run starts the profiler there): it is logged and left out
    of the mean, and a window of one row has no mean."""
    from benchmarks.lib import step_log_readers as R

    steps = step_log.StepLog()
    before = step_log.step_logs()
    try:
        step_log.publish(steps, step_log.FirstTokenLog())
        _write_step(steps, 1, STEPS[0])
        sum(i * i for i in range(300_000))  # CPU burnt outside the window
        t0 = time.perf_counter()
        for ordinal, row in enumerate(STEPS[:4], 2):
            _write_step(steps, ordinal, row)
        whole = {"steps": [(t0, time.perf_counter() - t0, 1)]}
        rows = steps.rows()
        assert rows[clock][1] > 10 * rows[clock][2:].max()
        assert R.cpu_ms_per_step(whole, clock) == pytest.approx(
            rows[clock][2:].mean() * 1e3)
        said = "".join(capsys.readouterr())
        assert "CPU over 3 steps" in said and "left out" in said
        one = step_log.StepLog()
        step_log.publish(one, step_log.FirstTokenLog())
        t0 = time.perf_counter()
        _write_step(one, 1, STEPS[0])
        alone = {"steps": [(t0, time.perf_counter() - t0, 1)]}
        assert R.cpu_ms_per_step(alone, clock) is None
        assert R.decode_rows_per_step(alone) == pytest.approx(5.0)
    finally:
        step_log._latest = before
