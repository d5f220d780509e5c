"""The engine's own log (ISSUE 39, ``unicore_tpu/serve/step_log.py``):
one row a step EMITTED, one row a request at its first token, in two
preallocated rings.  Every case reads ``engine.step_log`` /
``engine.first_token_log`` of the engine it built; only the last case
reads the module's ``step_logs()``."""

import time

import numpy as np
import pytest

import unicore_tpu.serve as serve
from tests.test_serve_run_ahead import (ROWS, V, _multi_head, drive,
                                        engine_of, prompts, reqs)
from unicore_tpu.serve import Request
from unicore_tpu.serve import step_log as step_log_lib
from unicore_tpu.serve.step_log import RING_ROWS, FirstTokenLog, StepLog


@pytest.fixture(scope="module")
def lm():
    return _multi_head()


def _driven(lm, rows):
    """An engine that ran ``reqs()``; ``engine.fetches`` holds, a step,
    when it was launched and when its tokens were on the host (the log
    keeps neither: nothing reads them)."""
    engine = engine_of(lm, rows)
    engine.fetches, fetch = [], engine._fetch

    def fetching(step):
        toks = fetch(step)
        engine.fetches.append((step.launched_at, engine._fetched_at))
        return toks

    engine._fetch = fetching
    drive(engine, reqs()())
    return engine


@pytest.fixture(scope="module")
def full(lm):
    """A full batch (``ROWS`` rows, ``ROWS`` requests at a time): the
    engine runs ahead."""
    return _driven(lm, ROWS)


@pytest.fixture(scope="module")
def free(lm):
    """The same requests with a row always free: every step synchronous."""
    return _driven(lm, ROWS + 1)


# -- a row a step, in order -----------------------------------------------


def _one_row_a_step(engine):
    rows = engine.step_log.rows()
    assert len(rows) == engine._steps_emitted == len(engine.step_log)
    assert rows["ordinal"].tolist() == list(range(1, len(rows) + 1))


def _clocks_in_order(engine):
    rows = engine.step_log.rows()
    launched, fetched = np.array(engine.fetches).T
    assert (launched < fetched).all()
    assert (fetched <= rows["emitted_at"]).all()
    assert (np.diff(rows["emitted_at"]) > 0).all()
    assert (rows["device_s"] > 0).all()


def _decode_rows_are_the_counters(engine):
    rows, stats = engine.step_log.rows(), engine.stats
    assert rows["decode_rows"].sum() == stats["decode_tokens"]
    decode = rows[rows["decode_rows"] > 0]
    assert len(decode) == stats["decode_steps"]
    assert decode["device_s"].sum() == pytest.approx(stats["decode_time_s"])
    assert (rows["decode_rows"] <= engine.max_batch).all()
    # a step of width 1 carries one token a row: what it handed out, less
    # a prompt's last token riding alone
    narrow = rows[rows["width"] == 1]
    assert (narrow["decode_rows"] <= narrow["carried"]).all()


def _fill_is_the_mixed_counters(engine):
    rows, stats = engine.step_log.rows(), engine.stats
    mixed = rows[rows["width"] > 1]
    assert len(mixed) == stats["mixed_steps"] > 0
    assert mixed["carried"].sum() == stats["mixed_tokens_carried"]
    assert mixed["capacity"].sum() == stats["mixed_tokens_capacity"]
    assert set(mixed["capacity"].tolist()) == {engine.mixed_tokens}
    decode = rows[rows["width"] == 1]
    assert set(decode["capacity"].tolist()) == {engine.max_batch}
    assert (rows["carried"] <= rows["capacity"]).all()


def _device_s_is_fetch_to_fetch_behind_a_step_in_flight(engine):
    """A step launched ahead started when the step before it was done:
    its ``device_s`` runs from that fetch, and it was launched before it;
    a step launched behind nothing runs from its own launch."""
    rows = engine.step_log.rows()
    assert len(engine.fetches) == len(rows)
    for n in range(1, len(rows)):
        (_, before), (launched, fetched) = engine.fetches[n - 1:n + 1]
        if rows["ran_ahead"][n]:
            assert launched < before
            assert rows["device_s"][n] == pytest.approx(fetched - before)
        else:
            assert launched >= before
            assert rows["device_s"][n] == pytest.approx(fetched - launched)


def _cpu_clocks_advance(engine):
    rows = engine.step_log.rows()
    assert (rows["thread_cpu_s"] > 0).all()
    assert (rows["process_cpu_s"] > 0).all()
    # every thread's time holds the loop's thread's
    assert rows["process_cpu_s"].sum() >= 0.9 * rows["thread_cpu_s"].sum()
    # no more CPU on one thread than wall time went by
    wall = rows["emitted_at"][-1] - rows["emitted_at"][0]
    assert rows["thread_cpu_s"][1:].sum() <= 1.05 * wall + 0.01


EVERY_ENGINE = [_one_row_a_step, _clocks_in_order,
                _decode_rows_are_the_counters, _fill_is_the_mixed_counters,
                _device_s_is_fetch_to_fetch_behind_a_step_in_flight,
                _cpu_clocks_advance]


@pytest.mark.parametrize("which", ["full", "free"])
@pytest.mark.parametrize("case", EVERY_ENGINE,
                         ids=lambda f: f.__name__.strip("_"))
def test_the_step_log_of_a_run(case, which, request):
    case(request.getfixturevalue(which))


def test_ran_ahead_on_a_batch_that_fills(full, free):
    rows = full.step_log.rows()
    assert rows["ran_ahead"].sum() == full.stats["steps_run_ahead"] > 10
    # the first step has nothing to run behind
    assert not rows["ran_ahead"][0]
    quiet = free.step_log.rows()
    assert free.stats["steps_run_ahead"] == 0
    assert not quiet["ran_ahead"].any()


def test_an_overrun_row_is_not_among_the_rows_handed_out(lm):
    plain = drive(engine_of(lm, ROWS + 1), reqs(3, new=(14,))())[0]
    tokens = plain["r1"].tokens
    at = next(i for i in range(3, len(tokens))
              if tokens[i] not in tokens[:i])

    def make():
        rs = reqs(3, new=(14,))()
        rs[1].eos_id = tokens[at]
        return rs

    engine = engine_of(lm, ROWS)
    got = drive(engine, make())[0]
    assert got["r1"].finish_reason == "eos"
    assert engine.stats["tokens_overrun"] == 1
    # the same requests with a row free: every step synchronous, so the
    # end is seen before the next launch and nothing is dropped
    sync = engine_of(lm, ROWS + 1)
    drive(sync, make())
    assert sync.stats["tokens_overrun"] == 0
    rows, want = engine.step_log.rows(), sync.step_log.rows()
    # the list of one step carried the token that was handed to nobody
    assert rows["carried"].sum() == want["carried"].sum() + 1
    assert rows["decode_rows"].sum() == want["decode_rows"].sum()
    assert rows["decode_rows"].sum() == engine.stats["decode_tokens"]


# -- the rings ---------------------------------------------------------------


def _write(log, ordinal):
    log.write(ordinal, 1, 3, 4, 3, False, 0.5)


def test_the_ring_wraps_and_between_is_still_right():
    log = StepLog()
    marks = {}
    for n in range(1, RING_ROWS + 501):
        if n in (4200, 4301):
            marks[n] = time.perf_counter()
        _write(log, n)
    assert log.written == RING_ROWS + 500 and len(log) == RING_ROWS
    rows = log.rows()
    assert rows["ordinal"].tolist() == list(range(501, RING_ROWS + 501))
    got = log.between(marks[4200], marks[4301])
    assert got["ordinal"].tolist() == list(range(4200, 4301))
    assert len(log.between(0.0, marks[4200])) == 4199 - 500
    assert len(log.between(time.perf_counter(), time.perf_counter() + 1)) == 0
    # a reader's copy does not move with the ring
    _write(log, 0)
    assert rows["ordinal"][0] == 501 and log.rows()["ordinal"][0] == 502


def test_an_empty_and_a_part_filled_ring():
    log = StepLog()
    assert len(log) == 0 and len(log.rows()) == 0
    assert len(log.between(0.0, time.perf_counter())) == 0
    for n in (1, 2, 3):
        _write(log, n)
    assert log.rows()["ordinal"].tolist() == [1, 2, 3]
    assert log.rows().dtype == step_log_lib.STEP_ROW
    first = FirstTokenLog()
    first.write(2.0, 3.0, 7)
    (row,) = first.rows()
    assert row.tolist() == (2.0, 3.0, 7)
    assert first.between(2.5, 3.5)["first_step"].tolist() == [7]
    assert len(first.between(3.5, 4.5)) == 0


def test_nothing_is_allocated_a_step_over_ten_thousand_steps(lm):
    """The compiled call stubbed out: 10,000 decode steps write 10,000
    rows into the arrays the log was built with."""
    import jax.numpy as jnp

    engine = engine_of(lm, ROWS + 1)
    out = jnp.asarray(np.full(engine._out_size(), 7, np.int32))
    engine._ragged_step_fn = lambda w, sampling: (
        lambda params, pages, packed, prev: (out, pages))
    log = engine.step_log
    arrays = [log._rows] + list(log._cols)
    ids, sizes = [id(a) for a in arrays], [a.shape for a in arrays]
    while log.written < 10_000:
        if not engine.has_work():
            engine.collect_finished()
            engine.submit([Request(prompt=p, max_new_tokens=200)
                           for p in prompts(ROWS, longest=16)])
        engine.serve_step()
    assert log.written >= 10_000 and len(log) == RING_ROWS
    assert [id(a) for a in [log._rows] + list(log._cols)] == ids
    assert [a.shape for a in arrays] == sizes
    rows = log.rows()
    assert (np.diff(rows["ordinal"]) == 1).all()
    assert rows["ordinal"][-1] == log.written == engine._steps_emitted


# -- first tokens -----------------------------------------------------------


def _joined(engine):
    """Every first-token row beside the step row that emitted it."""
    steps = {int(r["ordinal"]): r for r in engine.step_log.rows()}
    return [(f, steps[int(f["first_step"])])
            for f in engine.first_token_log.rows()]


def test_first_step_joins_a_request_to_the_step_that_emitted_it(full):
    pairs = _joined(full)
    assert len(pairs) == 6
    fetched = {n: at for n, (_, at) in enumerate(full.fetches, 1)}
    for first, step in pairs:
        # the engine's clock is perf_counter here: the stamp lies inside
        # the emit of that step
        assert fetched[int(step["ordinal"])] <= first[
            "first_token_at"] <= step["emitted_at"]
        assert first["admitted_at"] < first["first_token_at"]
    firsts = full.first_token_log.rows()
    assert (np.diff(firsts["first_step"]) >= 0).all()
    assert (np.diff(firsts["first_token_at"]) > 0).all()


def test_first_step_under_a_prefix_hit_and_after_an_eviction(lm):
    """An injected clock counts calls of itself, so it never meets
    ``perf_counter``: the join is by ordinal."""
    # below zero, where ``perf_counter`` never reads (a machine that has
    # been up for under three hours reads under 10,000 there)
    ticks = iter(range(-10_000, 0))
    engine = engine_of(lm, ROWS + 1, clock=lambda: float(next(ticks)))
    doc = prompts(1, seed=11, shared=16, longest=7)[0]
    first = Request(prompt=doc + [9, 8, 7], max_new_tokens=2, request_id="a")
    engine.generate([first])
    # a hit on the document's two full pages; and a prompt of 100 tokens
    # takes two steps (the list holds 64), preempted by hand between them
    second = Request(prompt=doc + [5, 6], max_new_tokens=2, request_id="b")
    rng = np.random.default_rng(3)
    third = Request(prompt=rng.integers(4, V, 100).tolist(),
                    max_new_tokens=2, request_id="c")
    seqs = engine.submit([second, third])
    engine.serve_step()
    assert seqs[1].first_token_at is None and seqs[1].prefilled > 0
    engine.scheduler.preempt(seqs[1])
    while engine.has_work():
        engine.serve_step()
    results = {r.request_id: r for r in engine.collect_finished()}
    assert results["b"].evictions == 0 and results["c"].evictions == 1
    assert engine.pool.prefix_stats["hits"] == 1
    cold, hit, evicted = _joined(engine)
    # the hit's first token left with the first step after its admission,
    # the evicted prompt's after it had been taken up again
    assert hit[0]["first_step"] == cold[0]["first_step"] + 2
    assert evicted[1]["ordinal"] > hit[0]["first_step"]
    for f, step in (cold, hit, evicted):
        # the injected clock's negative whole numbers, perf_counter's positive
        assert f["admitted_at"] < f["first_token_at"] < 0 < step[
            "emitted_at"]
    # a resumed sequence keeps its first admission: the row's prefill is
    # the result's first token less its wait
    for (f, _), rid in ((hit, "b"), (evicted, "c")):
        assert (f["first_token_at"] - f["admitted_at"]) * 1e3 == pytest.approx(
            results[rid].ttft_ms - results[rid].queue_ms)


# -- what reads the logs ----------------------------------------------------


def test_load_snapshots_step_ms_is_the_median_of_the_last_decode_steps(
        lm, full):
    engine = engine_of(lm, ROWS)
    assert engine.load_snapshot()["step_ms"] == 0.0
    # 100 rows of 1, 2, ... ms, every other one with a decode row: the
    # last 33 of those are rows 36, 38, ... 100, and their median is 68
    for n in range(1, 101):
        engine.step_log.write(n, 1, 3, 4, 3 * (n % 2 == 0), False, n * 1e-3)
    assert engine.load_snapshot()["step_ms"] == pytest.approx(68.0)
    rows = full.step_log.rows()
    recent = sorted(rows["device_s"][rows["decode_rows"] > 0][-33:] * 1e3)
    assert 0 < len(recent) <= 33
    assert full.load_snapshot()["step_ms"] == round(
        float(recent[len(recent) // 2]), 4)


def test_bench_reads_the_decode_steps_since_a_mark_and_refuses_a_cut_window():
    """``bench.py`` marks ``step_log.written`` before a flood and reads
    the decode steps written since; the ring holds steps of every kind,
    so a stretch longer than the ring is refused, not read short."""
    from types import SimpleNamespace

    import bench

    log = StepLog(size=8)
    engine = SimpleNamespace(step_log=log)
    for n in range(1, 6):  # steps 1, 3, 5 hold a decode row
        log.write(n, 1, 3, 4, n % 2, False, n * 1e-3)
    assert bench._decode_ms_since(engine, 2) == pytest.approx([3.0, 5.0])
    assert bench._decode_ms_since(engine, 5) == []
    for n in range(6, 12):  # the ring wraps
        log.write(n, 1, 3, 4, 1, False, n * 1e-3)
    assert bench._decode_ms_since(engine, 5) == pytest.approx(
        [6.0, 7.0, 8.0, 9.0, 10.0, 11.0])
    with pytest.raises(RuntimeError, match="9 steps since the mark"):
        bench._decode_ms_since(engine, 2)


def test_the_summary_an_operator_reads():
    log = StepLog()
    for width, carried, capacity, ahead, device_s in (
            (1, 3, 4, True, 0.010), (1, 3, 4, True, 0.020),
            (1, 4, 4, False, 0.030), (16, 20, 64, False, 0.040),
            (16, 44, 64, True, 0.060)):
        log.write(log.written + 1, width, carried, capacity, 2, ahead,
                  device_s)
    got = step_log_lib.summary(log.rows())
    assert got["rows"] == 5
    assert got["decode"] == {"rows": 3, "device_ms_median": 20.0,
                             "device_ms_p95": 29.0}
    assert got["mixed"] == {"rows": 2, "device_ms_median": 50.0,
                            "device_ms_p95": 59.0}
    assert got["mixed_fill_pct"] == 50.0 and got["run_ahead_pct"] == 60.0
    # the two CPU means leave out the first row, whose advance reaches
    # back to the making of the log
    rows = log.rows()
    assert got["thread_cpu_ms_per_step"] == round(
        float(rows["thread_cpu_s"][1:].mean()) * 1e3, 4)
    assert got["process_cpu_ms_per_step"] == round(
        float(rows["process_cpu_s"][1:].mean()) * 1e3, 4)
    assert "thread_cpu_ms_per_step" not in step_log_lib.summary(rows[:1])
    assert step_log_lib.summary(StepLog().rows()) == {"rows": 0}


def test_the_logs_of_the_engine_built_last_are_behind_step_logs(lm):
    one = engine_of(lm, ROWS)
    assert serve.step_logs() == (one.step_log, one.first_token_log)
    two = engine_of(lm, ROWS)
    assert serve.step_logs()[0] is two.step_log
    assert serve.step_logs()[1] is two.first_token_log
    assert one.step_log is not two.step_log
    two.generate([Request(prompt=[5, 6, 7], max_new_tokens=2)])
    assert len(one.step_log) == 0 and len(serve.step_logs()[0]) == 2
    assert isinstance(one.step_log, StepLog)
    assert isinstance(one.first_token_log, FirstTokenLog)
