"""The watch on the host while a window runs: a main thread that is held
up shows in the clocks, and the ticker tells a frozen process from a busy
or a waiting one."""

import gc
import time

from benchmarks.lib import hostwatch


def _watched(work):
    w = hostwatch.Watch().start()
    t0 = time.perf_counter()
    work()
    w.stop()
    return w, t0, time.perf_counter() - t0


def test_a_sleeping_main_thread_leaves_the_ticker_on_time():
    w, t0, took = _watched(lambda: time.sleep(0.6))
    assert len(w.ticks) >= 3 and not w.late_ticks()
    clocks = w.between(t0, t0 + took)
    assert clocks["process_cpu_s"] < 0.5 and clocks["sampled_s"] >= took - 0.01


def test_a_ticker_that_woke_late_is_listed_with_its_delay():
    w, t0, _ = _watched(lambda: time.sleep(0.25))
    w.ticks = [t0 + 0.1, t0 + 0.2, t0 + 1.7, t0 + 1.8]  # frozen for 1.5 s
    late = w.late_ticks()
    assert len(late) == 1 and abs(late[0][1] - 1.5) < 1e-6


def test_the_report_names_each_hold_up_and_counts_gc_pauses():
    lines = []

    def work():
        gc.collect()
        time.sleep(0.25)

    w, t0, took = _watched(work)
    w.report(lambda *a: lines.append(" ".join(map(str, a))), t0,
             [(t0, took, "the whole of it")])
    assert len(lines) == 2 and "gc pauses 1" in lines[0]
    assert "the whole of it" in lines[1] and "process_cpu_s" in lines[1]
    assert w._on_gc not in gc.callbacks  # nothing left behind
