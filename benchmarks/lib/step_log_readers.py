"""Readers of the serve engine's own log (``unicore_tpu/serve/step_log.py``):
one row a step the engine EMITTED, one row a request at its first token.

The harness times a CALL of ``serve_step``; since the step in flight a
step is launched in one call and handed out in the next, so what a step
carried, how long the device had it and whether it ran ahead are read
from the rows the engine wrote, not rebuilt from outside.

The window is the harness's own: from the start of the first to the end
of the last of ``ctx["steps"]``, on ``time.perf_counter``, the clock of
the step rows' ``emitted_at``.  A window's first tokens are those whose
``first_step`` is the ``ordinal`` of a selected step row, so the engine's
own clock (which a test may inject) never meets ``perf_counter``.

The benchmark has released its engine by the time a reader runs:
``unicore_tpu.serve.step_logs()`` hands out the two logs of the engine
built last.  A reader returns None, and the metric is left out of the
line, where the context has no steps, where the program has no
``step_logs`` (every commit before PR 39) and where no row lies in the
window; a fault is logged and is nothing to read, as in
``span_readers``.
"""

import numpy as np

from . import span_readers
from .device import log

EMIT = "serve/emit"
_SELECTED = "step_log_rows"  # where one context keeps what was selected


def _select(ctx):
    """``(step rows, first-token rows)`` of the context's window, or None;
    selected and logged once a context."""
    if _SELECTED in ctx:
        return ctx[_SELECTED]
    ctx[_SELECTED] = None
    steps = ctx.get("steps")
    if not steps:
        return None
    import unicore_tpu.serve as serve

    step_logs = getattr(serve, "step_logs", None)
    if step_logs is None:
        log("step log: unicore_tpu.serve has no step_logs (a program "
            "from before PR 39): nothing to read")
        return None
    logs = step_logs()
    if logs is None:
        log("step log: no engine was built in this process")
        return None
    t0, t1 = steps[0][0], steps[-1][0] + steps[-1][1]
    rows = logs[0].between(t0, t1)
    if not len(rows):
        log(f"step log: none of the ring's {len(logs[0])} rows was "
            f"emitted in the window of {t1 - t0:.3f} s")
        return None
    firsts = logs[1].rows()
    firsts = firsts[np.isin(firsts["first_step"], rows["ordinal"])]
    _describe(ctx, rows, firsts, len(steps))
    ctx[_SELECTED] = rows, firsts
    return ctx[_SELECTED]


def _describe(ctx, rows, firsts, calls):
    """The rows selected beside the window's ``serve/emit`` spans (log
    and spans describe the same steps: the counts agree to within one),
    and the five steps the device had longest."""
    trace = ctx.get("trace")
    spans = "no trace"
    if trace is not None:
        t0, t1 = ctx["t0"], ctx["t1"]
        spans = sum(1 for name, at, dur in trace.host
                    if name == EMIT and at < t1 and at + dur > t0)
    longest = np.argsort(-rows["device_s"], kind="stable")[:5]
    log(f"step log: {len(rows)} rows selected (ordinals "
        f"{int(rows['ordinal'][0])}-{int(rows['ordinal'][-1])}; "
        f"{int((rows['width'] > 1).sum())} mixed, "
        f"{int(rows['ran_ahead'].sum())} ahead) over {calls} calls "
        f"that launched; {EMIT} spans in the traced window: {spans}"
        f"{_agree(len(rows), spans)}; {len(firsts)} first tokens; the five "
        f"longest device_s (ordinal, ms) "
        f"{[(int(rows['ordinal'][i]), round(float(rows['device_s'][i]) * 1e3, 3)) for i in longest]}")


def _agree(rows, spans):
    if not isinstance(spans, int):
        return ""
    return " (agree)" if abs(rows - spans) <= 1 else " (DISAGREE)"


def _steps(ctx, mixed=None):
    """The window's step rows: all, or those at the prefill width
    (``mixed``) or at width 1; None where there is none."""
    selected = _select(ctx)
    if selected is None:
        return None
    rows = selected[0]
    if mixed is not None:
        rows = rows[(rows["width"] > 1) == mixed]
    return rows if len(rows) else None


@span_readers._nothing_on_a_fault
def emitted_ms(ctx, mixed):
    """Median ``device_s`` of the window's steps at the prefill width
    (``mixed``) or at width 1, ms: launch, or the step before it done, to
    fetched."""
    rows = _steps(ctx, mixed)
    if rows is None:
        return None
    ms = rows["device_s"] * 1e3
    log(f"step log: device_s at width {sorted(set(rows['width'].tolist()))}: "
        f"{len(ms)} steps, median {np.median(ms):.3f} ms, longest "
        f"{ms.max():.3f} ms")
    return float(np.median(ms))


@span_readers._nothing_on_a_fault
def mixed_fill_pct(ctx):
    """Tokens the window's mixed steps carried over the tokens their
    program was compiled for."""
    rows = _steps(ctx, mixed=True)
    if rows is None:
        return None
    carried, capacity = int(rows["carried"].sum()), int(rows["capacity"].sum())
    log(f"step log: {len(rows)} mixed steps carried {carried} of "
        f"{capacity} tokens")
    return 100.0 * carried / capacity


@span_readers._nothing_on_a_fault
def run_ahead_pct(ctx):
    """Share of the window's steps launched with a step in flight."""
    rows = _steps(ctx)
    if rows is None:
        return None
    return 100.0 * float(rows["ran_ahead"].mean())


@span_readers._nothing_on_a_fault
def decode_rows_per_step(ctx):
    """Mean decode rows a step of the window handed out."""
    rows = _steps(ctx)
    if rows is None:
        return None
    return float(rows["decode_rows"].mean())


@span_readers._nothing_on_a_fault
def cpu_ms_per_step(ctx, clock):
    """Mean advance of ``clock`` (``thread_cpu_s``: all the serve loop's
    thread did for one step, the harness's submit and collect included;
    ``process_cpu_s``: every thread's) a step of the window, ms.  The
    window's FIRST row is left out: its advance reaches back to the row
    before the window, over whatever the harness did between its warm-up
    and its window (a traced run starts the profiler there)."""
    rows = _steps(ctx)
    if rows is None or len(rows) < 2:
        return None
    first, rows = rows[0], rows[1:]
    thread = float(rows["thread_cpu_s"].sum())
    process = float(rows["process_cpu_s"].sum())
    log(f"step log: CPU over {len(rows)} steps: the loop's thread "
        f"{thread:.3f} s, the process {process:.3f} s, so other threads "
        f"{process - thread:.3f} s; left out, the window's first row, "
        f"which reaches back before it: thread "
        f"{float(first['thread_cpu_s']) * 1e3:.3f} ms, process "
        f"{float(first['process_cpu_s']) * 1e3:.3f} ms")
    return float(rows[clock].mean()) * 1e3


def _first_tokens(ctx):
    selected = _select(ctx)
    if selected is None or not len(selected[1]):
        return None
    return selected[1]


@span_readers._nothing_on_a_fault
def prefill_ms(ctx):
    """Median admission-to-first-token of the requests whose first token
    a step of the window emitted, ms."""
    firsts = _first_tokens(ctx)
    if firsts is None:
        return None
    ms = (firsts["first_token_at"] - firsts["admitted_at"]) * 1e3
    return float(np.median(ms))
