"""The engine's own log: one row a step it EMITS, one row a request at
its first token, in two fixed rings it owns.

The ``serve/*`` spans (``engine.py``) say where the host stood on the
profiler's clock; a row says WHAT a step was and how long the device had
it.  Since the step in flight a step is launched in one ``serve_step``
call and handed out in the next, so nothing that times a CALL times a
step any more: the row is written where the step's tokens are handed
out, from what the launch recorded.

Both rings are preallocated numpy structured arrays written by index: a
row costs three clock reads and a store a field, allocates no array and
grows nothing.  The log is always on, so a row holds only what something
reads: a per-layer metric of the benchmark (``benchmarks/lib/
step_log_readers.py``), :func:`summary`, ``load_snapshot()`` or
``bench.py``.  A reader takes a
COPY (:meth:`StepLog.rows`, :meth:`StepLog.between`), oldest row first;
a ring keeps the last :data:`RING_ROWS` rows.

:func:`step_logs` hands out the two logs of the engine built LAST in
this process, for a reader that no longer holds the engine (the
benchmark releases its engine before its readers run); whoever holds an
engine reads ``engine.step_log`` / ``engine.first_token_log``.
"""

import time

import numpy as np

RING_ROWS = 4096

STEP_ROW = np.dtype([
    # which step: 1 for the first one the engine handed out
    ("ordinal", np.int64),
    # the compiled width (1, or the prefill chunk), the tokens the list
    # carried and the tokens its program was compiled for
    ("width", np.int32), ("carried", np.int32), ("capacity", np.int32),
    # decode rows handed out (a step with none is no decode step)
    ("decode_rows", np.int32),
    # launched with a step in flight
    ("ran_ahead", np.bool_),
    # what the pool's pages in use held as the row was written, and what
    # ONE table for all layers would have held for the same sequences
    # (``ServeEngine.kv_residency``; equal for a model with one kind of
    # page): a window model's saving, step by step
    ("resident_kv_bytes", np.int64), ("resident_kv_bytes_one_table", np.int64),
    # ``time.perf_counter`` as the row was written: what ``between``
    # selects by, on the clock of whoever timed the calls
    ("emitted_at", np.float64),
    # how long the device had the step: from its launch, or from the
    # fetch of the step before it where it was launched ahead, to its
    # own fetch
    ("device_s", np.float64),
    # ``time.thread_time`` / ``time.process_time``: each clock's advance
    # since the row before (the first row: since the log was made, so a
    # mean a step leaves the first row of its selection out); where the
    # kernel accounts CPU time in ticks one row reads 0 or a tick, and
    # only a sum over many rows means something
    ("thread_cpu_s", np.float64), ("process_cpu_s", np.float64),
])

FIRST_TOKEN_ROW = np.dtype([
    # the engine's clock: admission to first token is the prefill
    ("admitted_at", np.float64), ("first_token_at", np.float64),
    # ``ordinal`` of the step row that emitted the token
    ("first_step", np.int64),
])


class _Ring:
    """Rows written by index into one preallocated structured array."""

    def __init__(self, dtype, stamp, size=RING_ROWS):
        self.size = int(size)
        self.written = 0           # rows ever written
        self._rows = np.zeros(self.size, dtype)
        self._stamp = stamp        # the field ``between`` selects by
        # one view a field, made once: a store allocates nothing
        self._cols = tuple(self._rows[name] for name in dtype.names)

    def __len__(self):
        return min(self.written, self.size)

    def _store(self, values):
        """One row, a value a field in the dtype's order."""
        i = self.written % self.size
        for col, value in zip(self._cols, values):
            col[i] = value
        self.written += 1

    def _oldest_first(self, held):
        """A copy of ``held`` (the ring's array, or one field of it) from
        the oldest row written to the newest."""
        at = self.written % self.size
        if self.written <= self.size:
            return held[:self.written].copy()
        return np.concatenate([held[at:], held[:at]])

    def rows(self):
        """The rows the ring holds, oldest first, as one array (a copy)."""
        return self._oldest_first(self._rows)

    def column(self, name):
        """One field of those rows (a copy): for a reader that is called
        often and wants one or two fields."""
        return self._oldest_first(self._rows[name])

    def between(self, t0, t1):
        """Rows stamped in ``[t0, t1]``, oldest first, as one array."""
        rows = self.rows()
        at = rows[self._stamp]
        return rows[(at >= t0) & (at <= t1)]


class StepLog(_Ring):
    """One row a step the engine emitted (:data:`STEP_ROW`)."""

    def __init__(self, size=RING_ROWS):
        super().__init__(STEP_ROW, "emitted_at", size)
        self._thread_cpu = time.thread_time()
        self._process_cpu = time.process_time()

    def write(self, ordinal, width, carried, capacity, decode_rows,
              ran_ahead, device_s, resident_kv_bytes=0,
              resident_kv_bytes_one_table=0):
        """The row of one emitted step; reads the three clocks itself."""
        thread_cpu, process_cpu = time.thread_time(), time.process_time()
        self._store((
            ordinal, width, carried, capacity, decode_rows, ran_ahead,
            resident_kv_bytes, resident_kv_bytes_one_table,
            time.perf_counter(), device_s,
            thread_cpu - self._thread_cpu, process_cpu - self._process_cpu))
        self._thread_cpu, self._process_cpu = thread_cpu, process_cpu


class FirstTokenLog(_Ring):
    """One row a request at its first token (:data:`FIRST_TOKEN_ROW`)."""

    def __init__(self, size=RING_ROWS):
        super().__init__(FIRST_TOKEN_ROW, "first_token_at", size)

    def write(self, admitted_at, first_token_at, first_step):
        self._store((admitted_at, first_token_at, first_step))


def summary(rows):
    """What an operator reads of the step rows of a run (``unicore-serve``'s
    JSON report): by width class the count and the median and 95th
    percentile of ``device_s``, the fill of the mixed program, the share
    of steps launched ahead, the two CPU means a step (over every row
    but the first, whose advance reaches back before the rows)."""
    out = {"rows": int(len(rows))}
    if not len(rows):
        return out
    mixed = rows[rows["width"] > 1]
    for name, cls in (("decode", rows[rows["width"] == 1]),
                      ("mixed", mixed)):
        out[name] = {"rows": int(len(cls))}
        if len(cls):
            ms = cls["device_s"] * 1e3
            out[name]["device_ms_median"] = round(float(np.median(ms)), 4)
            out[name]["device_ms_p95"] = round(
                float(np.percentile(ms, 95)), 4)
    if len(mixed):
        out["mixed_fill_pct"] = round(
            100.0 * float(mixed["carried"].sum())
            / float(mixed["capacity"].sum()), 4)
    out["run_ahead_pct"] = round(100.0 * float(rows["ran_ahead"].mean()), 4)
    if len(rows) > 1:
        out["thread_cpu_ms_per_step"] = round(
            float(rows["thread_cpu_s"][1:].mean()) * 1e3, 4)
        out["process_cpu_ms_per_step"] = round(
            float(rows["process_cpu_s"][1:].mean()) * 1e3, 4)
    return out


_latest = None


def publish(step_log, first_token_log):
    """Called by an engine at construction: its logs replace the last
    engine's behind :func:`step_logs`."""
    global _latest
    _latest = (step_log, first_token_log)


def step_logs():
    """``(StepLog, FirstTokenLog)`` of the engine built last in this
    process, or None before the first."""
    return _latest
