"""A serve cell: one ``ServeEngine`` under generated traffic.

The harness owns the clock and the load; the engine is driven through its
public stepping surface (``submit`` / ``serve_step`` / the ``Sequence``
handles ``submit`` returns).  Requests are timed from when they were DUE,
not from when the loop got round to sending them, and how late the
generator ran is reported beside the numbers.
"""

import gc
import time

import numpy as np

from . import device, hostwatch, tracing, traffic, weights
from .device import log
from .tracing import span

OK_REASONS = ("eos", "length")


def abstract_params(model):
    import jax
    import jax.numpy as jnp

    return jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32)
    )["params"]


class Tracked:
    """One request as the harness sees it."""

    __slots__ = ("spec", "seq", "due", "sent", "token_times", "finished_at",
                 "in_window")

    def __init__(self, spec, due):
        self.spec, self.due = spec, due
        self.seq = None
        self.sent = None
        self.token_times = []
        self.finished_at = None
        self.in_window = False


class Driver:
    """Steps one engine, stamps tokens, counts step widths."""

    def __init__(self, engine, record_rows=False):
        from unicore_tpu.serve.scheduler import Request

        self.engine, self.Request = engine, Request
        self.record_rows = record_rows
        self.rows = []             # per step: [(query tokens, context), ...]
        self._seen = {}            # sid -> tokens whose KV was written
        self.live = []
        self.steps = []            # (start, seconds, width or None)
        self._width = None
        inner = engine.width_fn

        def counted(chunk):
            self._width = inner(chunk)
            return self._width

        engine.width_fn = counted
        self.prompt_tokens_sent = 0

    def send(self, tracked, now):
        spec = tracked.spec
        with span("bench/submit"):
            req = self.Request(
                prompt=spec["prompt"], max_new_tokens=spec["max_new_tokens"],
                eos_id=None, request_id=spec["id"])
            tracked.seq = self.engine.submit([req])[0]
        tracked.sent = now
        self.prompt_tokens_sent += len(spec["prompt"])
        self.live.append(tracked)

    def _rows_of_step(self):
        """(query tokens, context length) of every row the last step
        ran, from each running sequence's prefill watermark before and
        after: a decode row is (1, context); a prompt advances in chunks,
        each row seeing the context up to its own end."""
        eng = self.engine
        chunk = eng.prefill_chunk
        rows = []
        for tr in self.live:
            seq = tr.seq
            after = seq.prefilled
            before = self._seen.get(seq.sid)
            if before is None:
                if seq not in eng.scheduler.running:
                    if not seq.done:
                        continue  # still waiting: no row yet
                    before = 0
                else:
                    # a prefix hit starts a prompt past its shared pages
                    before = min(eng.pool.cached_tokens(seq.sid), after)
            at = before
            while at < after:
                m = min(chunk, after - at)
                rows.append((m, at + m))
                at += m
            self._seen[seq.sid] = after
        return rows

    def step(self):
        """One ``serve_step`` and the stamping of what it produced."""
        self._width = None
        t0 = time.perf_counter()
        with span("bench/serve_step"):
            self.engine.serve_step()
        t1 = time.perf_counter()
        self.steps.append((t0, t1 - t0, self._width))
        if self.record_rows:
            self.rows.append(self._rows_of_step())
        with span("bench/collect"):
            still = []
            for tr in self.live:
                n = len(tr.seq.generated)
                while len(tr.token_times) < n:
                    tr.token_times.append(t1)
                if tr.seq.done:
                    tr.finished_at = t1
                else:
                    still.append(tr)
            self.live = still
            self.engine.collect_finished()  # keep its list from growing
        return t1


def warm(engine, vocab, seed):
    """Both compiled widths through the calls the window makes: a prompt
    longer than one chunk (the prefill width), then its decode steps (the
    decode width)."""
    rng = np.random.default_rng(seed)
    d = Driver(engine)
    n = engine.prefill_chunk + 7
    for i in range(2):
        d.send(Tracked({"id": f"warm{i}", "max_new_tokens": 3,
                        "prompt": rng.integers(4, vocab, n).tolist()}, 0.0),
               time.perf_counter())
    while engine.has_work():
        d.step()
    widths = sorted({w for _, _, w in d.steps if w})
    engine.width_fn = engine._width_for
    log("warm-up steps (width, seconds):",
        [(w, round(dt, 3)) for _, dt, w in d.steps])
    return widths


def run_open_loop(engine, schedule, ramp_s, window_s, drain_s,
                  trace_dir=None, on_open=None, on_close=None):
    """Requests at their due times; the window is ``[ramp_s, ramp_s +
    window_s)`` of the schedule's clock.  Returns the tracked requests,
    the driver and the window's bounds on the host clock."""
    d = Driver(engine, record_rows=bool(trace_dir))
    pending = [Tracked(s, s["due_s"]) for s in schedule]
    for tr in pending:
        tr.in_window = ramp_s <= tr.due < ramp_s + window_s
    nxt = 0
    start = time.perf_counter()
    t_open = start + ramp_s
    t_close = t_open + window_s
    opened = closed = False
    while True:
        now = time.perf_counter()
        if not opened and now >= t_open:
            opened = True
            if on_open:
                on_open()
            if trace_dir:
                tracing.start(trace_dir)
        if opened and not closed and now >= t_close:
            closed = True
            if trace_dir:
                tracing.stop()
            if on_close:
                on_close()
        while nxt < len(pending) and start + pending[nxt].due <= now \
                and pending[nxt].due < ramp_s + window_s:
            d.send(pending[nxt], now)
            nxt += 1
        if closed:
            waiting = [tr for tr in pending[:nxt]
                       if tr.in_window and tr.finished_at is None]
            if not waiting or now > t_close + drain_s:
                break
        if engine.has_work():
            d.step()
        else:
            due_next = (start + pending[nxt].due if nxt < len(pending)
                        else t_close)
            time.sleep(max(0.0, min(0.0005, due_next - now)))
    sent = pending[:nxt]
    for tr in sent:
        tr.due = start + tr.due  # onto the host clock, like the stamps
    return sent, d, (t_open, t_close)


def run_closed_loop(engine, sessions, ramp_s, window_s,
                    trace_dir=None, on_open=None, on_close=None):
    """Every client sends its next ask the moment the last came back."""
    d = Driver(engine, record_rows=bool(trace_dir))
    clients = len(sessions.clients)
    outstanding = [None] * clients
    sent = []
    start = time.perf_counter()
    t_open, t_close = start + ramp_s, start + ramp_s + window_s
    opened = closed = False
    while True:
        now = time.perf_counter()
        if not opened and now >= t_open:
            opened = True
            if on_open:
                on_open()
            if trace_dir:
                tracing.start(trace_dir)
        if now >= t_close:
            if trace_dir:
                tracing.stop()
            if on_close:
                on_close()
            break
        for c in range(clients):
            tr = outstanding[c]
            if tr is None or tr.finished_at is not None:
                nxt = Tracked(sessions.next_request(c), now)
                d.send(nxt, now)
                outstanding[c] = nxt
                sent.append(nxt)
        d.step()
    for tr in sent:
        tr.in_window = (tr.finished_at is not None
                        and t_open <= tr.finished_at < t_close)
    return sent, d, (t_open, t_close)


def release(engine):
    """Free the pool and the compiled steps: the reference runs next and
    ``memory_peak_bytes`` has to stay the program's."""
    engine.pages = None
    engine._step_fns.clear()
    gc.collect()


def build(cell, seed, weights_dtype=None):
    """The engine with seeded weights, both step widths warmed.
    ``weights_dtype``: for ``control.py``, the same weights rounded to a
    lower precision before the engine gets them."""
    import jax

    from unicore_tpu.serve import ServeEngine

    cfg = cell["config"]
    model = cell["family"].build_model(cfg)
    abstract = abstract_params(model)
    if weights_dtype is not None:
        abstract = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, weights_dtype), abstract)
    t = time.perf_counter()
    params = weights.make(abstract, seed, scales=cfg.get("weight_scales"))
    jax.block_until_ready(params)
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(abstract))
    log(f"weights: {n:,} parameters made in {time.perf_counter() - t:.2f} s")
    engine = ServeEngine(model, params, **cfg["engine"])
    compiles = device.Compiles.listen()
    t = time.perf_counter()
    widths = warm(engine, cfg["vocab_size"], seed)
    log(f"warm-up: widths {widths} in {time.perf_counter() - t:.2f} s, "
        f"{compiles.count()} compiles so far, {compiles.hits} cache hits; "
        f"seconds by stage {({k: round(v, 2) for k, v in compiles.stages.items()})}")
    return engine, params, widths


def run(cell, seed, seconds, trace, devices, workdir, process_t0):
    """Run the cell once; returns the facts of the run."""
    engine, params, widths = build(cell, seed)
    facts = drive(cell, engine, params, widths, seed, seconds, trace,
                  devices, workdir, process_t0)
    release(engine)
    del engine
    gc.collect()
    return facts


def drive(cell, engine, params, widths, seed, seconds, trace, devices,
          workdir, process_t0):
    """The cell's traffic against a built engine: ramp, window, drain."""
    import os

    import jax

    from unicore_tpu.ops import backend

    cfg, tr = cell["config"], cell["traffic"]
    vocab = cfg["vocab_size"]
    if seconds <= 0:
        raise ValueError("--seconds must be positive")
    window = min(seconds, tracing.TRACE_SECONDS) if trace else seconds
    trace_dir = os.path.join(workdir, "trace") if trace else None
    compiles = device.Compiles.listen()

    marks = {}
    watch = hostwatch.Watch()

    def on_open():
        watch.start()
        marks["setup_s"] = time.perf_counter() - process_t0
        marks["compiles"] = compiles.count()
        marks["stats"] = dict(engine.stats)
        marks["prefix"] = dict(engine.pool.prefix_stats)

    def on_close():
        watch.stop()
        marks["compiles_in_window"] = compiles.count() - marks["compiles"]
        marks["stats_close"] = dict(engine.stats)
        marks["prefix_close"] = dict(engine.pool.prefix_stats)

    if tr["kind"] == "open_loop":
        schedule = traffic.open_loop_schedule(
            tr, seed, tr["ramp_s"] + window, vocab)
        sent, driver, bounds = run_open_loop(
            engine, schedule, tr["ramp_s"], window, tr["drain_s"],
            trace_dir, on_open, on_close)
    elif tr["kind"] == "closed_loop":
        sessions = traffic.ClosedLoopSessions(tr, seed, vocab)
        sent, driver, bounds = run_closed_loop(
            engine, sessions, tr["ramp_s"], window, trace_dir,
            on_open, on_close)
    else:
        raise ValueError(f"a serve cell cannot run traffic kind {tr['kind']!r}")
    facts = {
        "kind": tr["kind"], "sent": sent, "steps": driver.steps,
        "rows": driver.rows, "t_open": bounds[0], "t_close": bounds[1],
        "window_s": bounds[1] - bounds[0], "setup_s": marks["setup_s"],
        "compiles_in_window": marks["compiles_in_window"],
        "stats_open": marks["stats"], "stats_close": marks["stats_close"],
        "prefix_open": marks["prefix"], "prefix_close": marks["prefix_close"],
        "widths_warmed": widths,
        "dispatch": backend.dispatch_report(),
        "memory_peak": device.memory_peak_bytes(devices),
        "params": params, "trace_dir": trace_dir, "watch": watch,
        "pool_dtype": str(jax.tree_util.tree_leaves(engine.pages)[0].dtype),
    }
    return facts
