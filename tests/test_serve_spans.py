"""The span tree of ``ServeEngine.serve_step`` (engine.py's docstring),
read back from a real profiler trace of a tiny engine on the CPU: every
span is there, children lie inside their parents, a step that had
nothing to do records nothing, and a fault inside a step leaves no span
open.  With a full batch (ISSUE 37) a call launches the next step before
it fetches the one in flight: the names stay, ``serve/launch`` comes
before ``serve/fetch`` inside one ``serve/dispatch-w<n>``, and n is the
width LAUNCHED in that call."""

import re

import jax
import jax.numpy as jnp
import pytest

from benchmarks.lib import trace as trace_lib
from benchmarks.lib import tracing
from examples.lm.model import TransformerLMModel
from unicore_tpu.serve import Request
from unicore_tpu.serve import engine as engine_mod
from unicore_tpu.serve.engine import ServeEngine

V = 29
CHUNK = 4
DISPATCH = re.compile(r"^serve/dispatch-w(\d+)$")
# child -> parents it may lie in; the per-width dispatch spans are matched
# by DISPATCH.  A call that only settles a step in flight fetches with no
# dispatch span around it: nothing was launched
PARENT = {
    "serve/schedule": ("serve/step",), "serve/admit": ("serve/schedule",),
    "serve/plan": ("serve/step",), "serve/assemble": ("serve/step",),
    "serve/transfer": ("serve/step",), "serve/dispatch": ("serve/step",),
    "serve/launch": ("serve/dispatch",),
    "serve/fetch": ("serve/dispatch", "serve/step"),
    "serve/emit": ("serve/step",),
}
EVERY_STEP = ("serve/schedule", "serve/plan", "serve/assemble",
              "serve/transfer", "serve/launch", "serve/fetch", "serve/emit")


@pytest.fixture(scope="module")
def lm():
    model = TransformerLMModel(
        vocab_size=V, padding_idx=0, decoder_layers=2,
        decoder_embed_dim=32, decoder_ffn_embed_dim=64,
        decoder_attention_heads=4, max_seq_len=64,
        emb_dropout=0.0, dropout=0.0, attention_dropout=0.0,
        activation_dropout=0.0, rel_pos=False, abs_pos=False, rotary=True,
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def make_engine(lm, **kw):
    model, params = lm
    return ServeEngine(model, params, num_pages=16, page_size=4,
                       max_batch=3, prefill_chunk=CHUNK, **kw)


def requests():
    return [Request(prompt=[3, 7, 2, 9, 4, 6], max_new_tokens=4,
                    request_id="long"),
            Request(prompt=[11, 5], max_new_tokens=3, request_id="short")]


def count_dispatches(engine):
    """The engine's own per-dispatch hook (``_input_capture`` fires once
    before every compiled call) as the count the spans must match."""
    seen = []
    engine._input_capture = lambda key, args: seen.append(key[0])
    return seen


def traced(tmp_path, drive):
    """The ``serve/*`` spans recorded while ``drive()`` ran, as
    ``(name, start, end)`` in start order."""
    tracing.start(str(tmp_path))
    try:
        drive()
    finally:
        tracing.stop()
    tr = trace_lib.read_xplane(trace_lib.find_xplane(str(tmp_path)))
    return sorted(((n, s, s + d) for n, s, d in tr.host
                   if n.startswith("serve/")), key=lambda x: (x[1], -x[2]))


def family(name):
    return "serve/dispatch" if DISPATCH.match(name) else name


def assert_nested(spans):
    """Every span but ``serve/step`` lies inside one span of its parent's
    name, and no two ``serve/step`` spans overlap (none was left open)."""
    by_family = {}
    for name, a, b in spans:
        by_family.setdefault(family(name), []).append((a, b))
    steps = by_family["serve/step"]
    assert all(e1 <= s2 for (_, e1), (s2, _) in zip(steps, steps[1:]))
    for name, a, b in spans:
        parents = PARENT.get(family(name))
        if parents is None:
            assert name == "serve/step", name
            continue
        assert any(pa <= a and b <= pb for parent in parents
                   for pa, pb in by_family.get(parent, ())), \
            f"{name} [{a}, {b}] lies in no {parents}"


def steps_holding(spans, pattern):
    """How many of ``pattern``'s spans each ``serve/step`` contains."""
    steps = [(a, b) for n, a, b in spans if n == "serve/step"]
    inner = [(a, b) for n, a, b in spans if re.match(pattern, n)]
    return [sum(1 for a, b in inner if sa <= a and b <= sb)
            for sa, sb in steps]


def test_every_span_of_the_tree_is_recorded_and_nested(lm, tmp_path):
    engine = make_engine(lm)
    dispatched = count_dispatches(engine)
    engine.submit(requests())

    def drive():
        while engine.serve_step():
            pass

    spans = traced(tmp_path, drive)
    names = [n for n, _, _ in spans]
    assert_nested(spans)
    for name in ("serve/step", "serve/admit") + EVERY_STEP:
        assert name in names, name
    per_step = steps_holding(spans, DISPATCH.pattern)
    assert sum(per_step) == len(dispatched) > 0
    assert set(per_step) == {1}       # unified: one dispatch a step
    for phase in EVERY_STEP:
        assert names.count(phase) == len(dispatched), phase
    widths = [int(DISPATCH.match(n).group(1)) for n in names
              if DISPATCH.match(n)]
    assert widths == dispatched and set(widths) == {1, CHUNK}
    assert engine.stats["decode_steps"] == int(
        (engine.step_log.rows()["decode_rows"] > 0).sum()) > 0


def test_a_call_that_runs_ahead_launches_before_it_fetches(lm, tmp_path):
    """Two rows, two requests: the batch is full, so every call but the
    first launches behind a step in flight."""
    model, params = lm
    engine = ServeEngine(model, params, num_pages=16, page_size=4,
                         max_batch=2, prefill_chunk=CHUNK)
    dispatched = count_dispatches(engine)
    # "long" samples its first token a step after "short": both end with
    # the sixth step
    engine.submit([Request(prompt=[3, 7, 2, 9, 4, 6], max_new_tokens=5,
                           request_id="long"),
                   Request(prompt=[11, 5], max_new_tokens=6,
                           request_id="short")])

    def drive():
        while engine.serve_step():
            pass

    spans = traced(tmp_path, drive)
    names = [n for n, _, _ in spans]
    assert_nested(spans)
    assert engine.stats["steps_run_ahead"] == len(dispatched) - 1 == 5
    for name in ("serve/step", "serve/admit") + EVERY_STEP:
        assert name in names, name
    # a dispatch span a launch, named by the width LAUNCHED in that call
    widths = [int(DISPATCH.match(n).group(1)) for n in names
              if DISPATCH.match(n)]
    assert widths == dispatched and set(widths) == {1, CHUNK}
    assert names.count("serve/launch") == len(dispatched)
    # every launched step is fetched and emitted once, one a call at most
    assert names.count("serve/fetch") == names.count("serve/emit") \
        == len(dispatched)
    assert max(steps_holding(spans, "serve/emit")) == 1
    by = {k: [(a, b) for n, a, b in spans if n == k]
          for k in ("serve/launch", "serve/fetch", "serve/emit")}
    holds = lambda outer, inner: [
        (a, b) for a, b in inner if outer[0] <= a and b <= outer[1]]
    dispatches = [(a, b) for n, a, b in spans if DISPATCH.match(n)]
    # the first call launches and leaves its step in flight
    assert holds(dispatches[0], by["serve/fetch"]) == []
    for d in dispatches[1:]:
        # launch of N+1, THEN the fetch of N, inside the one span
        [launch], [fetch] = (holds(d, by["serve/launch"]),
                             holds(d, by["serve/fetch"]))
        assert launch[1] <= fetch[0]
    # and the last step's tokens come from a call that launches nothing:
    # schedule, fetch, emit
    steps = [(a, b) for n, a, b in spans if n == "serve/step"]
    assert len(steps) == len(dispatched) + 1
    last = [family(n) for n, a, b in spans
            if steps[-1][0] <= a and b <= steps[-1][1]]
    assert last == ["serve/step", "serve/schedule", "serve/fetch",
                    "serve/emit"]
    # the first decode launch waited for the MIXED step's tokens: the
    # span's name is the width launched, not the width fetched
    assert widths[:3] == [CHUNK, CHUNK, 1]
    assert engine.stats["decode_steps"] == int(
        (engine.step_log.rows()["decode_rows"] > 0).sum()) > 0


def test_an_idle_serve_step_records_no_span(lm, tmp_path):
    engine = make_engine(lm)
    engine.generate(requests())   # compiled and drained: nothing queued

    def drive():
        for _ in range(3):
            assert engine.serve_step() is False

    assert traced(tmp_path, drive) == []


def test_split_mode_holds_two_dispatch_runs_in_one_step(lm, tmp_path):
    engine = make_engine(lm, unified=False)
    seqs = engine.submit(requests()[1:])     # "short" decodes first ...

    def drive():
        while not seqs[0].generated:
            engine.serve_step()
        engine.submit(requests()[:1])        # ... then "long" prefills
        engine.serve_step()                  # beside it: two programs

    spans = traced(tmp_path, drive)
    assert_nested(spans)
    per_step = steps_holding(spans, DISPATCH.pattern)
    assert per_step[-1] == 2 and set(per_step[:-1]) == {1}
    for phase in ("serve/assemble", "serve/transfer", "serve/emit"):
        assert steps_holding(spans, re.escape(phase))[-1] == 2, phase
    assert steps_holding(spans, "serve/plan")[-1] == 1


@pytest.mark.parametrize("fault", ["row-assembly", "quarantine",
                                   "compiled-call"])
def test_a_fault_inside_a_step_closes_every_span(lm, tmp_path, fault):
    """After the fault the next step's spans nest under their own
    ``serve/step``: an annotation left open would swallow them."""
    kw = {"poison_requests": ["short"]} if fault == "quarantine" else {}
    engine = make_engine(lm, **kw)
    if fault == "compiled-call":
        # both widths have run once: a later fault is a host fault,
        # not a StepCompileError
        engine.generate([Request(prompt=[5, 8], max_new_tokens=2)])
        engine.collect_finished()
    seqs = engine.submit(requests())
    if fault == "row-assembly":
        real_table = engine.pool.page_table

        def bad_table(sid):
            if sid == seqs[1].sid:
                raise RuntimeError("corrupted per-sequence state")
            return real_table(sid)

        engine.pool.page_table = bad_table
    elif fault == "compiled-call":
        real_step_fn, calls = engine._ragged_step_fn, []

        def failing_once(width, sampling):
            calls.append(width)
            if len(calls) == 2:
                def boom(*args):
                    raise RuntimeError("step fault")
                return boom
            return real_step_fn(width, sampling)

        engine._ragged_step_fn = failing_once

    def drive():
        while engine.serve_step():
            pass

    spans = traced(tmp_path, drive)
    assert_nested(spans)
    by = {r.request_id: r for r in engine.collect_finished()}
    if fault == "compiled-call":
        assert engine.stats["host_faults"] == 1
        assert {r.finish_reason for r in by.values()} == {"failed"}
        # the failed launch's spans closed without a fetch or an emit
        assert [n for n, _, _ in spans].count("serve/launch") == 2
        assert [n for n, _, _ in spans].count("serve/fetch") == 1
    else:
        assert by["short"].finish_reason == "failed"
        assert by["long"].finish_reason == "length"
        assert len(by["long"].tokens) == 4
    steps = [n for n, _, _ in spans].count("serve/step")
    assert steps >= 2 and engine.pool.is_idle()


def test_span_names_are_the_module_constants():
    """The benchmark's readers know the spans by name
    (``benchmarks/lib/span_readers.py``): a rename here has to be one
    there."""
    from benchmarks.lib import span_readers

    assert span_readers.STEP == (engine_mod.SPAN_STEP,)
    assert span_readers.HOST == (
        engine_mod.SPAN_SCHEDULE, engine_mod.SPAN_PLAN,
        engine_mod.SPAN_ASSEMBLE, engine_mod.SPAN_EMIT)
    assert span_readers.TRANSFER == (engine_mod.SPAN_TRANSFER,)
    assert span_readers.DISPATCH == (engine_mod.SPAN_LAUNCH,
                                     engine_mod.SPAN_FETCH)
    assert span_readers.ADMIT == (engine_mod.SPAN_ADMIT,)
    assert span_readers._DISPATCH_WIDTH.match(
        engine_mod.SPAN_DISPATCH.format(width=128)).group(1) == "128"


def test_queue_ms_is_the_wait_for_the_first_admission(lm):
    """``queue_ms`` runs from enqueue to the first admission on the
    engine's clock; a preempted and resumed sequence keeps its stamp, a
    request that never ran has none."""
    ticks = iter(range(10_000))
    engine = make_engine(lm, clock=lambda: float(next(ticks)),
                         max_waiting=0)
    # max_batch 3 + max_waiting 0: the fourth is shed at the door
    reqs = [Request(prompt=[3 + i, 7], max_new_tokens=3,
                    request_id=f"r{i}") for i in range(4)]
    seqs = engine.submit(reqs)
    assert seqs[3].finish_reason == "shed"
    engine.serve_step()
    first = seqs[0].admitted_at
    assert first is not None and first > seqs[0].enqueued_at
    engine.scheduler.preempt(seqs[0])
    while engine.serve_step():
        pass
    assert seqs[0].evictions == 1 and seqs[0].admitted_at == first
    by = {r.request_id: r for r in engine.collect_finished()}
    assert by["r0"].queue_ms == (first - seqs[0].enqueued_at) * 1e3
    assert 0 < by["r0"].queue_ms < by["r0"].ttft_ms
    assert by["r3"].queue_ms is None and by["r3"].ttft_ms is None
