"""ServeEngine: batched offline/online generation over the paged pool.

The engine owns the device side of serving, and since the ragged
unification that side is ONE step function: every batch row carries
per-sequence ``(start, len, decode?)`` metadata — a decode row holds a
single token, a prefill row holds a CHUNK of its prompt — and both run
in the same compiled program ("Ragged Paged Attention", arxiv
2604.15464).  The old per-pow2-bucket prefill family plus the separate
decode jit collapse to a constant two lowerings of that one function:
the ``width=1`` pure-decode dispatch (steady-state traffic pays no
chunk padding) and the ``width=prefill_chunk`` mixed dispatch, so a
long prompt is admitted in bounded-TTFT slices WHILE the running batch
keeps decoding in the same dispatch.  UL205 audits that the program
count stays constant over every prompt length.

The step's tokens are a FLAT list of one fixed size per program:
``max_batch`` tokens at width 1 (the ``[max_batch, 1]`` rectangle is the
list) and :attr:`ServeEngine.mixed_tokens` at the prefill width, 512
tokens whatever ``max_batch`` is (a small engine: its rectangle).  Embedding, norms,
projections and FFNs run on the tokens the step carries; only the mixers
that need rows sort them into the ``[max_batch, width]`` rectangle
(attention for the ragged kernel, a recurrent layer for its chain), and
the head runs on each row's last token, ``max_batch`` rows.
Pool buffers are DONATED through every step — after warmup nothing
reallocates — and sampling (greedy/temperature/top-k, seeded per
request) runs inside the step, so only the [B] sampled token ids cross
the host boundary.

The pool itself is MULTI-TENANT: ``kv_pool.py`` dedups shared prefixes
by chain-hash — a repeat of a warm system prompt becomes a page-table
lookup instead of a prefill (``prefix_cache=True``), with the partial
tail page always privately owned (copy-on-write by recompute), so one
session's decode never mutates another's shared page.

A model with RECURRENT layers (``model.has_recurrent_state``: linear
attention beside full attention) holds a second kind of cache: one
fixed-size state per sequence, in the same donated ``pagedkv`` tree as
the pages.  The pool hands each resident sequence a state slot with its
pages and takes it back with them; a step gathers the slots of its rows,
runs, and scatters them back inside the one jitted program, zeroing a
state whose row starts at position 0.  The step is the one every model
gets: the slots are one more entry of its packed operands, and the
recurrence gathers its rows out of the flat list inside the layer.  Two
rules follow from the state being a chain: such a model gets ONE row per
sequence per dispatch (chunk k needs the state chunk k-1 leaves, so
consecutive chunks of one prompt cannot share a program the way K/V
scatters do), and prefix hits are refused (a hit starts a prompt past
its shared pages, where no state exists).

A model with SPARSE EXPERTS (``modules/pattern_decoder.py``
``ExpertFFN``) routes the tokens the step carries and no others: a cell
of the list nobody carries sits at position -1 and reaches no expert.
Each expert layer counts its routing ON THE DEVICE, in two variables of
the same donated tree (``moe_load``: tokens each expert got,
``moe_touched``: experts that got a token, both summed over steps);
:meth:`ServeEngine.moe_stats` reads them on demand.  What a step added to
their sums comes back with its tokens, two numbers behind them in the one
array the step fetches anyway, and feeds ``stats["moe_assignments"]`` /
``["moe_experts_touched"]`` with no transfer of its own.  A layer that
holds a SHARE of its experts keeps a third counter, ``moe_held`` (the
choices that landed on its own experts): ``stats["moe_assignments"]`` is
then what the router chose over all experts, ``["moe_assignments_held"]``
what this engine's experts got, and ``moe_touched`` counts held experts.

A model with LATENT attention (``modules/pattern_decoder.py``
``LatentAttentionMixer``) keeps ``latent_pages`` where another keeps K/V
pages: one narrow vector a token a layer for all heads.  Pages are
counted, not measured, so pool, page table, admission, prefix cache and
its hashes are the same; ``stats["cache_bytes_per_token"]`` says what one
token holds over all layers.  Both step widths attend in the absorbed
form (``serve/attention.py``): ``stats["latent_decode_tokens"]`` counts
the tokens the decode width served (each is one query cell a head),
``["latent_prefill_tokens"]`` the tokens the prefill width served, a
one-token row riding a mixed step among them.  Both are the step log's
``carried`` summed by width (below), for every model: the counters stay
only because ``tests/bench/test_bench_run_pangu.py`` reads them.

A model with SLIDING-WINDOW layers (``model.attention_window`` > 0:
window and global attention mixed) holds TWO KINDS of page
(``kv_pool.py``): the global layers' keep every token, the sliding
layers' are taken as a sequence moves on and RELEASED, at the step
boundary, once no query still to come can see them
(:meth:`ServeEngine._advance_window`, the ``serve/window-release``
span).  A row's window table is trimmed to what the row sees and comes
with the position of its first slot; table, base and write slots are
three more entries of the step's one packed operand.  The window kind is
sized from shape for a full batch (every row's reserve and one step's
tokens), never configured; prefix hits are refused (a hit would start
behind pages released long ago).  ``stats["cache_bytes_per_token"]`` is
then the global layers', ``["cache_bytes_per_row_window"]`` what a row
holds at most in the sliding layers, and every step's row in the step
log says what the pool held and what ONE table for all layers would have
held for the same sequences.  For a model without a window none of this
exists: one kind of page, the operands it always had.

THE STEP IN FLIGHT.  The device starts a step when the host launches
it, and a host that fetches step N's tokens before it plans, assembles,
transfers and launches step N+1 leaves the device idle for all of that,
every step.  So ``serve_step`` may return with ONE launched step whose
tokens it has not fetched; the next call launches step N+1 first and
fetches and emits step N after, so that emit, the caller's own work
between two calls, schedule, plan, assemble, transfer and the launch's
latency all lie under the device time of a step already queued.  What
makes it possible:

- *The step program takes its decode tokens from the device.*  Its
  fourth input is the output array of the step before (zeros when
  nothing is in flight), and the packed operand ``token_src`` says, for
  each cell of the token list, which row of that step sampled its token
  (-1: the token the host wrote).  A gather of int32 ids: every row's
  arithmetic is the synchronous engine's.
- *A sequence knows what it has in flight* (``Sequence.in_flight``, 0 or
  1 sampled tokens; ``Sequence.launched``, KV positions past
  ``prefilled``).  Whatever PLANS a step reads a sequence through
  ``length()`` / ``written()``: decode readiness, the rows, the page the
  next step writes, the sampling step index.  ``prefilled``,
  ``register_prefix``, ``generated``, ``first_token_at``, the counters
  and the routing record move at EMIT, in step order, as they always did.
- *The rule*, from what the engine observes and nothing else.  Every
  call first does what cannot wait: deadline expiry and a drain's
  sheds.  Then, with a step in flight, it launches N+1 before fetching
  N if and only if (a) the batch is full — every one of ``max_batch``
  rows is held by a running sequence that goes on past what has been
  launched (an end known ahead, ``max_new_tokens`` or ``max_context``,
  frees its row, and so does whoever just expired or was shed) — which
  is ``Scheduler.admit``'s own test, so no request that arrived
  meanwhile could have been admitted into N+1 and nobody's time to first
  token pays; and (b) the plan is plain continuation: no drain under
  way, no waiting head to fail for capacity, no page whose extension
  needs an eviction, no chaos preemption configured, the split
  ``unified=False`` baseline not in use.  Otherwise the call SETTLES: it
  fetches and emits N and returns, and the next call schedules, admits
  and launches exactly as a synchronous engine would.  A synchronous
  step is the same code with nothing in flight: launch, then (the batch
  not full) fetch and emit.  There is no second path and nothing to
  configure; a call emits one step at most.
- *Ends that are known late.*  ``eos_id``, a row of nonfinite logits
  (token -1), a deadline blown and a drain's shed are found after N+1
  was launched with that sequence's row.  The row's token is an OVERRUN: at
  N+1's emit it is dropped and counted (``stats["tokens_overrun"]``), so
  the sequence's result is the synchronous engine's, ``finish_reason``
  included; a sequence preempted by hand between two calls is told by
  its ``evictions`` and treated alike (its token is sampled again after
  the re-prefill).  The overrun WRITE is harmless: it lands in a page,
  and for a recurrent model a state slot, that the sequence still owned
  when N+1 was launched.  Pages and slots freed at N's emit can only be
  handed to rows of N+2, which the device runs after N+1.  A registered
  prefix covers full PROMPT pages only, and an overrun writes position
  ``len(prompt) + len(generated)``, past every one of them.  A
  recurrent state's next owner starts at position 0 and is zeroed in its
  own step.  The expert layers' device counters do count an overrun
  row's routing: it was routed.
- *Whoever reads the engine between two steps settles first*:
  ``generate()`` returns with nothing in flight, ``has_work()`` is true
  while a step is, ``moe_stats()``, ``swap_weights()`` (the step in
  flight ran on the old weights), ``reclaim_waiting(include_running=
  True)``, ``reopen()``, a host fault and the ``PoolExhausted`` recovery
  all hand out its tokens before they act.  ``collect_finished()`` hands
  out what has been emitted.

``stats["steps_run_ahead"]`` counts the launches made with a step in
flight, beside the step counts: over them, how often the rule held.

Metrics: per-request queue wait and TTFT, the counters of
:attr:`ServeEngine.stats` (aggregate decode tokens/sec, peak pool
occupancy, prefix-cache hits), which the JSON report and the fleet
router read, and THE TWO LOGS (``serve/step_log.py``), always on, each a
preallocated ring of 4,096 rows written by index.
:attr:`ServeEngine.step_log` gets one row a step EMITTED, at the end of
``_emit_step``: ``ordinal``, ``width``, ``carried`` / ``capacity`` (the
list's fill), ``decode_rows`` handed out, ``ran_ahead`` (launched with
a step in flight), ``resident_kv_bytes`` / ``resident_kv_bytes_one_table``
(:meth:`ServeEngine.kv_residency` as the row is written),
``emitted_at`` on ``time.perf_counter``,
``device_s`` (how long the device had the step: launch, or the step
before it done, to fetched) and ``thread_cpu_s`` / ``process_cpu_s``,
the advance of ``time.thread_time`` and ``time.process_time`` since the
row before: the serve loop is one thread, so the first is all that
thread did for one step, the caller's submit and collect included, and
the difference of the two is the runtime's and every other thread's.
:attr:`ServeEngine.first_token_log` gets one row a request where its
first token is stamped: ``admitted_at`` / ``first_token_at`` (the
engine's clock) and ``first_step``, the ``ordinal`` of the step row
that emitted the token, which joins a request to its steps.  A row
holds only what something reads (``serve/step_log.py``).
``load_snapshot()``'s ``step_ms`` is the median ``device_s`` of the
last 33 rows that held a decode row.

Tracing: a ``serve_step`` that has work records the span tree below as
``jax.profiler.TraceAnnotation``s, so the spans land in the profiler's
trace on the clock of the device operations (``unicore-serve --profile``,
or the benchmark's traced window) and an idle gap of the device can be
put down to the phase the host was in.  With no trace running an
annotation costs well under a microsecond::

    serve/step                one scheduler iteration that had work
      serve/schedule          expiry, drain, capacity fail-fast, admission,
                              chaos preemption, prepare_decode; behind a
                              step in flight: the rule
        serve/admit           Scheduler.admit: prefix match, can_alloc, alloc
      serve/plan              _plan_rows
        serve/window-release  a model with a window: pages behind every
                              running sequence's window handed back, the
                              planned rows' window pages taken
      serve/assemble          the numpy rows of one dispatch
        serve/state           a recurrent model's rows: each row's state slot
                              looked up, rows starting from zero counted
      serve/transfer          the step's operands onto the device: one
                              packed vector
      serve/dispatch-w<n>     the compiled step at width n LAUNCHED in this
                              call, and the fetch the call waits in
        serve/launch          the compiled call returning
        serve/fetch           the sampled tokens to the host (-1: a row
                              of nonfinite logits): this step's or, in a
                              call that ran ahead, the step's before
      serve/emit              counters, quarantine, prefill watermark,
                              register_prefix, _emit, of the fetched step

A synchronous call's ``serve/dispatch-w<n>`` is one step's launch until
its tokens are on the host: about its device time.  In a call that ran
ahead it is how long the host STOOD in the launch of N+1 and the fetch
of N, which is the rest of N's device time and no step's whole; n is
the width launched (what ``width_fn`` returned in that call).  A call
that launches and leaves its step in flight records launch and no fetch
or emit; a call that only settles records ``serve/step`` { ``serve/
schedule``, ``serve/fetch``, ``serve/emit`` } and no dispatch span.

Robustness (ISSUE 7), layered on the ``resilience/`` machinery:

- **Per-request fault isolation.**  Every ragged step also returns a
  per-row finite-logits flag (:func:`~unicore_tpu.serve.sampling.
  finite_rows` — the anomaly-guard pattern applied per request); a
  poisoned row is QUARANTINED: it finishes ``"failed"``, its pages are
  freed (shared prefix pages just drop one reference — survivors
  sharing them are untouched), and the rest of the batch continues
  token-identically.  A host-side step exception (sampler fault, bad
  assembly) likewise fails only the in-flight sequences — the engine
  survives unless the fault consumed the donated pool buffers.
- **Graceful drain.**  Wire a :class:`~unicore_tpu.resilience.
  preemption.GracefulShutdown` in (or call :meth:`request_drain`):
  admission closes at the next step boundary, waiting requests are
  shed, running ones get ``drain_timeout`` seconds to finish, and
  :attr:`drain_report` records the outcome — the pool ends idle (a
  warm prefix cache counts as idle), nothing leaks.
- **Watchdog.**  ``step_timeout > 0`` arms a
  :class:`~unicore_tpu.resilience.watchdog.StepWatchdog` around every
  ragged dispatch, with a context hook naming the stuck phase and the
  queue depths before the process exits.
- **Capacity fail-fast.**  A request whose prompt+generated prefix can
  never fit the pool terminates with reason ``"capacity"`` instead of
  cycling the preempt-retry recovery forever.

Fleet-facing API (ISSUE 11): the run loop is incrementally steppable so
a router can interleave N replicas on one thread — :meth:`submit`
enqueues, :meth:`serve_step` advances ONE scheduler iteration,
:meth:`collect_finished` drains results, :meth:`load_snapshot` is the
cheap typed health/load snapshot the router polls at admission (now
carrying prefix-cache hit stats, so a router can see affinity paying
off), and :meth:`reclaim_waiting`/:meth:`reopen` are the
rolling-restart hooks.  :meth:`generate` is a thin driver over the
same pieces, so solo-engine and fleet behavior cannot diverge.
"""

import contextlib
import dataclasses
import functools
import logging
import math
import os
import time
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from unicore_tpu.ops import moe

from . import step_log
from .attention import PagedMeta
from .kv_pool import PagedKVPool, PoolExhausted, window_kind_for
from .sampling import finite_rows, sample_tokens, step_keys
from .scheduler import DEFAULT_REQUEST_RETRIES, Scheduler

logger = logging.getLogger(__name__)

# the span tree of one serve_step (module docstring); the benchmark's
# readers (benchmarks/lib/span_readers.py) know the spans by these names
_span = jax.profiler.TraceAnnotation
SPAN_STEP = "serve/step"
SPAN_SCHEDULE = "serve/schedule"
SPAN_ADMIT = "serve/admit"
SPAN_STATE = "serve/state"
SPAN_PLAN = "serve/plan"
SPAN_WINDOW_RELEASE = "serve/window-release"
SPAN_ASSEMBLE = "serve/assemble"
SPAN_TRANSFER = "serve/transfer"
SPAN_DISPATCH = "serve/dispatch-w{width}"
SPAN_LAUNCH = "serve/launch"
SPAN_FETCH = "serve/fetch"
SPAN_EMIT = "serve/emit"


@functools.lru_cache(maxsize=None)
def _dispatch_span(width):
    """The dispatch span's name, formatted once per compiled width."""
    return SPAN_DISPATCH.format(width=width)


@dataclasses.dataclass
class ServeResult:
    request_id: Optional[str]
    prompt: List[int]
    tokens: List[int]          # generated tokens (eos included if hit)
    # "eos" | "length" | "capacity" | "expired" | "shed" | "failed" —
    # plus "replica_lost", synthesized by the FLEET router (never by an
    # engine) when a request exhausts max_failovers replica deaths
    finish_reason: str
    ttft_ms: Optional[float]   # None when no token was ever emitted
    evictions: int
    # enqueue to first admission: the scheduler's part of ttft_ms (the
    # rest is prefill); None when the request was never admitted
    queue_ms: Optional[float] = None


class WeightSwapError(RuntimeError):
    """Typed hot-swap precondition failure: the incoming param tree
    does not match the serving tree (structure, leaf shape, or dtype).
    A swap that would force a recompile — or worse, silently reshape
    what the cached jitted step programs close over — must fail BEFORE
    touching the engine; the caller (deploy rollout) treats this like
    any other bad-manifest fault: quarantine and roll back."""


class StepCompileError(RuntimeError):
    """The first call of a serve step at some (width, sampling) failed:
    tracing, lowering or compiling it raised (nothing is donated before
    that).  This is a fault of the PROGRAM, not of the requests in
    flight — failing them and carrying on would end a run with exit 0
    and no token served — so ``serve_step`` lets it propagate past the
    per-request fault isolation."""


class _SettleFirst(Exception):
    """A row's assembly failed while a step was in flight: the launch is
    given up, and the next call assembles the row again with that
    step's tokens emitted (``ServeEngine._dispatch``)."""


@dataclasses.dataclass
class _Launched:
    """A launched step whose tokens the host has not fetched."""
    out: object        # the step's one output array, on the device
    rows: list         # the planned rows that went in; row b is rows[b]
    epochs: list       # each row's ``seq.evictions`` at launch
    row_of: dict       # sid -> the row that samples its next token
    width: int
    carried: int       # tokens the list carried
    launched_at: float
    ran_ahead: bool       # launched with a step in flight
    seconds: float = 0.0  # launch (or the step before done) to fetched


DEFAULT_PREFILL_CHUNK = 32
# the mixed step's token list, in TOKENS whatever the chunk and whatever
# the model: the best of 256 / 512 / 1024 on the chip in both opt_1.3b
# cells (float32 weights, chunk 128, one v5e: PERF.md, PR 28), near where
# such weights turn from weight-bound to compute-bound (197e12 / 819e9
# FLOP a byte at 2 FLOP a weight a token and 4 bytes a weight: ~480).
# Not read for another dtype or chip; the hybrid's three-pass matmuls
# turn at ~160, and its readings at 256 / 384 / 512 are in PERF.md, PR 30
MIXED_STEP_TOKENS = 512


class ServeEngine:
    """Continuous-batching generation engine over a paged KV pool.

    ``model`` is any decoder LM following the ``examples/lm`` contract
    (``apply(variables, tokens, decode=True, positions=..., paged=...)``
    returning [B, T, V] logits, plus ``max_seq_len``/``padding_idx``
    attributes)."""

    def __init__(self, model, params, *, num_pages=64, page_size=16,
                 max_batch=8, prefill_token_budget=512, max_context=None,
                 prefill_chunk=0, prefix_cache=True, unified=True,
                 chaos_rate=0.0, chaos_rng=None, max_waiting=None,
                 request_retries=DEFAULT_REQUEST_RETRIES,
                 drain_timeout=30.0, shutdown=None, step_timeout=0.0,
                 clock=None, poison_requests=None, progress_path=None):
        self.model = model
        self.params = params
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.max_batch = int(max_batch)
        self.prefill_token_budget = int(prefill_token_budget)
        cap = (self.num_pages - 1) * self.page_size
        self.max_context = min(
            int(max_context or model.max_seq_len), model.max_seq_len, cap
        )
        self.num_slots = self.num_pages * self.page_size
        # a model with recurrent layers keeps one state per sequence
        # beside its pages (module docstring): a slot per batch row, and
        # no prefix hits, which would start a prompt where no state is
        self.recurrent = bool(getattr(model, "has_recurrent_state", False))
        # a model with sliding-window layers keeps a second kind of page
        # for them (module docstring), and takes no prefix hits either: a
        # hit would start where the window pages before it are long gone
        self.window = int(getattr(model, "attention_window", 0) or 0)
        self.prefix_cache_refused = bool(
            (self.recurrent or self.window) and prefix_cache)
        if self.prefix_cache_refused:
            logger.warning(
                "prefix cache REFUSED: the model has %s, and a prefix hit "
                "would start a prompt past its shared pages with no %s for "
                "that boundary; every prompt prefills from position 0",
                *(("recurrent layers", "recurrent state") if self.recurrent
                  else ("sliding-window layers",
                        "window pages of the tokens before it")))
        # prefill-chunk width: a prompt is admitted in <= this many
        # tokens per ragged step (bounded-TTFT slices).  0 = the default
        chunk = int(prefill_chunk) or DEFAULT_PREFILL_CHUNK
        self.prefill_chunk = max(1, min(chunk, self.max_context))
        # tokens the mixed step carries: derived, never configured.
        # MIXED_STEP_TOKENS, at least a chunk beside a token for every
        # row (so more tokens than rows: the step tells a model's
        # all-token logits from its last-token ones by that), at most
        # the rectangle
        self.mixed_tokens = min(
            self.max_batch * self.prefill_chunk,
            max(MIXED_STEP_TOKENS, self.max_batch + self.prefill_chunk))
        self.pool = PagedKVPool(
            self.num_pages, self.page_size,
            prefix_cache=prefix_cache and not self.prefix_cache_refused,
            state_slots=self.max_batch if self.recurrent else 0,
            # the window kind, from shape (module docstring)
            **window_kind_for(self.window, self.page_size, self.max_batch,
                              self.mixed_tokens))
        self.table_width = self.pool.pages_for(self.max_context)
        # pages of a row's window table: what one chunk's queries see
        self.window_table_width = (
            self.pool.window_row_pages(self.prefill_chunk)
            if self.window else 0)
        # unified=False is the bench A/B baseline: prefill rows and
        # decode rows dispatch as two separate programs per step (the
        # old split-program behavior) instead of one mixed dispatch
        self.unified = bool(unified)
        self.scheduler = Scheduler(
            self.pool, self.max_batch,
            prefill_token_budget=self.prefill_token_budget,
            chaos_rate=chaos_rate, chaos_rng=chaos_rng,
            max_waiting=max_waiting, request_retries=request_retries,
        )
        self.pages = self._init_pages()
        # bytes a token takes in the sliding layers' pages (0: no window)
        self._window_slot_bytes = self._slot_bytes("k_window_pages",
                                                   "v_window_pages")
        # expert layers that count their routing on the device, and
        # whether they hold a share of their experts (a third counter)
        loads, _, held = self._moe_counters(self.pages)
        self.moe_layers, self.moe_share = len(loads), bool(held)
        # layers whose cache is a latent page (module docstring)
        self.latent_layers = len(self._leaves_named(self.pages,
                                                    "latent_pages"))
        # the chunk-size -> compiled-width map, overridable so the
        # static audit (analysis/hlo_audit.py UL205) can check that it
        # never produces a lowering outside serve_step_widths()
        self.width_fn = self._width_for
        self._step_fns = {}
        # (width, sampling) keys whose step has run once: a failure
        # before that is a StepCompileError, not a host fault
        self._step_ran = set()
        # Pass-5 determinism harness hook: when set, called with
        # ((width, sampling), args) BEFORE the jitted call consumes
        # (donates) the pages — tools/unicore_determinism.py captures
        # host copies here and replays them twice
        self._input_capture = None
        # the step in flight (module docstring): launched, its tokens
        # not fetched; the zeros a step launched behind nothing takes in
        # its output's place (no ``token_src`` points at them); when the
        # last fetch came back; steps emitted so far
        self._in_flight = None
        self._no_prev = jnp.zeros((self._out_size(),), jnp.int32)
        self._fetched_at = 0.0
        self._steps_emitted = 0
        # one host clock for enqueue stamps, TTFT, deadlines, and the
        # drain timer — injectable so deadline/drain tests are exact
        self._clock = clock or time.perf_counter
        self.drain_timeout = float(drain_timeout)
        self.shutdown = shutdown
        self.drain_report = None
        self._drain_flag = False
        self._drain_started = None
        # incremental-stepping state (serve_step): the drain detector,
        # its counter snapshots, and the stall watchdog live on the
        # instance so a router can interleave this engine with others
        self._draining = False
        self._drain_shed0 = 0
        self._drain_expired0 = 0
        self._stalled = 0
        self.progress_path = progress_path
        # seeded poisoned-request injection (chaos harness): listed
        # request ids get their sampled-from logits row NaN'd INSIDE
        # the jitted step.  Trace-time gated — with no ids the
        # production program carries no injection code at all.
        if poison_requests is None:
            env = os.environ.get("UNICORE_TPU_CHAOS_SERVE_POISON", "")
            poison_requests = [s for s in env.split(",") if s]
        self._poison_ids = frozenset(poison_requests or ())
        self._chaos_poison = bool(self._poison_ids)
        self.watchdog = None
        if step_timeout and float(step_timeout) > 0:
            from unicore_tpu.resilience.watchdog import StepWatchdog

            self.watchdog = StepWatchdog(
                float(step_timeout), context=self._watchdog_context
            )
        # a row a step emitted, a row a request at its first token
        # (module docstring, "Metrics"); step_logs() hands out the logs
        # of the engine built last
        self.step_log = step_log.StepLog()
        self.first_token_log = step_log.FirstTokenLog()
        step_log.publish(self.step_log, self.first_token_log)
        self.stats = {
            "prefills": 0, "decode_steps": 0, "decode_tokens": 0,
            "generated_tokens": 0, "peak_pool_occupancy": 0.0,
            "decode_time_s": 0.0, "wall_time_s": 0.0,
            "pool_exhausted_recoveries": 0,
            "shed": 0, "expired": 0, "quarantined": 0, "host_faults": 0,
            "capacity_failfast": 0, "peak_waiting": 0,
            "prefix_hits": 0, "prefix_tokens_saved": 0,
            "state_resets": 0, "state_slots_peak": 0,
            # the fill of the mixed program: dispatches at the prefill
            # width, the tokens they carried, mixed_tokens x dispatches
            "mixed_steps": 0, "mixed_tokens_carried": 0,
            "mixed_tokens_capacity": 0,
            # the step in flight: launches made before the fetch of the
            # step before, and sampled tokens dropped because their
            # sequence had ended (or was preempted) by the time they
            # came back
            "steps_run_ahead": 0, "tokens_overrun": 0,
            # a model with sparse experts (module docstring): token x
            # expert assignments and experts that got a token, summed
            # over expert layers and steps; the hottest expert's load
            # over the mean as moe_stats() last read it
            "moe_assignments": 0, "moe_experts_touched": 0,
            "moe_load_max_over_mean": 0.0,
            # the choices that landed on experts held HERE (all of them
            # unless the layers hold a share)
            "moe_assignments_held": 0,
            # what one token holds in the pool over all layers, bytes
            # (a model with a window: over its GLOBAL layers) and what a
            # row holds at most in the sliding layers, whatever its length
            "cache_bytes_per_token": self._slot_bytes(
                "k_pages", "v_pages", "latent_pages"),
            "cache_bytes_per_row_window": (
                self.window_table_width * self.page_size
                * self._window_slot_bytes),
            # latent attention (module docstring): tokens the decode
            # width served, tokens the prefill width served (the step
            # log's ``carried`` by width; a benchmark test reads these)
            "latent_decode_tokens": 0, "latent_prefill_tokens": 0,
        }
        # live weight swaps installed via swap_weights (ISSUE 18);
        # _owns_params flips on the first swap — boot params may be
        # SHARED (other replicas in an in-process fleet, the trainer),
        # so only buffers the engine placed itself are donation-safe
        self.weight_swaps = 0
        self._owns_params = False

    # -- pool buffers --------------------------------------------------

    def _slot_bytes(self, *names):
        """Bytes one slot (one token) takes in the ``pagedkv`` leaves
        called ``names``, over all layers."""
        return sum(x.shape[1] * x.dtype.itemsize for name in names
                   for x in self._leaves_named(self.pages, name))

    def kv_residency(self):
        """``(resident_kv_bytes, resident_kv_bytes_one_table)``: what the
        pool's pages in use hold now, and what the same sequences would
        hold if every layer kept every token (one table for all layers).
        Counted from the two kinds' pages in use, no walk: without prefix
        sharing the global pages in use ARE the sequences' lengths in
        pages.  Equal for a model with one kind."""
        page = self.page_size * self.stats["cache_bytes_per_token"]
        held = self.pool.global_pages_in_use
        if not self.window:
            return held * page, held * page
        window_page = self.page_size * self._window_slot_bytes
        return (held * page + self.pool.window_pages_in_use * window_page,
                held * (page + window_page))

    def _init_pages(self):
        """Allocate the per-layer k/v page buffers once (eval_shape over
        flax init — zero FLOPs, exactly like the dense ``init_cache``)."""
        proto = jnp.zeros((1, 2), jnp.int32)
        meta = PagedMeta(
            page_table=jnp.zeros((1, self.table_width), jnp.int32),
            slot_mapping=jnp.zeros((2,), jnp.int32),
            lengths=jnp.ones((1,), jnp.int32),
            page_size=self.page_size,
            num_slots=self.num_slots,
            num_state_slots=self.pool.num_state_slots,
            num_window_slots=self.pool.num_window_pages * self.page_size,
        )
        shapes = jax.eval_shape(
            lambda key, p: self.model.init(
                key, p, decode=True, paged=meta,
                positions=jnp.zeros((1, 2), jnp.int32),
            ),
            jax.random.PRNGKey(0), proto,
        )["pagedkv"]
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes
        )

    @staticmethod
    def _leaves_named(pages, name):
        """The leaves of a ``pagedkv`` tree called ``name``, in layer
        order."""
        return [leaf for path, leaf
                in jax.tree_util.tree_flatten_with_path(pages)[0]
                if getattr(path[-1], "key", None) == name]

    @classmethod
    def _moe_counters(cls, pages):
        """``(loads, touched, held)``: the ``moe_load``, ``moe_touched``
        and ``moe_held`` leaves of a ``pagedkv`` tree in layer order;
        all empty for a model without expert layers, the last for one
        whose layers hold all of their experts."""
        return tuple(cls._leaves_named(pages, name)
                     for name in ("moe_load", "moe_touched", "moe_held"))

    def moe_stats(self):
        """The expert layers' routing counters, read from the device
        now: ``{"load": [[tokens each expert got] per expert layer],
        "experts_touched": [per layer, summed over steps], "assignments":
        all of ``load`` summed, "load_max_over_mean": the hottest
        expert's load over its layer's mean, the largest over the
        layers}``; None for a model without expert layers.  Also leaves
        the last of these in ``stats["moe_load_max_over_mean"]``, which
        ``load_snapshot`` carries without a read of its own.  Settles a
        step in flight first, so the device's sums and the host's
        counters cover the same steps."""
        self._settle()
        loads, touched, _ = self._moe_counters(self.pages)
        if not loads:
            return None
        loads = [np.asarray(x).astype(np.int64) for x in loads]
        skew = max((float(x.max() / x.mean()) for x in loads if x.sum()),
                   default=0.0)
        self.stats["moe_load_max_over_mean"] = skew
        return {"load": [x.tolist() for x in loads],
                "experts_touched": [int(np.asarray(x)) for x in touched],
                "assignments": int(sum(x.sum() for x in loads)),
                "load_max_over_mean": skew}

    # -- the one jitted step -------------------------------------------

    @staticmethod
    def _pick_tokens(logits, seeds, steps, temperature, top_k, sampling):
        """``sampling`` is a TRACE-TIME mode: ``"greedy"`` (the engine
        default) skips the whole sampling composition, ``"temp"`` skips
        the full-vocab top-k sort, ``"topk"`` traces everything — the
        variants compile separately and the host picks per step from
        the live batch's request params (a row samples identically
        under any variant that covers it)."""
        if sampling == "greedy":
            return jnp.argmax(
                logits.astype(jnp.float32), axis=-1
            ).astype(jnp.int32)
        return sample_tokens(
            logits, step_keys(seeds, steps), temperature, top_k,
            use_top_k=sampling == "topk",
        )

    @staticmethod
    def _sampling_mode(seqs):
        if any(s.req.top_k > 0 and s.req.temperature > 0 for s in seqs):
            return "topk"
        if any(s.req.temperature > 0 for s in seqs):
            return "temp"
        return "greedy"

    def _width_for(self, chunk):
        """Compiled width for a step whose widest row carries ``chunk``
        tokens: the pure-decode width-1 program when every row is a
        single token, the prefill-chunk program otherwise.  The
        compile surface is CONSTANT — two lowerings per sampling
        variant, independent of prompt length (the UL205 contract)."""
        return 1 if chunk <= 1 else self.prefill_chunk

    def serve_step_widths(self):
        """The declared compile surface: every ragged-step width
        ``width_fn`` may produce.  ``trace_step_fns`` traces one
        executable per entry, and UL205 fails when ``width_fn`` can
        produce a width outside this set."""
        if self.prefill_chunk == 1:
            return (1,)
        return (1, self.prefill_chunk)

    def _step_tokens(self, width):
        """Tokens in the list of the step program at ``width``."""
        return self.max_batch if width == 1 else self.mixed_tokens

    def _step_operands(self, width):
        """``[(name, shape), ...]`` of the per-dispatch operands of the
        step program at ``width``, in the order the step finds them in
        its one packed vector.  All are 32 bits wide: ``temperature`` is
        float32, ``poison`` a flag, the rest int32.  ``token_src`` says,
        for each cell of the token list, which row of the step BEFORE
        sampled its token (that step's output is the program's fourth
        input, still on the device), or -1 for the token the host wrote.
        A model that holds a recurrent state gets one operand more, its
        rows' state slots; one with a window three more, each row's
        window table, its tokens' window write slots and the position of
        the table's first slot."""
        B, n = self.max_batch, self._step_tokens(width)
        ops = [("tokens", (1, n)), ("positions", (1, n)),
               ("page_table", (B, self.table_width)),
               ("slot_mapping", (n,)), ("lengths", (B,)), ("last", (B,)),
               ("seeds", (B,)), ("steps", (B,)), ("temperature", (B,)),
               ("top_k", (B,)), ("token_src", (n,))]
        if self._chaos_poison:
            ops.append(("poison", (B,)))
        if self.recurrent:
            ops.append(("state_slots", (B,)))
        if width > 1:
            ops += [("rect_token", (B, width)), ("token_cell", (n,))]
        if self.window:
            ops += [("window_page_table", (B, self.window_table_width)),
                    ("window_slot_mapping", (n,)), ("window_base", (B,))]
        return ops

    @staticmethod
    def _packed_size(operands):
        return sum(math.prod(shape) for _, shape in operands)

    def _out_size(self):
        """Entries of the one array a step hands back: a token a row
        and, behind them, what the step added to the expert layers'
        counters."""
        return self.max_batch + (
            0 if not self.moe_layers else 3 if self.moe_share else 2)

    @staticmethod
    def _cut(packed, operands):
        """``{name: view}`` of the operands laid end to end in one
        vector: numpy views on the host, slices of the traced argument
        in the step."""
        o, end = {}, 0
        for name, shape in operands:
            o[name] = packed[end:end + math.prod(shape)].reshape(shape)
            end += math.prod(shape)
        return o

    def _last_token_rows(self, logits, last, width):
        """``[max_batch, vocab]`` logits of the tokens ``last`` out of
        what a model made of the flat list (traced).  A model that
        honours ``PagedMeta.last_token`` ran its head on those tokens
        only and returns ``[1, max_batch, vocab]``; one that ignores it
        returns ``[1, N, vocab]`` and is picked from here, correct if
        slower.  Shapes tell the two apart, since ``mixed_tokens >
        max_batch``, and at width 1 they are the same thing: row b's
        one token is token b.  Anything else fails at trace time."""
        B, n = self.max_batch, self._step_tokens(width)
        if logits.ndim != 3 or logits.shape[0] != 1 \
                or logits.shape[1] not in (B, n):
            raise ValueError(
                f"the serve step at width {width} handed "
                f"{type(self.model).__name__} tokens [1, {n}] and got "
                f"logits {logits.shape}: expected [1, {B}, vocab] (each "
                f"row's PagedMeta.last_token) or [1, {n}, vocab]")
        rows = logits[0]
        return rows if rows.shape[0] == B else jnp.take(rows, last, axis=0)

    def _ragged_step_fn(self, width, sampling):
        """The unified serve step at one static width.  Its tokens are
        a flat list (module docstring): ``tokens`` / ``positions``
        [1, N] and ``slot_mapping`` [N], with N = ``max_batch`` at width
        1 (the rectangle is the list) and ``mixed_tokens`` otherwise,
        where ``rect_token`` [max_batch, width] and ``token_cell`` [N]
        map list to rectangle and back; a token nobody carries sits at
        position -1 writing the trash slot.  Each row samples from the
        logits of its LAST token, ``last`` [max_batch] (a decode row:
        its single token; a prefill tail chunk: the final prompt token),
        the only tokens the head runs on.  The operands arrive as ONE
        int32 vector, ``_step_operands`` end to end (one transfer a
        step, not one an operand), and are cut apart here; the sampled
        tokens go back as one too, -1 where a row's logits were not
        finite.  ``prev`` is that array of the step BEFORE (zeros when
        nothing is in flight; never donated: the host fetches it after
        this step is launched): a cell whose ``token_src`` is not -1
        takes its token from there, an exact gather of int32 ids."""
        key = (width, sampling)
        fn = self._step_fns.get(key)
        if fn is None:
            model, page_size = self.model, self.page_size
            operands = self._step_operands(width)

            def forward(params, pages, o):
                rect_token = o.get("rect_token")
                meta = PagedMeta(
                    page_table=o["page_table"],
                    slot_mapping=o["slot_mapping"], lengths=o["lengths"],
                    page_size=page_size, state_slots=o.get("state_slots"),
                    rect_token=rect_token,
                    rect_positions=None if rect_token is None else jnp.take(
                        o["positions"][0], rect_token, mode="fill",
                        fill_value=-1),
                    token_cell=o.get("token_cell"), last_token=o["last"],
                    window_page_table=o.get("window_page_table"),
                    window_slot_mapping=o.get("window_slot_mapping"),
                    window_base=o.get("window_base"),
                )
                logits, mutated = model.apply(
                    {"params": params, "pagedkv": pages}, o["tokens"],
                    decode=True, positions=o["positions"], paged=meta,
                    mutable=["pagedkv"],
                )
                return logits, mutated["pagedkv"]

            def sample(rows, o):
                if "poison" in o:  # chaos injection, gated at trace time
                    rows = jnp.where(
                        o["poison"][:, None],
                        jnp.asarray(jnp.nan, rows.dtype), rows,
                    )
                ok = finite_rows(rows)
                return self._pick_tokens(
                    rows, o["seeds"], o["steps"], o["temperature"],
                    o["top_k"], sampling
                ), ok

            def moe_sums(pages):
                loads, touched, held = self._moe_counters(pages)
                sums = [sum(jnp.sum(x) for x in loads), sum(touched)]
                if held:  # a share of the experts: what landed on it
                    sums.append(sum(held))
                return jnp.stack(sums)

            def step(params, pages, packed, prev):
                o = self._cut(packed, operands)
                o["temperature"] = jax.lax.bitcast_convert_type(
                    o["temperature"], jnp.float32)
                src = o.pop("token_src")
                # a row of nonfinite logits left -1 there: its sequence is
                # quarantined when that step is emitted, and this row's
                # token dropped with it
                o["tokens"] = jnp.where(
                    src >= 0,
                    jnp.maximum(jnp.take(
                        prev[:self.max_batch], src, mode="clip"), 0),
                    o["tokens"][0])[None]
                if "poison" in o:
                    o["poison"] = o["poison"] != 0
                before = pages
                logits, pages = forward(params, pages, o)
                toks, ok = sample(self._last_token_rows(
                    logits, o["last"], width), o)
                # one array to fetch: -1 for a row of nonfinite logits
                out = jnp.where(ok, toks, -1)
                if self.moe_layers:
                    # and, behind the tokens, what this step added to the
                    # expert layers' counters (module docstring)
                    out = jnp.concatenate(
                        [out, moe_sums(pages) - moe_sums(before)])
                return out, pages

            fn = self._step_fns[key] = jax.jit(
                step, donate_argnums=(1,)
            )
        return fn

    # -- static-audit surface ------------------------------------------

    def trace_step_fns(self, *, sampling="greedy", widths=None):
        """AOT trace + lower every serve executable WITHOUT executing.

        The static-analysis subsystem audits the returned artifacts
        exactly like ``Trainer.trace_train_step``'s: the jaxpr for
        Pass-1 rules (upcast/callback/fp64), ``args_info`` for donation
        coverage, and the lowered module for the Pass-3 compiled-HLO
        audit.  All step inputs are ShapeDtypeStructs — nothing touches
        a device — and the traced jit objects are the SAME cached
        closures ``serve_step`` dispatches through, so the audit sees
        the program that serves."""
        import jax

        def sds(tree):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree
            )

        params, pages = sds(self.params), sds(self.pages)
        arts = {}
        widths = self.serve_step_widths() if widths is None else widths
        for w in widths:
            packed = jax.ShapeDtypeStruct(
                (self._packed_size(self._step_operands(w)),), jnp.int32)
            prev = jax.ShapeDtypeStruct((self._out_size(),), jnp.int32)
            traced = self._ragged_step_fn(w, sampling).trace(
                params, pages, packed, prev)
            arts[f"ragged-w{w}"] = {
                "jaxpr": traced.jaxpr, "lowered": traced.lower(),
            }
        return arts

    # -- host-side step assembly ---------------------------------------

    def _armed(self, phase):
        """Watchdog guard for a blocking dispatch (no-op when no
        ``step_timeout`` was configured)."""
        if self.watchdog is None:
            return contextlib.nullcontext()
        return self.watchdog.armed(phase)

    def _watchdog_context(self):
        """Queue-depth snapshot for the watchdog's timeout dump: a hung
        serve step should die naming what was in flight."""
        sched = self.scheduler
        return (
            f"waiting={len(sched.waiting)} running={len(sched.running)} "
            f"prefills={self.stats['prefills']} "
            f"decode_steps={self.stats['decode_steps']} "
            f"pool_free_pages={self.pool.num_free_pages} in_flight="
            + ("none" if self._in_flight is None
               else f"ragged-w{self._in_flight.width}")
        )

    def _poison_row(self, seq):
        return seq.req.request_id in self._poison_ids

    def _quarantine(self, seq, phase):
        """Retire one poisoned-row sequence: reason ``"failed"``, pages
        freed (shared prefix pages drop one reference — survivors
        sharing the prefix keep theirs), batch untouched."""
        logger.warning(
            "quarantined request %r after a nonfinite logits row in %s "
            "(%d tokens emitted so far); the rest of the batch continues",
            seq.req.request_id, phase, len(seq.generated),
        )
        self.scheduler.finish(seq, "failed")
        self.stats["quarantined"] += 1

    @staticmethod
    def _is_decode_ready(seq):
        """A sequence whose only missing KV is its newest generated
        token (steady-state decode) vs one still advancing prefill."""
        return (bool(seq.generated or seq.in_flight)
                and seq.written() == seq.length() - 1)

    def _plan_rows(self, seqs):
        """Assign this step's batch rows: ``[(seq, start, m, emit,
        is_decode), ...]``, at most ``max_batch`` of them.

        Decode-ready sequences take their single-token rows first (a
        running decode is never delayed by admission), then LEFTOVER
        row capacity soaks prompt chunks — one span per prefilling
        sequence in admission order, then EXTRA spans of the same
        prompts — until the step's ``mixed_tokens`` TOKENS are spent.
        The budget may cut the last chunk of a step short; a prompt
        that does not fit continues next step from its watermark, as it
        does when the rows run out.  Packing several consecutive chunks
        of ONE prompt into several rows of one dispatch is sound
        because every layer's KV scatter lands before its gather:
        chunk k's queries see chunk j<k's keys written in the same
        program, exactly as a single full-length prefill would — so a
        cold solo prompt fills the step's token list instead of paying
        for one ragged row and B-1 padded ones.  The rows of one
        sequence are consecutive chunks in ascending order.

        NOT for a model with recurrent layers: chunk k starts from the
        state chunk k-1 leaves, and the rows of one dispatch all start
        from the state the store held BEFORE it.  Such a model gets one
        row per sequence per dispatch; its prompt advances one chunk a
        step, as every other prefilling sequence's does beside it."""
        rows = []
        prefilling = []
        for seq in seqs:
            if self._is_decode_ready(seq):
                rows.append((seq, seq.written(), 1, True, True))
            else:
                prefilling.append([seq, seq.written()])
        budget = self.mixed_tokens - len(rows)
        while prefilling and len(rows) < self.max_batch and budget > 0:
            for entry in list(prefilling):
                if len(rows) >= self.max_batch or budget <= 0:
                    break
                seq, start = entry
                total = seq.length()
                m = min(self.prefill_chunk, total - start, budget)
                rows.append((seq, start, m, start + m == total, False))
                budget -= m
                entry[1] = start + m
                if entry[1] >= total:
                    prefilling.remove(entry)
            if self.recurrent:
                break  # one row per sequence: the state is a chain
        return rows

    def _advance_window(self, rows):
        """The window kind at the step boundary (a model with a window):
        hand back, for EVERY running sequence, the window pages no query
        still to come can see (its next query sits at ``written()``), then
        take the pages the planned rows write.  The step in flight may
        still read what is handed back here; the device's order of steps
        makes the reuse safe (``kv_pool.py``).  Releasing first, and for
        all, is what keeps the pool's counted capacity true: nobody
        holds more than its reserve beside what THIS step carries."""
        pool = self.pool
        with _span(SPAN_WINDOW_RELEASE):
            for seq in self.scheduler.running:
                pool.window_release(seq.sid, seq.written())
        upto = {}
        for seq, start, m, _, _ in rows:
            upto[seq.sid] = start + m  # rows per seq are ascending
        for sid, end in upto.items():
            pool.window_extend(sid, end)

    def _dispatch(self, rows):
        """ONE ragged step over planned ``rows`` (mixed prefill-chunk
        and decode rows): build the per-row metadata, LAUNCH the unified
        compiled program, then fetch and emit the step that is due: the
        one in flight where there is one (this launch then ran ahead of
        its tokens), else this one, unless the batch is full and it
        stays in flight for the next call (module docstring).

        Row ASSEMBLY faults stay per-request: the host work most likely
        to be poisoned by one bad request's state (slot lookups, prefix
        indexing) runs in a per-row guard that fails only that
        sequence — the unified dispatch must not widen a single
        request's blast radius from 1 to ``max_batch`` (the per-seq
        isolation the old split prefill path had).  Only a fault in the
        compiled call itself still fails the whole in-flight batch.
        Behind a step in flight a row's fault aborts the launch instead
        (:class:`_SettleFirst`): the next call meets it with that step's
        tokens emitted, as a synchronous engine would."""
        prev = self._in_flight
        with _span(SPAN_ASSEMBLE):
            B = self.max_batch
            w = self.width_fn(max(m for _, _, m, _, _ in rows))
            assert all(m <= w for _, _, m, _, _ in rows), (rows, w)
            # the step's token list: the rows' tokens end to end, with
            # the map to the [B, w] rectangle where that is another
            # thing (at width 1 token b is row b)
            N = self._step_tokens(w)
            # every operand is a view of one buffer: what the step is
            # handed whole
            operands = self._step_operands(w)
            packed = np.zeros(self._packed_size(operands), np.int32)
            o = self._cut(packed, operands)
            flat = "rect_token" in o
            o["temperature"] = o["temperature"].view(np.float32)
            tokens = o["tokens"].reshape(-1)
            positions = o["positions"].reshape(-1)
            positions[:] = -1
            slot_mapping = o["slot_mapping"]  # 0 = trash slot
            o["token_src"][:] = -1
            if flat:
                o["rect_token"][:] = N
            if self.recurrent:
                # an empty row's state slot is out of range (and unlike
                # any other row's): its gather clips, its write is dropped
                o["state_slots"][:] = B + np.arange(B, dtype=np.int32)
            live = []
            carried = 0
            for seq, start, m, emit, dec in rows:
                if seq.done:
                    continue  # failed through an earlier row this step
                b, at = len(live), carried
                mine = slice(at, at + m)
                try:
                    ptable = np.asarray(self.pool.page_table(seq.sid),
                                        np.int32)
                    pos = np.arange(start, start + m)
                    page_idx = pos // self.page_size
                    if page_idx[-1] >= len(ptable):
                        raise IndexError(
                            f"position {start + m - 1} beyond the "
                            f"{len(ptable)} page(s) of sequence {seq.sid!r}"
                        )
                    if seq.in_flight:
                        # a decode row whose token the step in flight is
                        # still sampling: the device hands it over
                        o["token_src"][at] = prev.row_of[seq.sid]
                    else:
                        tokens[mine] = seq.prefix()[start:start + m]
                    positions[mine] = pos
                    o["page_table"][b, :len(ptable)] = ptable
                    # a chunk's write slots, vectorized: one table fetch per
                    # row instead of a per-token pool.slot() call
                    slot_mapping[mine] = (
                        ptable[page_idx] * self.page_size
                        + pos % self.page_size
                    )
                    if flat:
                        o["rect_token"][b, :m] = np.arange(at, at + m)
                        o["token_cell"][mine] = b * w + np.arange(m)
                    if self.window:
                        # the row's trimmed window table, where it starts,
                        # and the tokens' write slots in the window kind
                        wpages, base = self.pool.window_view(
                            seq.sid, start, start + m)
                        wpages = np.asarray(wpages, np.int32)
                        o["window_page_table"][b, :len(wpages)] = wpages
                        o["window_base"][b] = base
                        o["window_slot_mapping"][mine] = (
                            wpages[page_idx - base // self.page_size]
                            * self.page_size + pos % self.page_size)
                    o["lengths"][b] = start + m
                    # the token of the list the row samples from
                    o["last"][b] = at + m - 1
                    o["temperature"][b] = seq.req.temperature
                    o["top_k"][b] = seq.req.top_k
                    o["seeds"][b] = seq.req.seed
                    o["steps"][b] = len(seq.generated) + seq.in_flight
                    if self._chaos_poison:
                        o["poison"][b] = self._poison_row(seq)
                except Exception as exc:  # noqa: BLE001 - per-row isolation
                    if prev is not None:
                        raise _SettleFirst() from exc
                    # scrub the half-written row (trash-slot defaults) and
                    # fail ONLY this sequence
                    tokens[mine] = 0
                    positions[mine] = -1
                    slot_mapping[mine] = 0
                    if flat:
                        o["token_cell"][mine] = 0
                        o["rect_token"][b] = N
                    o["page_table"][b] = 0
                    o["lengths"][b] = 0
                    if self.window:
                        o["window_slot_mapping"][mine] = 0
                        o["window_page_table"][b] = 0
                        o["window_base"][b] = 0
                    self._host_fault([seq], "row-assembly", exc)
                    continue
                live.append((seq, start, m, emit, dec))
                carried += m
            rows = live
            if self.recurrent:
                with _span(SPAN_STATE):
                    for b, (seq, start, *_rest) in enumerate(rows):
                        o["state_slots"][b] = self.pool.state_slot(seq.sid)
                    self.stats["state_resets"] += sum(
                        1 for r in rows if r[1] == 0)
        if not rows:
            return
        sampling = self._sampling_mode([r[0] for r in rows])
        with _span(SPAN_TRANSFER):
            args = [self.params, self.pages, jnp.asarray(packed),
                    self._no_prev if prev is None else prev.out]
        if self._input_capture is not None:
            # determinism-harness capture: before the call — the jit
            # donates the pages (argnums 1), so the buffers are gone
            # the moment it is issued
            self._input_capture((w, sampling), args)
        step_fn = self._ragged_step_fn(w, sampling)
        # the call that waits is the fetch: of the step in flight where
        # there is one, and the watchdog names THAT step
        waits = w if prev is None else prev.width
        with _span(_dispatch_span(w)), self._armed(f"serve/ragged-w{waits}"):
            with _span(SPAN_LAUNCH):
                t0 = time.perf_counter()
                try:
                    out, self.pages = step_fn(*args)
                except Exception as exc:
                    if (w, sampling) in self._step_ran:
                        raise
                    raise StepCompileError(
                        f"serve step ragged-w{w}/{sampling} failed on its "
                        f"first call (trace, lowering or compile): {exc}"
                    ) from exc
                self._step_ran.add((w, sampling))
            step = self._in_flight = _Launched(
                out=out, rows=rows, epochs=[r[0].evictions for r in rows],
                row_of={r[0].sid: b for b, r in enumerate(rows) if r[3]},
                width=w, carried=carried, launched_at=t0,
                ran_ahead=prev is not None)
            for seq, _, m, emit, _ in rows:
                seq.launched += m
                seq.in_flight += emit
            if prev is not None:
                self.stats["steps_run_ahead"] += 1
            # the step whose tokens this call hands out: the one that
            # was in flight, else this one unless the batch is full
            due = prev or (None if self._batch_full() else step)
            if due is not None:
                toks = self._fetch(due)
        if due is not None:
            self._emit_step(due, toks)

    def _batch_full(self):
        """The rule's observable (module docstring): every row is held
        by a sequence that goes on past what has been launched, so
        ``Scheduler.admit`` could let nobody into the next step
        (``len(running) < max_batch`` is its test) whoever arrives
        meanwhile.  A sequence whose token in flight is known to be its
        last (``max_new_tokens``, ``max_context``) frees its row.  Never
        with the split A/B baseline, nor under chaos preemption, whose
        draws a step in flight would move."""
        sched = self.scheduler
        return (self.unified and len(sched.running) >= self.max_batch
                and not (sched.chaos_rate > 0 and sched.chaos_rng is not None)
                and not any(
                    s.in_flight and (
                        len(s.generated) + 1 >= s.req.max_new_tokens
                        or s.length() > self.max_context)
                    for s in sched.running))

    def _fetch(self, step):
        """The tokens of a launched step onto the host: the one call
        that waits for the device."""
        with _span(SPAN_FETCH):
            # host sync: the scheduler needs the tokens, and which
            # rows sampled from finite logits (a token of -1: not)
            toks = np.asarray(step.out)
        t1 = time.perf_counter()
        # the device took it up when it was launched or, launched ahead,
        # when the step before it was done
        step.seconds = t1 - max(step.launched_at, self._fetched_at)
        self._fetched_at = t1
        if step is self._in_flight:
            self._in_flight = None
        return toks

    def _settle(self):
        """Fetch and emit the step in flight, if there is one: what
        every reader of the engine between two steps does first."""
        step = self._in_flight
        if step is not None:
            with self._armed(f"serve/ragged-w{step.width}"):
                toks = self._fetch(step)
            self._emit_step(step, toks)
        return step is not None

    def _emit_step(self, step, toks):
        """A fetched step into the scheduler's state: counters,
        quarantine, prefill watermark, ``register_prefix``, ``_emit``,
        in row order, then the step's row in the step log.  A row whose
        sequence ended or was preempted after the step was launched is
        dropped: its token is an OVERRUN (module docstring), counted and
        never emitted."""
        with _span(SPAN_EMIT):
            self._steps_emitted += 1
            B, w, dt = self.max_batch, step.width, step.seconds
            toks, routed = toks[:B], toks[B:]
            ok = toks >= 0
            rows = []
            for b, (row, epoch) in enumerate(zip(step.rows, step.epochs)):
                if row[0].done or row[0].evictions != epoch:
                    self.stats["tokens_overrun"] += row[3]
                else:
                    rows.append((b, row))
            decode_rows = sum(1 for _, r in rows if r[4])
            self.stats["prefills"] += len(rows) - decode_rows
            if self.moe_layers:
                assigned, touched = int(routed[0]), int(routed[1])
                held = int(routed[2]) if self.moe_share else assigned
                self.stats["moe_assignments"] += assigned
                self.stats["moe_assignments_held"] += held
                self.stats["moe_experts_touched"] += touched
                moe.note_routing(held, touched)
            if self.latent_layers:
                self.stats["latent_decode_tokens" if w == 1
                           else "latent_prefill_tokens"] += step.carried
            if w > 1:
                self.stats["mixed_steps"] += 1
                self.stats["mixed_tokens_carried"] += step.carried
                self.stats["mixed_tokens_capacity"] += self._step_tokens(w)
            if decode_rows:
                self.stats["decode_time_s"] += dt
                self.stats["decode_steps"] += 1
                self.stats["decode_tokens"] += decode_rows
                if self.progress_path:
                    with open(self.progress_path, "a") as fh:
                        fh.write(f"{self.stats['decode_steps']}\n")
            for b, (seq, start, m, emit, _) in rows:
                if seq.done:
                    continue  # quarantined through an earlier row this step
                if not bool(ok[b]):
                    self._quarantine(seq, f"ragged-w{w}")
                    continue
                seq.prefilled = start + m  # rows per seq are ascending
                seq.launched -= m
                if (not seq.prefix_registered
                        and seq.prefilled >= len(seq.req.prompt)):
                    # the prompt's KV is fully written: index its full
                    # pages so later shared-prefix requests dedup
                    self.pool.register_prefix(seq.sid, seq.req.prompt)
                    seq.prefix_registered = True
                if emit:
                    seq.in_flight -= 1
                    self._emit(seq, int(toks[b]))
            self.step_log.write(
                self._steps_emitted, w, step.carried, self._step_tokens(w),
                decode_rows, step.ran_ahead, dt, *self.kv_residency())

    def _emit(self, seq, token):
        """Append one sampled token and settle termination."""
        seq.generated.append(token)
        self.stats["generated_tokens"] += 1
        if seq.first_token_at is None:
            seq.first_token_at = self._clock()  # same clock as enqueued_at
            self.first_token_log.write(
                seq.admitted_at, seq.first_token_at, self._steps_emitted)
        req = seq.req
        if req.eos_id is not None and token == req.eos_id:
            self.scheduler.finish(seq, "eos")
        elif len(seq.generated) >= req.max_new_tokens:
            self.scheduler.finish(seq, "length")
        elif len(req.prompt) + len(seq.generated) > self.max_context:
            # the NEXT decode would need a KV slot at position
            # max_context — beyond the table width; truncate here
            self.scheduler.finish(seq, "capacity")

    # -- public API ----------------------------------------------------

    def submit(self, requests):
        """Validate and enqueue a batch of :class:`Request`s WITHOUT
        driving them; returns the scheduler's Sequence handles.  The
        fleet router's admission path — pair with :meth:`serve_step`
        and :meth:`collect_finished`.  A bounded queue may shed some of
        them immediately; the shed sequences come back terminal."""
        self._validate_requests(requests)
        seqs = [self._enqueue(req) for req in requests]
        if self.scheduler.num_shed:
            self._sync_lifecycle_stats()
        return seqs

    def _enqueue(self, req, generated=None):
        """One validated request into the scheduler (may shed
        immediately — bounded queue) with the shared bookkeeping:
        enqueue stamp on the engine clock, peak-waiting gauge."""
        seq = self.scheduler.add(req, generated=generated)
        seq.enqueued_at = self._clock()
        self.stats["peak_waiting"] = max(
            self.stats["peak_waiting"], len(self.scheduler.waiting)
        )
        return seq

    def _validate_requests(self, requests):
        # validate EVERYTHING before enqueuing anything: a mid-list
        # reject must not leave earlier requests queued as ghost work
        # for the next generate()/submit() call
        for req in requests:
            if len(req.prompt) > self.max_context:
                raise ValueError(
                    f"prompt of {len(req.prompt)} tokens exceeds the "
                    f"engine's context of {self.max_context} "
                    "(num_pages * page_size and model.max_seq_len bound "
                    "it); generation past the context is truncated with "
                    'a "capacity" finish instead'
                )
            if not req.prompt:
                raise ValueError("empty prompt")
            if req.max_new_tokens < 1:
                raise ValueError("max_new_tokens must be >= 1")
            if not 0 <= req.seed < 2 ** 31:
                raise ValueError(
                    f"seed {req.seed} out of the int32 sampling-key "
                    "range [0, 2**31)"
                )
            if req.deadline_ms is not None and req.deadline_ms <= 0:
                raise ValueError(
                    f"deadline_ms must be > 0, got {req.deadline_ms!r}"
                )

    def adopt(self, request, generated=None):
        """Enqueue one request SALVAGED from a dead replica together
        with the tokens it already generated there (the fleet router's
        failover path).  The sequence enters exactly like a preempted
        requeue: admission re-prefills ``prompt + generated`` — with a
        warm prefix cache most of that re-prefill is page-table
        lookups — and absolute-step sampling keys continue the stream
        token-identically from where the dead replica stopped.  The
        deadline TTL restamps from THIS enqueue (the request already
        survived a replica loss; ``max_failovers`` bounds its total
        lifetime instead).  A bounded queue may shed it immediately;
        the shed sequence comes back terminal."""
        self._validate_requests([request])
        seq = self._enqueue(request, generated=generated)
        if self.scheduler.num_shed:
            self._sync_lifecycle_stats()
        return seq

    def generate(self, requests) -> List[ServeResult]:
        """Run a batch of :class:`Request`s to completion; results come
        back in request order."""
        sched = self.scheduler
        seqs = self.submit(requests)
        t0 = time.perf_counter()
        try:
            self._run_to_completion(sched)
        except BaseException:
            # mid-run failure (device OOM, interrupt): detach THIS
            # call's unfinished sequences and free their pages so the
            # engine stays usable — otherwise the next generate() would
            # silently decode this call's ghosts against its pool
            self._settle_or_abandon()
            for seq in seqs:
                if seq.done:
                    continue
                if seq in sched.running:
                    sched.running.remove(seq)
                    self.pool.free(seq.sid)
                elif seq in sched.waiting:
                    sched.waiting.remove(seq)
            raise
        self.stats["wall_time_s"] += time.perf_counter() - t0
        self.stats["evictions"] = sched.num_evictions
        if self.stats["decode_time_s"] > 0:
            self.stats["decode_tokens_per_sec"] = (
                self.stats["decode_tokens"] / self.stats["decode_time_s"]
            )
        # this call's Sequence objects carry their own terminal state —
        # and draining them from sched.finished keeps a long-lived
        # engine's memory flat across generate() calls
        ours = set(id(s) for s in seqs)
        sched.finished = [s for s in sched.finished if id(s) not in ours]
        out = []
        for seq in seqs:
            assert seq.done, "generate() returned with an unfinished seq"
            out.append(self._result_of(seq))
        return out

    @staticmethod
    def _result_of(seq):
        return ServeResult(
            request_id=seq.req.request_id,
            prompt=list(seq.req.prompt),
            tokens=list(seq.generated),
            finish_reason=seq.finish_reason,
            ttft_ms=(
                None if seq.first_token_at is None
                else (seq.first_token_at - seq.enqueued_at) * 1e3
            ),
            evictions=seq.evictions,
            queue_ms=(
                None if seq.admitted_at is None
                else (seq.admitted_at - seq.enqueued_at) * 1e3
            ),
        )

    def collect_finished(self) -> List[ServeResult]:
        """Drain every finished sequence into results (the fleet
        router's harvest path; keeps a long-lived engine's finished
        list from growing without bound)."""
        done, self.scheduler.finished = self.scheduler.finished, []
        return [self._result_of(seq) for seq in done]

    # -- lifecycle plumbing --------------------------------------------

    def request_drain(self):
        """Programmatic drain trigger — same semantics as SIGTERM
        through a wired :class:`GracefulShutdown`: admission closes at
        the next step boundary, running work gets ``drain_timeout``
        seconds, and the engine stays drained (a drained engine sheds
        everything a later ``generate()`` enqueues)."""
        self._drain_flag = True

    def _drain_requested(self):
        return self._drain_flag or bool(
            self.shutdown is not None and self.shutdown.requested
        )

    def _sync_lifecycle_stats(self):
        self.stats["shed"] = self.scheduler.num_shed
        self.stats["expired"] = self.scheduler.num_expired
        self.stats["prefix_hits"] = self.pool.prefix_stats["hits"]
        self.stats["prefix_tokens_saved"] = (
            self.pool.prefix_stats["tokens_saved"])
        self.stats["state_slots_peak"] = self.pool.state_stats["peak"]

    def _fail_capacity(self, seq):
        """Satellite fix: a request whose prefix can never fit even an
        EMPTY pool must terminate — retrying admission (or the
        preempt-retry recovery) forever cannot make room that does not
        exist.  Reason ``"capacity"``, counted in metrics."""
        logger.warning(
            "request %r needs %d pages for its %d-token prefix; the "
            "pool holds %d — failing fast with reason 'capacity'",
            seq.req.request_id,
            self.pool.pages_for(len(seq.prefix())), len(seq.prefix()),
            self.pool.num_usable_pages,
        )
        self.scheduler.finish(seq, "capacity")
        self.stats["capacity_failfast"] += 1

    def _host_fault(self, seqs, phase, exc):
        """A host-side step fault (sampler bug, bad batch assembly)
        fails the IN-FLIGHT sequences, not the engine: they finish
        ``"failed"``, their pages free, and the loop continues with the
        rest.  Only when the fault consumed the donated pool buffers
        (the jit died after invalidating its donation) is the engine
        unservable — that re-raises."""
        if any(getattr(leaf, "is_deleted", lambda: False)()
               for leaf in jax.tree_util.tree_leaves(self.pages)):
            logger.error(
                "%s fault consumed the donated pool buffers — the "
                "engine cannot continue", phase,
            )
            raise exc
        failed = [s for s in seqs
                  if not s.done and s in self.scheduler.running]
        logger.error(
            "host-side %s fault failed %d in-flight request(s): %r",
            phase, len(failed), exc,
        )
        for seq in failed:
            self.scheduler.finish(seq, "failed")
        self.stats["host_faults"] += 1

    def _run_to_completion(self, sched):
        del sched  # serve_step reads self.scheduler
        while self.serve_step():
            pass

    def has_work(self):
        """Work queued, or a launched step whose tokens have yet to be
        handed out."""
        return self.scheduler.has_work() or self._in_flight is not None

    def _step_rows(self, todo):
        """Dispatch this step's planned rows.  Unified (production):
        ONE mixed ragged dispatch.  Split (``unified=False``, the
        bench A/B baseline): prefill rows and decode rows run as two
        separate programs — the old two-program shape, expressed
        through the same machinery so the comparison isolates the
        unification."""
        with _span(SPAN_PLAN):
            rows = self._plan_rows(todo)
            if self.window:
                self._advance_window(rows)
        if not rows:
            return
        if self.unified:
            self._dispatch(rows)
            return
        for group in ([r for r in rows if not r[4]],
                      [r for r in rows if r[4]]):
            live = [r for r in group
                    if r[0] in self.scheduler.running and not r[0].done]
            if live:
                self._dispatch(live)

    def serve_step(self):
        """Advance the engine by ONE scheduler iteration: deadline
        expiry, drain bookkeeping, capacity fail-fast, admission, one
        ragged dispatch (mixed prefill-chunk + decode rows).  Returns
        True while work remains — queued, or a step in flight — the
        fleet router's interleaving unit (and what ``generate()`` loops
        on).  With a step in flight the call either launches the next
        step and then hands out that one's tokens, or only hands them
        out (module docstring); a call emits one step at most.  An idle
        call is cheap, records no span and finalizes a pending drain
        report."""
        if not self.has_work():
            return self._settle_idle()
        with _span(SPAN_STEP):
            return self._step_with_work()

    def _settle_idle(self):
        self._sync_lifecycle_stats()
        self._maybe_finalize_drain()
        self._stalled = 0
        return False

    def _continuation(self):
        """With a step in flight: the sequences of the next step where
        it may be launched BEFORE that step's tokens are fetched, else
        None (settle first).  The rule (module docstring): the batch is
        full, so nobody who arrived meanwhile could have been admitted,
        and the plan is plain continuation — no drain under way, no
        waiting head to fail for capacity, no page that needs an
        eviction.  (Expiry and the drain's sheds have run: whoever they
        ended freed a row.)"""
        sched = self.scheduler
        if not self._batch_full() or self._draining:
            return None
        if self._head_cannot_fit():
            return None
        return sched.prepare_decode(evict=False)

    def _head_cannot_fit(self):
        """The waiting head needs more pages than an EMPTY pool holds."""
        waiting = self.scheduler.waiting
        return bool(waiting) and (
            self.pool.pages_for(waiting[0].length())
            > self.pool.num_usable_pages)

    def _settle_or_abandon(self):
        """Settle, or where the fetch itself fails give the step in
        flight up: what it launched of the running sequences is
        forgotten (a fault's clean-up, not a way to take a step back:
        a recurrent state has moved)."""
        try:
            self._settle()
        except Exception:  # noqa: BLE001 - the fault is the caller's to report
            self._in_flight = None
            for seq in list(self.scheduler.running):
                if self.window and seq.launched:
                    # the window pages behind what it launched are gone:
                    # its positions cannot be taken up again from
                    # ``prefilled``, so it prefills anew
                    self.scheduler.preempt(seq)
                seq.in_flight = seq.launched = 0

    def _step_with_work(self):
        sched = self.scheduler
        failed_fast = shed_now = 0
        admitted, did_dispatch, expired = [], False, False
        emitted0 = self._steps_emitted
        try:
            with _span(SPAN_SCHEDULE):
                todo = []
                now = self._clock()
                # deadline expiry at the ADMISSION boundary: a blown
                # request must not take (or keep) pool pages
                expired = bool(sched.expire(now))
                if not self._draining and self._drain_requested():
                    self._draining = True
                    self._drain_started = now
                    # report what the DRAIN cut, not lifetime counters —
                    # pre-drain overload sheds are not the drain's doing
                    self._drain_shed0 = sched.num_shed
                    self._drain_expired0 = sched.num_expired
                    logger.warning(
                        "drain requested: admission closed; shedding %d "
                        "waiting request(s), %d running get %.1fs to "
                        "finish", len(sched.waiting), len(sched.running),
                        self.drain_timeout,
                    )
                if self._draining:
                    # admission is closed: what waits now can never run
                    for seq in list(sched.waiting):
                        sched.finish(seq, "shed")
                        shed_now += 1
                    if (now - self._drain_started) > self.drain_timeout:
                        for seq in list(sched.running):
                            sched.finish(seq, "shed")
                            shed_now += 1
                self._sync_lifecycle_stats()
                if self._in_flight is not None:
                    # whoever ended above has its token in flight dropped
                    # when that step is emitted: the result a synchronous
                    # engine gives, which had emitted as far
                    todo = self._continuation()
                elif not sched.has_work():
                    return self._settle_idle()
                else:
                    # capacity fail-fast BEFORE admission: a head request
                    # that can never fit would otherwise stall the queue
                    while self._head_cannot_fit():
                        self._fail_capacity(sched.waiting[0])
                        failed_fast += 1
                    if not self._draining:
                        # admit() hands back fresh AND resumed sequences —
                        # their ragged prefill starts past any
                        # shared-prefix pages the pool matched (a resumed
                        # one re-creates exactly the KV its eviction
                        # dropped)
                        with _span(SPAN_ADMIT):
                            admitted = sched.admit(
                                bucket=lambda n: min(n, self.prefill_chunk))
                        for seq in admitted:
                            if seq.admitted_at is None:  # resumed: kept
                                seq.admitted_at = now
                        sched.chaos_preempt()
                    if sched.running:
                        todo = sched.prepare_decode()
            if todo is None:
                # a step in flight and no plain continuation: hand out
                # its tokens; the next call schedules and launches as a
                # synchronous engine would
                self._settle()
            elif todo:
                try:
                    self._step_rows(todo)
                except StepCompileError:
                    raise  # the program is broken, not a request
                except _SettleFirst:
                    self._settle()
                except Exception as exc:  # host fault isolation
                    # the step that was in flight is older than the
                    # fault: its tokens are the requests' own
                    self._settle_or_abandon()
                    self._host_fault(todo, "ragged-step", exc)
                did_dispatch = True
            if self._steps_emitted != emitted0:
                # deadline expiry at the DECODE boundary: pages free
                # the moment the deadline blows, not a decode tail later
                expired = bool(sched.expire(self._clock())) or expired
        except PoolExhausted:
            # a pathological admission race got past the
            # can_alloc/extend guards (e.g. page accounting the
            # scheduler didn't see move).  This is recoverable,
            # not fatal: preempt the scheduler's LIFO victim — the
            # same requeue-front path organic exhaustion takes, so
            # nothing is lost and its re-prefill recreates the
            # dropped KV — and retry the step on the freed pages.
            self._settle_or_abandon()
            if not sched.running:
                if sched.waiting and self.pool.is_idle():
                    # even an EMPTY pool cannot hold the head
                    # request: capacity, not a recoverable race
                    self._fail_capacity(sched.waiting[0])
                    self._stalled = 0
                    return True
                raise  # pages missing with nothing running: a bug
            sched.preempt(sched._pick_victim())
            self.stats["pool_exhausted_recoveries"] += 1
            self._stalled = 0  # freed pages guarantee the retry runs
            return True
        self.stats["peak_pool_occupancy"] = max(
            self.stats["peak_pool_occupancy"], self.pool.occupancy()
        )
        self.stats["peak_waiting"] = max(
            self.stats["peak_waiting"], len(sched.waiting)
        )
        # an iteration may legitimately emit nothing when its only
        # event was an eviction (chaos, or an exhaustion cascade
        # that drained the batch): the freed pages guarantee the
        # NEXT iteration admits.  Two empty iterations in a row
        # cannot happen unless the scheduler is genuinely wedged.
        # A launch is progress, and so are a step's tokens handed out.
        progressed = bool(admitted or did_dispatch or expired
                          or failed_fast or shed_now
                          or self._steps_emitted != emitted0)
        self._stalled = 0 if progressed else self._stalled + 1
        if self._stalled >= 2 and sched.has_work():
            raise RuntimeError(
                "scheduler stalled with work queued — this is a bug "
                "(the admission guard should make progress "
                "inevitable)"
            )
        if not self.has_work():
            return self._settle_idle()
        return True

    def _maybe_finalize_drain(self):
        """Write the drain report once the queue empties while a drain
        is active, and re-arm the detector (the flag stays set — a
        drained engine sheds whatever a later submit enqueues, and the
        NEXT drive re-snapshots its own counters)."""
        if not self._draining:
            return
        drain_ms = (self._clock() - self._drain_started) * 1e3
        signame = None
        if (self.shutdown is not None
                and self.shutdown.signum is not None):
            import signal

            signame = signal.Signals(self.shutdown.signum).name
        self.drain_report = {
            "requested": True,
            "signal": signame,
            "drain_ms": round(drain_ms, 2),
            "drain_timeout_s": self.drain_timeout,
            "shed": self.scheduler.num_shed - self._drain_shed0,
            "expired": self.scheduler.num_expired - self._drain_expired0,
            "deadline_exceeded": drain_ms > self.drain_timeout * 1e3,
            "pool_idle": self.pool.is_idle(),
        }
        self._draining = False
        logger.warning("drain complete: %s", self.drain_report)

    # -- fleet-facing surface ------------------------------------------

    def load_snapshot(self):
        """Cheap router-facing load/health snapshot — a STABLE typed
        dict (tests pin the keys and types; routers across versions
        depend on them):

        ``free_pages``/``total_pages`` (int) pool headroom (cached
        prefix pages count as free), ``waiting``/``running`` (int)
        queue depths, ``free_slots`` (int) open decode-batch rows,
        ``max_waiting`` (int or None) the bounded-queue shed line,
        ``draining`` (bool) admission closed (flag set or a wired
        shutdown requested), ``step_ms`` (float) median of the recent
        decode-step wall latencies (the step log's ``device_s`` over its
        last 33 rows with a decode row; 0.0 until the first) — what
        the router multiplies queue depth by to project a request's
        wait against its deadline — and the prefix-cache hit surface:
        ``prefix_hits`` (int), ``prefix_tokens_saved`` (int),
        ``prefix_hit_rate`` (float, hits/lookups, 0.0 before the first
        lookup) — how much the router's session affinity is paying
        off on this replica; ``prefix_cache_refused`` (bool) says the
        hit surface will stay at zero because the model has recurrent
        layers and the engine refused the cache it was asked for, so a
        router should not spend affinity on this replica's behalf.

        Health surface (ISSUE 14): ``last_progress`` (int) is the
        retired-token watermark — the monotonic count of tokens this
        replica has ever emitted; a replica holding work whose
        watermark does not advance for the router's progress budget is
        WEDGED, whatever its queues claim.  ``host_faults`` (int) is
        the monotonic host-fault counter; the router differences it
        per fleet step, and a burst over its fault window marks the
        replica dead before a wedge would.

        The fill of the mixed step (monotonic, for a reader to
        difference): ``mixed_steps`` (int) dispatches at the prefill
        width, ``mixed_tokens_carried`` (int) the tokens they carried,
        ``mixed_tokens_capacity`` (int) the tokens their programs were
        compiled for; carried over capacity is how much of a mixed
        step's dense work was for tokens somebody sent.

        The step in flight (monotonic): ``steps_run_ahead`` (int)
        launches made before the tokens of the step before were
        fetched — over the steps served, how often the batch was full;
        ``tokens_overrun`` (int) sampled tokens dropped, never emitted,
        because their sequence had ended (``eos_id``, a quarantine, a
        deadline) by the time they came back.

        A model with sparse experts (zeros for any other):
        ``moe_assignments`` (int) token x expert assignments and
        ``moe_experts_touched`` (int) experts that got a token, both
        summed over expert layers and steps (touched over steps x expert
        layers is how many experts' weights a step reads);
        ``moe_load_max_over_mean`` (float) the hottest expert's load over
        the mean, as :meth:`moe_stats` last read it from the device (0.0
        before the first read: the snapshot itself reads nothing);
        ``moe_assignments_held`` (int) the assignments that landed on
        experts this replica holds (all of them unless its layers hold a
        share).  ``cache_bytes_per_token`` (int): what one token holds in
        the pool over all layers (K/V pages, or a latent model's one
        narrow vector a layer): with ``free_pages`` x the page size, how
        many tokens of context this replica can still take."""
        sched = self.scheduler
        log = self.step_log
        recent = np.sort(log.column("device_s")[
            log.column("decode_rows") > 0][-33:])
        step_ms = float(recent[len(recent) // 2]) * 1e3 if len(recent) else 0.0
        ps = self.pool.prefix_stats
        hit_rate = (ps["hits"] / ps["lookups"]) if ps["lookups"] else 0.0
        return {
            "free_pages": int(self.pool.num_free_pages),
            "total_pages": int(self.pool.num_usable_pages),
            "waiting": int(len(sched.waiting)),
            "running": int(len(sched.running)),
            "free_slots": int(max(0, self.max_batch - len(sched.running))),
            "max_waiting": (None if sched.max_waiting is None
                            else int(sched.max_waiting)),
            "draining": bool(self._draining or self._drain_requested()),
            "step_ms": round(step_ms, 4),
            "prefix_hits": int(ps["hits"]),
            "prefix_tokens_saved": int(ps["tokens_saved"]),
            "prefix_hit_rate": round(float(hit_rate), 4),
            "prefix_cache_refused": bool(self.prefix_cache_refused),
            "last_progress": int(self.stats["generated_tokens"]),
            "host_faults": int(self.stats["host_faults"]),
            "mixed_steps": int(self.stats["mixed_steps"]),
            "mixed_tokens_carried": int(self.stats["mixed_tokens_carried"]),
            "mixed_tokens_capacity": int(
                self.stats["mixed_tokens_capacity"]),
            "steps_run_ahead": int(self.stats["steps_run_ahead"]),
            "tokens_overrun": int(self.stats["tokens_overrun"]),
            "moe_assignments": int(self.stats["moe_assignments"]),
            "moe_experts_touched": int(self.stats["moe_experts_touched"]),
            "moe_load_max_over_mean": round(
                float(self.stats["moe_load_max_over_mean"]), 4),
            "moe_assignments_held": int(self.stats["moe_assignments_held"]),
            "cache_bytes_per_token": int(
                self.stats["cache_bytes_per_token"]),
        }

    def reclaim_waiting(self, *, include_running=False):
        """Detach and return every WAITING request (rolling restart:
        the router reroutes them to other replicas before this one
        drains).  Waiting sequences hold no pool pages, so nothing
        leaks; a reclaimed request re-runs from scratch elsewhere, and
        absolute-step-keyed sampling makes the re-run token-identical
        — even for a preempted sequence whose generated tokens are
        simply regenerated.

        ``include_running=True`` is the FAILOVER salvage (the router's
        dead-replica eviction): RUNNING sequences are force-detached
        too, and the return value becomes ``[(Request, generated), …]``
        pairs — running first (they carry sunk decode work, mirroring
        the preemption requeue-at-front priority), then waiting in
        queue order — so a healthy replica can :meth:`adopt` each one
        and re-prefill prompt+generated instead of re-decoding.  Page
        frees on the dead pool are best-effort: the replica is leaving
        the fleet, its pool dies with it."""
        sched = self.scheduler
        if not include_running:
            reqs = [seq.req for seq in sched.waiting]
            sched.waiting.clear()
            return reqs
        # a step in flight holds tokens of the running ones: hand them
        # out first (best effort, like the frees: the replica is dying)
        self._settle_or_abandon()
        salvaged = []
        for seq in list(sched.running):
            salvaged.append((seq.req, list(seq.generated)))
            sched.running.remove(seq)
            try:
                self.pool.free(seq.sid)
            except Exception as e:  # noqa: BLE001 - dying pool, best effort
                logger.warning(
                    "failover salvage: freeing %r on the dead replica's "
                    "pool failed (%s) — the pool leaves with the replica",
                    seq.sid, e,
                )
        salvaged.extend((seq.req, list(seq.generated))
                        for seq in sched.waiting)
        sched.waiting.clear()
        return salvaged

    def reopen(self):
        """Re-open admission after a COMPLETED drain — the fleet
        router's in-place "restart" when no replacement-engine factory
        is given.  Refuses on a non-idle pool or queued work: reopening
        mid-drain would resurrect exactly the half-drained state the
        drain existed to retire."""
        self._settle()
        if self.scheduler.has_work() or not self.pool.is_idle():
            raise RuntimeError(
                "reopen() on a busy engine: drain to idle first "
                f"(waiting={len(self.scheduler.waiting)} "
                f"running={len(self.scheduler.running)} "
                f"pool_idle={self.pool.is_idle()})"
            )
        self._drain_flag = False
        self._draining = False
        # the restart's drain record must not masquerade as a LATER
        # drain's report (the router synthesizes a fresh zero report
        # for an idle replica only when this is None)
        self.drain_report = None
        if self.shutdown is not None and hasattr(self.shutdown, "clear"):
            self.shutdown.clear()  # ChildShutdown: fleet-wide reads through

    # -- live weight hot-swap (ISSUE 18) -------------------------------

    def swap_weights(self, new_params, *, donate=None):
        """Install ``new_params`` IN PLACE between serve steps, donating
        the old param buffers the engine owns.

        The cached jitted step programs take params as a NON-donated
        argument, so replacing :attr:`params` with a tree of identical
        structure/shapes/dtypes reuses every compiled program — no
        retrace, no recompile.  Everything else survives untouched: the
        paged KV pool, the prefix-cache index, page tables, and every
        in-flight sequence (their KV history was computed token by
        token and lives in the pool, not in the weights).

        Same tree structure + per-leaf shape/dtype is a HARD
        precondition — violations raise :class:`WeightSwapError` before
        the engine is touched.  On success the OLD leaves are deleted
        explicitly (donation-in-place): during a rollout HBM must hold
        one param set per replica plus the pool, never two param sets
        waiting on the garbage collector.  ``donate=None`` (auto)
        deletes only buffers a PREVIOUS swap installed — the boot
        params may be shared (sibling replicas of an in-process fleet,
        or the trainer that built them) and the engine cannot prove
        ownership of what it did not place; pass ``donate=True`` when
        the caller guarantees exclusive ownership, ``donate=False`` to
        never delete.

        Must be called at a step boundary (the deploy subscriber hooks
        the fleet router's step loop); never from inside a dispatch.
        A step in flight is settled first (part of the stall): it ran
        on the OLD weights, so behind a full batch the new ones serve
        from the step after it.  Returns the host-side stall in
        seconds."""
        old = self.params
        old_struct = jax.tree_util.tree_structure(old)
        new_struct = jax.tree_util.tree_structure(new_params)
        if new_struct != old_struct:
            raise WeightSwapError(
                f"param tree structure mismatch: engine serves "
                f"{old_struct}, swap offered {new_struct}"
            )
        old_leaves = jax.tree_util.tree_leaves(old)
        new_leaves = jax.tree_util.tree_leaves(new_params)
        for i, (o, n) in enumerate(zip(old_leaves, new_leaves)):
            o_shape, n_shape = tuple(np.shape(o)), tuple(np.shape(n))
            o_dtype = np.asarray(o).dtype if not hasattr(o, "dtype") \
                else o.dtype
            n_dtype = np.asarray(n).dtype if not hasattr(n, "dtype") \
                else n.dtype
            if o_shape != n_shape or o_dtype != n_dtype:
                raise WeightSwapError(
                    f"param leaf {i} mismatch: engine serves "
                    f"{o_shape}/{o_dtype}, swap offered "
                    f"{n_shape}/{n_dtype}"
                )
        t0 = self._clock()
        # a step in flight was launched on the old weights: its tokens
        # are handed out before the buffers it reads may go
        self._settle()
        placed = jax.tree_util.tree_map(jnp.asarray, new_params)
        # commit before the cutover: a device transfer failing halfway
        # must leave the engine on its OLD params, not a broken tree.
        # The sync is the point — swap_weights runs at a step boundary
        # (never inside a dispatch) and RETURNS the measured stall
        jax.block_until_ready(placed)  # unicore-lint: disable=UL104
        self.params = placed
        if donate is None:
            donate = self._owns_params
        if donate:
            placed_ids = {id(leaf)
                          for leaf in jax.tree_util.tree_leaves(placed)}
            for leaf in old_leaves:
                # a self-swap (rollback to buffers the caller still
                # holds) must not delete the arrays it just installed
                if id(leaf) in placed_ids or not isinstance(leaf, jax.Array):
                    continue
                if not leaf.is_deleted():
                    leaf.delete()
        self._owns_params = True
        self.weight_swaps += 1
        stall = self._clock() - t0
        logger.info(
            "weight swap #%d installed (%d leaves, %.2f ms host stall)",
            self.weight_swaps, len(old_leaves), stall * 1e3,
        )
        return stall
