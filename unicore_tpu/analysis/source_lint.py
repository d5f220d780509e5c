"""Pass 2: repo-specific AST lint over Python sources.

Rules (see docs/static_analysis.md for rationale and incidents):

- UL101 jit-missing-donation: ``jax.jit`` on a train-step-shaped
  function without ``donate_argnums``/``donate_argnames``.
- UL102 numpy-in-jit: host numpy calls inside a jitted function (each
  one constant-folds at trace time at best, breaks tracing at worst).
- UL103 unseeded-dataset-rng: dataset code drawing from global RNG
  state outside the per-(seed, epoch, index) ``numpy_seed`` idiom —
  epoch resume and multi-worker determinism silently break.
- UL104 blocking-fetch: ``.block_until_ready()`` / ``.item()`` in
  library code outside the stats slow path (each is a host sync that
  serializes dispatch).
- UL105 dropout-dead-rate: a literal dropout rate that quantizes to
  exact identity or full drop at the uint8 keep resolution of
  ``ops/dropout.py`` (rates within 1/512 of 0 or 1).
- UL106 where-nan-grad: ``jnp.where(cond, f(x), g(x))`` where a branch
  applies a domain-restricted function (sqrt/log/arcsin/…, or a
  division guarded by the condition itself) — ``where`` evaluates BOTH
  branches, and autodiff propagates the untaken branch's NaN/Inf
  cotangent through the select.  The fix is clamping the argument
  (``jnp.sqrt(jnp.maximum(x, eps))``), which the rule recognizes.
- UL107 swallowed-io-error: a bare ``except:`` — or an ``except
  Exception:``/``except BaseException:`` whose body is only
  ``pass``/``continue`` — around IO calls (open/os/shutil/pickle/…).
  In checkpoint paths a swallowed write error means the run believes a
  save succeeded that never hit the disk, and the failure surfaces
  days later as a missing resume point.  Narrow handlers
  (``except FileNotFoundError:``) and handlers that log or re-raise
  are fine.
- UL108 sync-in-step-loop: a blocking host sync — ``jax.device_get``,
  ``.block_until_ready()``, or a synchronous checkpoint write
  (``save_checkpoint``/``write_checkpoint``/``atomic_save``) — inside
  a STEP LOOP (any ``for``/``while`` whose body calls
  ``train_step``).  Each one stalls dispatch every iteration; the
  async APIs exist precisely for these: the ``--stats-lag`` pipeline
  defers the stats fetch, ``stage_batches`` double-buffers input, and
  the background checkpoint writer streams saves off the step path.
- UL109 unbounded-queue-growth: ``.append``/``.appendleft``/
  ``.insert`` onto a collection inside a SERVE LOOP (any
  ``for``/``while`` whose body drives request scheduling —
  ``admit``/``prepare_decode``/``serve_step``/``poll_requests``)
  with no bound check (a ``len(...)`` comparison on the same
  collection) or shed path (``pop``/``popleft``/``clear``/``remove``
  or a ``*shed*`` call) anywhere in the loop.  Under sustained
  overload an unbounded queue grows until every queued request has
  blown its deadline and the host OOMs — the serve tier's bounded
  ``max_waiting`` + deterministic shedding exists precisely so
  backpressure is visible to callers instead.

- UL111 blocking-in-router-loop: a blocking host call inside a ROUTER
  DISPATCH LOOP (any ``for``/``while`` whose body drives replica
  fan-out — ``serve_step``/``route``/``dispatch``/``poll_replicas``)
  — the fleet-tier analog of UL108/UL109.  Flagged: ``sleep`` (the
  loop's pacing belongs to the virtual-time replay or the caller, not
  a stall every fan-out cycle), a zero-arg ``.join()`` (a thread or
  process join parks the router behind ONE replica while every other
  replica's queue ages toward its deadline; ``str.join(iterable)``
  takes an argument and is not matched), and a ``.generate(...)``
  method call (the engine's batch-blocking run-to-completion API — one
  replica's whole batch would serialize the fleet; routers must
  interleave ``submit()``/``serve_step()``/``collect_finished()``).

- UL112 sync-on-current-step: a blocking host sync — ``jax.device_get``,
  ``.item()``, or ``.block_until_ready()`` — applied to a value bound
  from the ``train_step`` call of the SAME loop iteration.  This is the
  pattern that silently collapses a pipelined train loop
  (``--pipeline-depth K >= 2``): the current step's outputs cannot be
  ready yet, so the sync stalls the host a full device step and the
  in-flight ring never fills.  The lag-K drain path is the sanctioned
  read — ``train_step``'s return value is already host-side lagged
  stats, and ``flush_stats()`` at real boundaries gives exact counts;
  syncing on THOSE does not fire (the rule tracks data flow from the
  step call, not the loop alone — that coarser check is UL108), and a
  sync placed textually BEFORE the binding reads the previous
  iteration's already-on-host value (the manual lag-1 idiom) and is
  silent too.

- UL113 unguarded-replica-step: a bare ``<replica>.serve_step()`` call
  inside a FLEET/ROUTER fan-out loop with neither typed fault handling
  (an enclosing ``try`` with a handler inside the loop) nor health
  recording (a ``record_*``/``observe*`` call, or anything reached
  through a ``health`` receiver) anywhere in the loop.  A fan-out loop
  is one that steps replicas it does not own: the stepped receiver is
  subscripted out of a collection (``engines[rid].serve_step()``), the
  loop iterates something named like a replica set
  (replica/engine/fleet), or two distinct replica receivers are
  stepped.  An engine driving ITSELF (``self.serve_step()``) or a
  harness driving one local engine is not a fleet loop and never
  fires.  The hazard: the engine only lets an exception escape
  ``serve_step`` when it cannot continue — unguarded, that one
  replica's crash re-raises out of the fan-out loop and takes every
  OTHER replica's traffic with it, and a wedged replica (claiming work,
  retiring nothing) is never noticed at all.  Route replica steps
  through a guarded helper that records typed faults and progress into
  the health model so a dead replica is evicted and its sessions fail
  over (``fleet/router.py`` ``FleetRouter._step_replica``).

- UL114 replicated-optim-state: in a module that plumbs the trainer's
  ``zero1`` flag, optimizer state created OUTSIDE a sharding-constraint
  context — a bare ``<optimizer>.init(params)`` call, or a full-shape
  moment allocation (``jnp.zeros_like(param)`` / ``jnp.zeros(p.shape)``)
  inside a function named ``init``.  Under ``--zero1`` the moments must
  be *created* data-axis-sharded (``jax.jit(opt.init,
  out_shardings=...)``, the ``Trainer._init_opt_state`` path, or a
  ``with_sharding_constraint``/``device_put`` wrapper): an unconstrained
  init materializes the full replicated fp32 moment tree on every
  replica first, which is precisely the peak allocation ZeRO-1 exists
  to avoid.  Modules that never see the flag are exempt — without
  ZeRO-1 in play, replicated moments are just the normal dp layout.

- UL115 unjoined-daemon-thread: a ``threading.Thread(...,
  daemon=True)`` spawn with no reachable shutdown path — neither a
  ``.join(...)`` on the receiver the thread was bound to anywhere in
  the module, nor a ``stop``/``close``/``drain``/``shutdown``/
  ``terminate``/``join`` method on the class that owns the spawn.  A
  chained ``threading.Thread(..., daemon=True).start()`` always fires:
  the reference is dropped on the spot, so no shutdown path can ever
  reach it.  Daemon threads die SILENTLY at interpreter exit — an
  async checkpoint writer's queued saves or a prefetch pump's
  in-flight batches vanish with no error; the sanctioned worker shape
  (``resilience/async_writer.py``, ``data/iterators.py`` pump,
  ``resilience/watchdog.py``) always owns a stop flag or a join on the
  shutdown path.  Non-daemon threads are exempt: they block exit
  visibly instead of losing work.

- UL110 unguarded-dataset-io: raw IO (``open``/``pickle.loads``/
  ``np.fromfile``/``np.memmap``/an LMDB ``get``) inside a dataset
  ``__getitem__``/``__iter__`` body with no enclosing ``try`` whose
  handler re-raises a typed error — or a broad ``except`` in such a
  body that never re-raises.  A torn record surfacing as a raw
  ``UnpicklingError`` (or worse, swallowed into a garbage sample)
  bypasses the input-pipeline fault ladder: the guarded fetch layer
  (``data/resilient.py``) keys its retry/skip/abort decisions on
  ``DataIntegrityError``, so every dataset fetch path must translate
  IO failures into it (the way ``indexed_dataset``/``lmdb_dataset``
  do).

- UL116 unverified-checkpoint-read: a raw ``open(...)`` or
  ``pickle.load``/``loads`` whose argument names a checkpoint or
  manifest (``checkpoint``/``ckpt``/``manifest`` name fragments, or a
  ``.pt`` literal) in deploy/serve/fleet code, outside both the
  sanctioned ``read_verified(...)`` wrapper and any ``try`` whose
  handler re-raises a typed error.  The deploy pipeline's whole
  contract is that a torn or tampered checkpoint can never reach a
  ServeEngine: ``read_verified`` re-hashes the bytes against the
  ``.sum`` sidecar and raises ``CheckpointIntegrityError``, and every
  manifest/params load path (``deploy/publish.py``,
  ``deploy/loader.py``) goes through it.  A bare read bypasses the
  integrity ladder exactly where it matters most — weights about to be
  hot-swapped into live traffic.  Train-side code is exempt (its reads
  are guarded by the checkpoint_utils load path itself).

- UL117 wall-clock-in-decision-path: a wall-clock read
  (``time.time``/``perf_counter``/``monotonic``/``datetime.now``/…)
  inside a production DECISION module — scheduler/router/health/
  rollout dispatch, and everything under ``fleet/`` and
  ``deploy/`` — outside the injectable-clock idiom those tiers
  standardize on (``clock=None`` parameter, ``self._clock = clock or
  time.monotonic``).  A decision keyed on the real clock cannot be
  replayed: the chaos/failover oracles, the virtual-time fleet traces,
  and the Pass-5 determinism harness all depend on every admission
  deadline, health verdict, and rollout gate being a pure function of
  injected state.  Recognized-clean shapes (never flagged): an elapsed
  MEASUREMENT — the read sits under a ``-`` (``dt = perf_counter() -
  t0``, ``stats[...] += perf_counter() - t0``) — and a timing ORIGIN
  stamp — ``t0 = perf_counter()``, any single target matching
  ``t``/``t<N>``/``*start*``/``*begin*``/``*origin*``.  Name
  references (``clock or time.perf_counter``) are defaults for the
  injectable idiom itself and are not calls, so they never fire.

- UL118 unbounded-replica-growth: a replica-factory boot — a
  ``*factory*(...)`` call — inside a ``for``/``while`` loop whose
  result GROWS the fleet (``.append``/``.add``/``.insert`` onto a
  collection, or a subscript store whose key is not the loop variable,
  or any store in a ``while`` loop) with no scale gate anywhere in the
  loop: no max-replicas bound (a comparison involving a ``*max*``
  name or a ``len()`` call), no ``*cooldown*`` gate, and no breaker
  ``.ready()`` check.
  This is UL109's fleet-tier sibling, but each unbounded "queue entry"
  here is a whole ServeEngine — params + KV pool + compiled step — so
  a retry/pressure loop that boots replicas without a bound turns one
  overload or one flapping replica into host OOM and a boot storm
  against the checkpoint store.  The sanctioned path is the
  autoscaler envelope: ``serving + booting < max_replicas``, a
  per-direction cooldown, and a bounded boot budget
  (``fleet/autoscaler.py``), with each boot routed through the
  breaker-gated canary (``FleetRouter.scale_up``).  The rolling
  restart's REPLACEMENT shape — ``engines[rid] = factory(rid)`` keyed
  by the loop variable — swaps slots without growing the fleet and
  never fires.

Suppression: append ``# unicore-lint: disable=UL104`` (comma-separated
ids, or ``all``) to the flagged line.
"""

import ast
import os
import re

from unicore_tpu.analysis.findings import Finding

_SUPPRESS_RE = re.compile(r"#\s*unicore-lint:\s*disable=([A-Za-z0-9_,\s]+)")

# UL102: numpy attributes that are metadata-only (safe inside jit)
_NUMPY_META_OK = {"prod", "dtype", "ndim", "issubdtype", "result_type",
                  "promote_types", "broadcast_shapes"}

# UL103: global-state numpy RNG draws
_NP_GLOBAL_RNG = {
    "rand", "randn", "randint", "random", "choice", "permutation",
    "shuffle", "uniform", "normal", "random_sample", "beta", "binomial",
    "poisson", "multinomial", "bytes", "sample", "ranf",
}
# UL103: the numpy_seed idiom's own plumbing (allowed anywhere)
_NP_RNG_PLUMBING = {"get_state", "set_state", "seed"}
# UL103: stdlib random draws (numpy_seed does NOT scope these)
_PY_RANDOM_FNS = {
    "random", "randint", "randrange", "choice", "choices", "shuffle",
    "sample", "uniform", "gauss", "normalvariate", "betavariate",
}
# UL103: explicitly-seeded generator constructors (need a seed argument)
_RNG_CONSTRUCTORS = {"RandomState", "default_rng", "Generator",
                     "SeedSequence"}

# UL104: allowed path fragments — the stats slow path (meter formatting)
_BLOCKING_OK_PATHS = ("logging" + os.sep,)

# UL106: unary fns whose value or gradient is non-finite outside their
# domain (sqrt'(0) = inf; log(0) = -inf; …)
_WHERE_RISKY_UNARY = {
    "sqrt", "rsqrt", "log", "log2", "log10", "log1p",
    "arcsin", "arccos", "arctanh", "arccosh",
    "asin", "acos", "atanh", "acosh", "reciprocal",
}
# UL106: wrapping the risky argument in one of these is the sanctioned
# fix — the whole subtree is considered clamped
_WHERE_CLAMP_FNS = {
    "maximum", "minimum", "clip", "clamp", "abs", "where", "nan_to_num",
    "exp", "softplus", "sigmoid",
}

# UL107: module roots whose calls mark a try block as an IO path
_IO_MODULE_ROOTS = {"os", "shutil", "pickle", "glob", "tempfile", "io",
                    "json", "gzip", "lzma", "lmdb"}
# UL107: method tails that mark a call as IO regardless of receiver
_IO_METHOD_TAILS = {
    "read", "readline", "readlines", "write", "writelines", "flush",
    "close", "seek", "unlink", "rename", "replace", "remove", "rmdir",
    "mkdir", "makedirs", "copyfile", "copy", "copytree", "move", "dump",
    "dumps", "load", "loads",
}
# UL107: broad handler types whose swallow is the hazard (narrow types
# like FileNotFoundError/ImportError are deliberate control flow)
_BROAD_EXC_NAMES = {"Exception", "BaseException"}

# UL108: a loop is a STEP LOOP iff its body dispatches train steps
_STEP_LOOP_MARKERS = {"train_step"}
# UL108: per-iteration host syncs (device_get also as a bare name from
# ``from jax import device_get``); block_until_ready is matched as a
# method tail like UL104 does
_UL108_SYNC_TAILS = {"device_get", "block_until_ready"}
# UL108: synchronous checkpoint writes — the background writer
# (CheckpointManager --async-save / AsyncCheckpointWriter) exists so
# the step path only ever pays the device->host capture
_UL108_SAVE_TAILS = {"save_checkpoint", "write_checkpoint", "atomic_save"}

# UL110: call tails that read raw record bytes inside a dataset fetch
# (open is matched separately; lmdb gets via the begin()/txn heuristic)
_UL110_IO_TAILS = {"loads", "load", "fromfile", "memmap", "frombuffer"}

# UL109: a loop is a SERVE LOOP iff its body drives request scheduling
_SERVE_LOOP_MARKERS = {"admit", "prepare_decode", "serve_step",
                       "poll_requests"}
# UL109: growth calls that need a visible bound or shed path
_UL109_GROW_TAILS = {"append", "appendleft", "insert"}
# UL109: calls on the SAME collection that count as a drain/shed path
_UL109_DRAIN_TAILS = {"pop", "popleft", "popitem", "clear", "remove"}

# UL111: a loop is a ROUTER DISPATCH LOOP iff its body drives replica
# fan-out (same subtree semantics as UL109: an outer while that fans
# out through a nested for still blocks once per dispatch cycle)
_ROUTER_LOOP_MARKERS = {"serve_step", "route", "dispatch",
                        "poll_replicas"}

# UL112: method-tail syncs on a value bound from the step call this
# iteration (device_get is matched by chain, it takes the value as an
# argument instead)
_UL112_METHOD_TAILS = {"item", "block_until_ready"}

# UL113: iterable-name fragments that mark a loop as replica fan-out
_UL113_FLEET_NAME_FRAGS = ("replica", "engine", "fleet")
# UL113: call-tail prefixes that count as health recording (plus any
# chain passing through a "health" receiver)
_UL113_HEALTH_PREFIXES = ("record_", "observe")

# UL114: full-shape moment allocations inside an optimizer ``init()``
_UL114_ALLOC_TAILS = {"zeros_like", "ones_like", "full_like", "empty_like"}
_UL114_ALLOC_SHAPE_TAILS = {"zeros", "ones", "full", "empty"}
# UL114: receiver names that mark a ``.init(...)`` call as optimizer-
# state creation
_UL114_OPTIM_RECEIVERS = ("optim", "opt")
# UL114: wrapping the creation in one of these IS the sanctioned
# sharding-constraint context (jax.jit(init, out_shardings=...) never
# produces a bare ``.init(...)`` Call node, so it is silent by shape)
_UL114_SHARDED_WRAPPERS = {"with_sharding_constraint", "device_put",
                           "make_array_from_callback",
                           "make_array_from_single_device_arrays"}


# UL115: a method with one of these names on the spawning class IS the
# shutdown path (the watchdog's close() stops its worker with a flag +
# wake event, never a join — the NAME marks the reachable path, the
# flag protocol inside is the worker's business)
_UL115_SHUTDOWN_METHODS = {"stop", "close", "drain", "shutdown",
                           "terminate", "join"}


# UL116: argument-name fragments that mark a read as checkpoint bytes
_UL116_NAME_HINTS = ("checkpoint", "ckpt", "manifest")


# UL117 (also imported by analysis/determinism_audit.py for UL403 —
# the rules share one definition of "a wall-clock read"): time-module
# attributes that read the real clock
_UL117_TIME_FNS = {
    "time", "perf_counter", "monotonic", "process_time",
    "time_ns", "perf_counter_ns", "monotonic_ns", "process_time_ns",
}
# UL117: datetime constructors that read the real clock
_UL117_DT_FNS = {"now", "utcnow", "today"}
# UL117: an Assign target matching this is a timing ORIGIN stamp
# (``t0 = perf_counter()``); the paired elapsed read is recognized by
# its BinOp-Sub shape instead
_UL117_TIMING_NAME_RE = re.compile(
    r"(^t\d*$|start|begin|origin)", re.IGNORECASE
)
# UL117: basename fragments that mark a module as decision dispatch
# (fleet/ and deploy/ are in scope wholesale — see _is_decision_file)
_UL117_DECISION_FRAGS = ("scheduler", "engine", "router", "rollout",
                         "health", "autoscaler")

# UL118: method tails that grow a collection with the factory's result
_UL118_GROW_TAILS = {"append", "appendleft", "add", "insert"}


def _attr_chain(node):
    """'jax.jit' for Attribute(Name('jax'), 'jit'); None when dynamic."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class _ModuleLint(ast.NodeVisitor):
    def __init__(self, path, source, *, dataset_file, deploy_file, lines,
                 decision_file=False):
        self.path = path
        self.dataset_file = dataset_file
        self.deploy_file = deploy_file
        self.decision_file = decision_file
        self.lines = lines
        self.findings = []
        # alias tracking: import numpy as np / import random as rnd
        self.np_aliases = {"numpy"}
        self.jnp_aliases = {"jnp"}
        self.random_aliases = set()
        self.jax_aliases = {"jax"}
        self.threading_aliases = {"threading"}
        self.thread_ctors = set()   # bare names: from threading import Thread
        self.time_aliases = {"time"}
        self.datetime_aliases = {"datetime", "date"}
        self.clock_bare_names = set()  # from time import perf_counter
        self.jitted_names = set()
        self._with_seed_depth = 0
        self._step_loop_depth = 0
        self._serve_loop_depth = 0
        self._router_loop_depth = 0
        self._ul113_depth = 0
        self._ul118_depth = 0
        self._tree = ast.parse(source, filename=path)
        self._collect_imports_and_jit_targets()
        self._collect_zero1_plumbing()
        self._collect_ul117_clean()

    # -- setup ---------------------------------------------------------

    def _collect_imports_and_jit_targets(self):
        for node in ast.walk(self._tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    name = alias.asname or alias.name
                    if alias.name == "numpy":
                        self.np_aliases.add(name)
                    elif alias.name == "jax.numpy":
                        self.jnp_aliases.add(name)
                    elif alias.name == "random":
                        self.random_aliases.add(name)
                    elif alias.name == "jax":
                        self.jax_aliases.add(name)
                    elif alias.name == "threading":
                        self.threading_aliases.add(name)
                    elif alias.name == "time":
                        self.time_aliases.add(name)
                    elif alias.name == "datetime":
                        self.datetime_aliases.add(name)
            elif isinstance(node, ast.ImportFrom):
                if node.module == "jax":
                    for alias in node.names:
                        if alias.name == "numpy":
                            self.jnp_aliases.add(
                                alias.asname or alias.name
                            )
                elif node.module == "threading":
                    for alias in node.names:
                        if alias.name == "Thread":
                            self.thread_ctors.add(
                                alias.asname or alias.name
                            )
                elif node.module == "time":
                    for alias in node.names:
                        if alias.name in _UL117_TIME_FNS:
                            self.clock_bare_names.add(
                                alias.asname or alias.name
                            )
                elif node.module == "datetime":
                    for alias in node.names:
                        if alias.name in ("datetime", "date"):
                            self.datetime_aliases.add(
                                alias.asname or alias.name
                            )
            elif isinstance(node, ast.Call) and self._is_jax_jit(node.func):
                if node.args and isinstance(node.args[0], ast.Name):
                    self.jitted_names.add(node.args[0].id)

    def _is_jax_jit(self, func):
        chain = _attr_chain(func)
        if chain is None:
            return False
        head, _, tail = chain.rpartition(".")
        return tail == "jit" and (head in self.jax_aliases or head == "")

    def _is_wall_clock(self, func):
        """``func`` (a Call's func node) reads the real clock: a
        ``time.*`` attribute, a ``datetime``/``date`` constructor, or a
        bare name from ``from time import perf_counter``."""
        chain = _attr_chain(func)
        if chain is None:
            return False
        parts = chain.split(".")
        tail = parts[-1]
        if len(parts) == 1:
            return tail in self.clock_bare_names
        if tail in _UL117_TIME_FNS and parts[-2] in self.time_aliases:
            return True
        return (tail in _UL117_DT_FNS
                and any(p in self.datetime_aliases for p in parts[:-1]))

    def _collect_ul117_clean(self):
        """Pre-pass marking wall-clock Call nodes in a recognized-clean
        shape: under a ``-`` anywhere up to the enclosing statement (an
        elapsed measurement, including ``+= perf_counter() - t0`` and
        ``(perf_counter() - t0) / iters``), or the whole value of an
        Assign to a timing-named target (``t0 = perf_counter()``)."""
        self._ul117_clean = set()
        if not self.decision_file:
            return
        parents = {}
        for parent in ast.walk(self._tree):
            for child in ast.iter_child_nodes(parent):
                parents[id(child)] = parent
        for node in ast.walk(self._tree):
            if not (isinstance(node, ast.Call)
                    and self._is_wall_clock(node.func)):
                continue
            cur = node
            while True:
                p = parents.get(id(cur))
                if p is None or isinstance(p, ast.stmt):
                    if (isinstance(p, ast.Assign) and p.value is node
                            and len(p.targets) == 1):
                        t = p.targets[0]
                        tname = (t.id if isinstance(t, ast.Name)
                                 else t.attr if isinstance(t, ast.Attribute)
                                 else "")
                        if _UL117_TIMING_NAME_RE.search(tname):
                            self._ul117_clean.add(id(node))
                    break
                if isinstance(p, ast.BinOp) and isinstance(p.op, ast.Sub):
                    self._ul117_clean.add(id(node))
                    break
                cur = p

    # -- emit ----------------------------------------------------------

    def _suppressed(self, rule, lineno):
        if 1 <= lineno <= len(self.lines):
            m = _SUPPRESS_RE.search(self.lines[lineno - 1])
            if m:
                ids = {s.strip() for s in m.group(1).split(",")}
                return rule in ids or "all" in ids
        return False

    def emit(self, rule, name, severity, node, message):
        if self._suppressed(rule, node.lineno):
            return
        self.findings.append(Finding(
            rule, name, severity, f"{self.path}:{node.lineno}", message
        ))

    # -- UL101 / UL102 -------------------------------------------------

    def _emit_missing_donation(self, node, target_name):
        self.emit(
            "UL101", "jit-missing-donation", "error", node,
            f"jax.jit({target_name}) without donate_argnums — a "
            f"train step that does not donate its state keeps two "
            f"copies of params+optimizer state in HBM",
        )

    def _check_jit_call(self, node):
        kwargs = {kw.arg for kw in node.keywords}
        target = node.args[0] if node.args else None
        target_name = None
        if isinstance(target, ast.Name):
            target_name = target.id
        elif isinstance(target, ast.Attribute):
            target_name = target.attr
        hot = target_name is not None and "train" in target_name.lower()
        if hot and not ({"donate_argnums", "donate_argnames"} & kwargs):
            self._emit_missing_donation(node, target_name)

    def _check_jit_decorators(self, fn):
        """UL101 for the decorator spellings: ``@jax.jit`` and
        ``@partial(jax.jit, ...)`` (the call form is handled by
        :meth:`_check_jit_call`)."""
        if "train" not in fn.name.lower():
            return
        for dec in fn.decorator_list:
            if self._is_jax_jit(dec):
                # bare @jax.jit carries no kwargs at all
                self._emit_missing_donation(dec, fn.name)
                continue
            if not isinstance(dec, ast.Call):
                continue
            kwargs = {kw.arg for kw in dec.keywords}
            donated = {"donate_argnums", "donate_argnames"} & kwargs
            chain = _attr_chain(dec.func)
            is_partial_jit = (
                chain and chain.split(".")[-1] == "partial"
                and dec.args and self._is_jax_jit(dec.args[0])
            )
            if (self._is_jax_jit(dec.func) or is_partial_jit) and not donated:
                self._emit_missing_donation(dec, fn.name)

    def _check_numpy_in_jit(self, fn):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            chain = _attr_chain(node.func)
            if chain is None:
                continue
            head, _, tail = chain.rpartition(".")
            root = head.split(".")[0] if head else ""
            if root in self.np_aliases and tail not in _NUMPY_META_OK:
                self.emit(
                    "UL102", "numpy-in-jit", "error", node,
                    f"host numpy call '{chain}' inside jitted function "
                    f"'{fn.name}' — it runs at trace time (silent "
                    f"constant folding) or fails on tracers; use jnp",
                )

    def _fn_is_jitted(self, fn):
        if fn.name in self.jitted_names:
            return True
        for dec in fn.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            if self._is_jax_jit(target):
                return True
            # @partial(jax.jit, ...) / @functools.partial(jax.jit, ...)
            if isinstance(dec, ast.Call):
                chain = _attr_chain(dec.func)
                if chain and chain.split(".")[-1] == "partial" and dec.args:
                    if self._is_jax_jit(dec.args[0]):
                        return True
        return False

    # -- UL103 ---------------------------------------------------------

    def _is_numpy_seed_with(self, node):
        for item in node.items:
            expr = item.context_expr
            if isinstance(expr, ast.Call):
                chain = _attr_chain(expr.func)
                if chain and chain.split(".")[-1] == "numpy_seed":
                    return True
        return False

    def _check_dataset_rng(self, node):
        chain = _attr_chain(node.func)
        if chain is None:
            return
        parts = chain.split(".")
        head, tail = parts[0], parts[-1]
        # numpy global-state draws: np.random.<draw>(...)
        if (head in self.np_aliases and len(parts) >= 3
                and parts[-2] == "random"):
            if tail in _NP_RNG_PLUMBING:
                return
            if tail in _RNG_CONSTRUCTORS:
                if not node.args and not node.keywords:
                    self.emit(
                        "UL103", "unseeded-dataset-rng", "error", node,
                        f"'{chain}()' without a seed in dataset code — "
                        f"samples become irreproducible across "
                        f"epochs/workers; derive the seed from "
                        f"(seed, epoch, index)",
                    )
                return
            if tail in _NP_GLOBAL_RNG and self._with_seed_depth == 0:
                self.emit(
                    "UL103", "unseeded-dataset-rng", "error", node,
                    f"'{chain}' draws from numpy's GLOBAL rng outside a "
                    f"'with data_utils.numpy_seed(seed, epoch, index)' "
                    f"block — bypasses the per-(seed, epoch, index) "
                    f"derivation idiom (resume/worker determinism breaks)",
                )
            return
        # stdlib random: numpy_seed does not scope it at all
        if head in self.random_aliases and tail in _PY_RANDOM_FNS:
            self.emit(
                "UL103", "unseeded-dataset-rng", "error", node,
                f"stdlib '{chain}' in dataset code — 'numpy_seed' does "
                f"not seed the stdlib rng; use the numpy generator "
                f"derived from (seed, epoch, index)",
            )

    # -- UL104 / UL105 -------------------------------------------------

    def _check_blocking(self, node):
        if any(frag in self.path for frag in _BLOCKING_OK_PATHS):
            return
        if not isinstance(node.func, ast.Attribute):
            return
        attr = node.func.attr
        if attr == "block_until_ready":
            self.emit(
                "UL104", "blocking-fetch", "error", node,
                "'.block_until_ready()' in library code — a host sync "
                "that serializes dispatch; only bench/test harnesses "
                "should block (use the stats slow path for logging)",
            )
        elif attr == "item" and not node.args:
            self.emit(
                "UL104", "blocking-fetch", "warning", node,
                "'.item()' in library code — device->host sync per call; "
                "batch fetches through jax.device_get on the stats slow "
                "path instead",
            )

    def _check_dropout_rate(self, node):
        chain = _attr_chain(node.func)
        if chain is None or chain.split(".")[-1] != "dropout":
            return
        candidates = []
        if len(node.args) >= 2:
            candidates.append(node.args[1])
        candidates.extend(
            kw.value for kw in node.keywords
            if kw.arg in ("rate", "dropout_prob", "p")
        )
        for arg in candidates:
            if not (isinstance(arg, ast.Constant)
                    and isinstance(arg.value, (int, float))):
                continue
            r = float(arg.value)
            # EXACTLY the op's quantization (ops/dropout.py): a dead
            # band re-derivation would disagree at the r = 1/512
            # boundary, where round() already banker's-rounds q to 256
            q = int(round((1.0 - r) * 256.0))
            dead = (q >= 256 and r > 0.0) or (q <= 0 and r < 1.0)
            if dead:
                self.emit(
                    "UL105", "dropout-dead-rate", "error", node,
                    f"dropout rate {r!r} quantizes to "
                    f"{'identity' if r < 0.5 else 'full drop'} at the "
                    f"uint8 q/256 keep resolution — the requested rate "
                    f"is silently not applied (ops/dropout.py)",
                )

    # -- UL106 ---------------------------------------------------------

    def _module_aliases(self):
        """Attribute roots (jnp/np/jax/...) — never 'data' names; the
        name-overlap heuristic must not count `jnp` appearing in both
        the condition and a denominator as a shared value."""
        return self.np_aliases | self.jnp_aliases | self.jax_aliases

    def _value_names(self, node):
        """Dotted names of VALUE references in an expression: ``x``,
        ``self.temperature`` — as full chains, so ``self.eps`` in a
        condition and ``self.temperature`` in a denominator do not
        collide on the bare ``self`` root.  Chains rooted at a module
        alias (``jnp.sum``) are function references, not data, and are
        excluded."""
        aliases = self._module_aliases()
        out = set()
        skip = set()
        for sub in ast.walk(node):
            if id(sub) in skip:
                continue
            if isinstance(sub, ast.Attribute):
                chain = _attr_chain(sub)
                if chain is None:
                    continue
                # consume the whole chain: its inner Name/Attribute
                # nodes must not ALSO register as bare names
                for inner in ast.walk(sub):
                    if inner is not sub:
                        skip.add(id(inner))
                if chain.split(".")[0] not in aliases:
                    out.add(chain)
            elif isinstance(sub, ast.Name) and sub.id not in aliases:
                out.add(sub.id)
        return out

    @staticmethod
    def _contains_clamp(node):
        """True when the expression passes through a clamp call anywhere
        (``sqrt(maximum(x, eps))`` — the argument IS the clamp;
        ``sqrt(maximum(x, eps) + y)`` still counts)."""
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                chain = _attr_chain(sub.func)
                if chain and chain.split(".")[-1] in _WHERE_CLAMP_FNS:
                    return True
        return False

    def _find_risky(self, node, cond_names):
        """First hazardous subexpression in a where() branch: a
        domain-restricted unary call on a non-constant argument, a
        ``x ** <fractional/negative>`` power, or a division whose
        denominator shares a name with the condition (the
        guard-the-denominator-with-where signature).  A clamp call
        (maximum/clip/abs/…) sanctions its whole subtree."""
        if isinstance(node, ast.Call):
            chain = _attr_chain(node.func)
            tail = chain.split(".")[-1] if chain else None
            if tail in _WHERE_CLAMP_FNS:
                return None
            if (tail in _WHERE_RISKY_UNARY and node.args
                    and not isinstance(node.args[0], ast.Constant)
                    and not self._contains_clamp(node.args[0])):
                return f"'{tail}'"
        elif isinstance(node, ast.BinOp):
            if isinstance(node.op, ast.Div):
                den = node.right
                if (not isinstance(den, ast.Constant)
                        and not self._contains_clamp(den)
                        and self._value_names(den) & cond_names):
                    return "a division whose denominator the condition " \
                           "guards"
            elif isinstance(node.op, ast.Pow):
                exp = node.right
                if (isinstance(exp, ast.Constant)
                        and isinstance(exp.value, (int, float))
                        and (exp.value < 0
                             or float(exp.value) != int(exp.value))
                        and not isinstance(node.left, ast.Constant)
                        and not self._contains_clamp(node.left)):
                    return f"'** {exp.value}'"
        for child in ast.iter_child_nodes(node):
            got = self._find_risky(child, cond_names)
            if got:
                return got
        return None

    def _check_where_nan(self, node):
        chain = _attr_chain(node.func)
        if chain is None or chain.split(".")[-1] != "where":
            return
        root = chain.split(".")[0]
        if root not in (self.np_aliases | self.jnp_aliases
                        | self.jax_aliases):
            return
        if len(node.args) < 3:
            return
        cond_names = self._value_names(node.args[0])
        for branch in node.args[1:3]:
            risky = self._find_risky(branch, cond_names)
            if risky:
                self.emit(
                    "UL106", "where-nan-grad", "warning", node,
                    f"where() branch applies {risky}, which is "
                    f"non-finite (in value or gradient) outside its "
                    f"domain — where evaluates BOTH branches, and the "
                    f"untaken branch's NaN/Inf cotangent propagates "
                    f"through the select; clamp the argument instead "
                    f"(e.g. sqrt(maximum(x, eps)))",
                )
                return

    # -- UL108 / UL109 -------------------------------------------------

    def _loop_body_calls(self, loop, markers, skip_nested_loops=True):
        """A for/while whose body calls one of ``markers``.  Nested
        function defs are always excluded (a closure defined in a loop
        does not run per iteration).  With ``skip_nested_loops`` (the
        UL108 semantics) NESTED loops are too: in ``for epoch: (for
        batch: train_step(batch)); device_get(...)`` only the inner
        loop is the step loop — the epoch-level sync runs once per
        epoch, which is exactly the sanctioned
        fetch-at-real-boundaries pattern, not a per-step stall.  UL109
        passes False: an outer ``while True`` that appends to a queue
        and drives ``admit()`` from a nested drain loop still grows
        the queue once per serve cycle, so the OUTER loop is the serve
        loop and its whole subtree is the growth-audit scope."""
        stack = list(loop.body) + list(getattr(loop, "orelse", []) or [])
        while stack:
            sub = stack.pop()
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                continue
            if skip_nested_loops and isinstance(
                    sub, (ast.For, ast.AsyncFor, ast.While)):
                continue
            if isinstance(sub, ast.Call):
                chain = _attr_chain(sub.func)
                if chain and chain.split(".")[-1] in markers:
                    return True
            stack.extend(ast.iter_child_nodes(sub))
        return False

    def _loop_is_step_loop(self, loop):
        return self._loop_body_calls(loop, _STEP_LOOP_MARKERS)

    def _loop_is_serve_loop(self, loop):
        return self._loop_body_calls(loop, _SERVE_LOOP_MARKERS,
                                     skip_nested_loops=False)

    def _loop_is_router_loop(self, loop):
        return self._loop_body_calls(loop, _ROUTER_LOOP_MARKERS,
                                     skip_nested_loops=False)

    def _check_unbounded_growth(self, loop):
        """UL109 over one outermost serve loop: every
        ``.append``/``.appendleft``/``.insert`` onto a named collection
        must be matched — anywhere in the same loop — by a bound check
        (``len(<collection>)``, e.g. against a ``max_waiting``) or a
        drain/shed path (``pop``/``popleft``/``clear``/``remove`` on
        it, or any ``*shed*`` call).  Closures defined in the loop do
        not run per iteration and are skipped, mirroring UL108."""
        grows = []
        sanctioned = set()
        shed_anywhere = False
        stack = list(ast.iter_child_nodes(loop))
        while stack:
            sub = stack.pop()
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                continue
            if isinstance(sub, ast.Call):
                chain = _attr_chain(sub.func)
                if chain is not None:
                    parts = chain.split(".")
                    tail, recv = parts[-1], ".".join(parts[:-1])
                    if isinstance(sub.func, ast.Attribute) and recv:
                        if tail in _UL109_GROW_TAILS:
                            grows.append((sub, recv))
                        elif tail in _UL109_DRAIN_TAILS:
                            sanctioned.add(recv)
                    if "shed" in tail.lower():
                        shed_anywhere = True
                if (isinstance(sub.func, ast.Name)
                        and sub.func.id == "len" and sub.args):
                    arg = _attr_chain(sub.args[0])
                    if arg:
                        sanctioned.add(arg)
            stack.extend(ast.iter_child_nodes(sub))
        for node, recv in grows:
            if recv in sanctioned or shed_anywhere:
                continue
            self.emit(
                "UL109", "unbounded-queue-growth", "error", node,
                f"'{recv}' grows inside a serve/scheduler loop with no "
                f"bound check or shed path in sight — under sustained "
                f"overload it grows until every queued request has "
                f"blown its deadline and the host OOMs; bound it "
                f"(len({recv}) vs a max) and shed deterministically "
                f"like the serve tier's max_waiting",
            )

    def _check_sync_in_step_loop(self, node):
        if self._step_loop_depth == 0:
            return
        chain = _attr_chain(node.func)
        if chain is None:
            return
        tail = chain.split(".")[-1]
        if tail in _UL108_SYNC_TAILS:
            self.emit(
                "UL108", "sync-in-step-loop", "error", node,
                f"'{chain}' inside the step loop — a per-iteration "
                f"host sync that stalls dispatch; fetch stats through "
                f"the lagged --stats-lag pipeline (flush_stats at real "
                f"boundaries only) instead of blocking every step",
            )
        elif tail in _UL108_SAVE_TAILS:
            self.emit(
                "UL108", "sync-in-step-loop", "error", node,
                f"synchronous checkpoint write '{chain}' inside the "
                f"step loop — the step path should pay only the "
                f"device->host capture; route saves through "
                f"CheckpointManager's background writer (--async-save) "
                f"so pickling+sha256+IO overlap the next steps",
            )

    def _check_sync_on_current_step(self, loop):
        """UL112 over one outermost step loop: collect the names bound
        from ``train_step`` calls anywhere in the loop subtree (tuple
        targets included), then flag every blocking sync whose operand
        data-flows from one of them — ``jax.device_get(<name>...)``,
        ``<name>....item()``, ``<name>....block_until_ready()``.  Values
        from the drain path (``flush_stats`` returns, lagged stats) are
        not step-call bindings and never fire.  Closures defined in the
        loop are fresh scopes, as everywhere in this linter."""
        step_binds = {}   # name -> linenos bound FROM train_step
        other_binds = {}  # name -> linenos bound from anything else
        syncs = []
        stack = list(ast.iter_child_nodes(loop))
        while stack:
            sub = stack.pop()
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                continue
            if isinstance(sub, ast.Assign):
                is_step = (
                    isinstance(sub.value, ast.Call)
                    and (chain := _attr_chain(sub.value.func)) is not None
                    and chain.split(".")[-1] in _STEP_LOOP_MARKERS
                )
                table = step_binds if is_step else other_binds
                for tgt in sub.targets:
                    elts = (tgt.elts if isinstance(
                        tgt, (ast.Tuple, ast.List)) else [tgt])
                    for el in elts:
                        if isinstance(el, ast.Name):
                            table.setdefault(el.id, []).append(sub.lineno)
            if isinstance(sub, ast.Call):
                chain = _attr_chain(sub.func)
                if (chain is not None
                        and chain.split(".")[-1] == "device_get"
                        and sub.args):
                    syncs.append(
                        (sub, chain, self._value_names(sub.args[0]))
                    )
                elif (isinstance(sub.func, ast.Attribute)
                        and sub.func.attr in _UL112_METHOD_TAILS
                        and not sub.args):
                    syncs.append((
                        sub, sub.func.attr,
                        self._value_names(sub.func.value),
                    ))
            stack.extend(ast.iter_child_nodes(sub))
        if not step_binds:
            return

        def current_step_value(root, sync_line):
            """Statement order is the lag discriminator: the sync fires
            only when the NEAREST binding of ``root`` above it is a
            train_step bind.  A sync before any step bind reads the
            previous iteration's (already-on-host, lag-1) value — the
            sanctioned manual lag idiom — and a rebind from anything
            else in between (e.g. ``out = trainer.flush_stats()``)
            launders the name back to the drain path."""
            step = max((x for x in step_binds.get(root, [])
                        if x < sync_line), default=None)
            if step is None:
                return False
            rebind = max((x for x in other_binds.get(root, [])
                          if x < sync_line), default=None)
            return rebind is None or rebind < step

        for node, what, names in syncs:
            roots = {n.split(".")[0] for n in names} & set(step_binds)
            if not any(current_step_value(r, node.lineno) for r in roots):
                continue
            self.emit(
                "UL112", "sync-on-current-step", "error", node,
                f"blocking sync '{what}' on the CURRENT step's outputs "
                f"inside the train loop — the value was bound from "
                f"train_step this very iteration, so the host stalls a "
                f"full device step and a pipelined loop "
                f"(--pipeline-depth >= 2) silently collapses to serial "
                f"dispatch; read the lag-K drained outputs train_step "
                f"already returns (or flush_stats() at real boundaries) "
                f"instead",
            )

    @staticmethod
    def _ul113_replica_step(call):
        """``X.serve_step()`` where X is not bare ``self`` — a REPLICA
        step (an engine stepping itself is its own driver, not a
        fan-out).  Returns a display chain or None."""
        if not (isinstance(call.func, ast.Attribute)
                and call.func.attr == "serve_step"):
            return None
        recv = call.func.value
        if isinstance(recv, ast.Name) and recv.id == "self":
            return None
        return _attr_chain(call.func) or "<replica>.serve_step"

    def _loop_has_replica_step(self, loop):
        stack = list(ast.iter_child_nodes(loop))
        while stack:
            sub = stack.pop()
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                continue
            if (isinstance(sub, ast.Call)
                    and self._ul113_replica_step(sub) is not None):
                return True
            stack.extend(ast.iter_child_nodes(sub))
        return False

    def _check_unguarded_replica_step(self, loop):
        """UL113 over one outermost replica-stepping loop: classify the
        loop as FLEET FAN-OUT (subscripted receiver, replica-ish
        iterable name, or >= 2 distinct stepped receivers), check for
        health recording anywhere in its subtree, then flag every
        replica step not shielded by a try-with-handler.  Closures
        defined in the loop are fresh scopes, as everywhere here."""
        steps = []
        fleet_shape = False
        has_health = False
        stack = [loop]
        while stack:
            sub = stack.pop()
            if sub is not loop and isinstance(
                    sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)):
                continue
            if isinstance(sub, (ast.For, ast.AsyncFor)):
                for n in ast.walk(sub.iter):
                    name = None
                    if isinstance(n, ast.Attribute):
                        name = n.attr
                    elif isinstance(n, ast.Name):
                        name = n.id
                    if name and any(f in name.lower()
                                    for f in _UL113_FLEET_NAME_FRAGS):
                        fleet_shape = True
            if isinstance(sub, ast.Call):
                rs = self._ul113_replica_step(sub)
                if rs is not None:
                    steps.append((sub, rs))
                    if any(isinstance(n, ast.Subscript)
                           for n in ast.walk(sub.func.value)):
                        fleet_shape = True  # engines[rid].serve_step()
                chain = _attr_chain(sub.func)
                tail = chain.split(".")[-1] if chain else (
                    sub.func.attr if isinstance(sub.func, ast.Attribute)
                    else None)
                if tail and tail.startswith(_UL113_HEALTH_PREFIXES):
                    has_health = True
                if chain and any("health" in part.lower()
                                 for part in chain.split(".")[:-1]):
                    has_health = True
            stack.extend(ast.iter_child_nodes(sub))
        if len({chain for _, chain in steps}) >= 2:
            fleet_shape = True
        if not steps or not fleet_shape or has_health:
            return

        def walk(node, guarded):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef, ast.Lambda)):
                    continue
                if isinstance(child, ast.Try):
                    covers = guarded or bool(child.handlers)
                    for stmt in child.body:
                        walk(stmt, covers)
                    for h in child.handlers:
                        for stmt in h.body:
                            walk(stmt, guarded)
                    for stmt in child.orelse + child.finalbody:
                        walk(stmt, guarded)
                    continue
                if isinstance(child, ast.Call) and not guarded:
                    rs = self._ul113_replica_step(child)
                    if rs is not None:
                        self.emit(
                            "UL113", "unguarded-replica-step", "error",
                            child,
                            f"bare '{rs}' on a replica inside a "
                            f"fleet/router loop with no typed fault "
                            f"handling or health recording — the engine "
                            f"only lets an exception escape serve_step() "
                            f"when it cannot continue, so one replica's "
                            f"crash re-raises out of the fan-out loop "
                            f"and takes every OTHER replica's traffic "
                            f"with it, and a wedged replica is never "
                            f"noticed; step replicas through a guarded "
                            f"helper that records typed faults and "
                            f"progress into the health model "
                            f"(FleetRouter._step_replica) so a dead "
                            f"replica is evicted and its sessions fail "
                            f"over",
                        )
                walk(child, guarded)

        walk(loop, False)

    @staticmethod
    def _ul118_factory_call(node):
        """A call whose callee's final name contains ``factory`` — the
        boot path of a fleet slot.  Returns a display name or None."""
        if not isinstance(node, ast.Call):
            return None
        func = node.func
        if isinstance(func, ast.Name):
            name = func.id
        elif isinstance(func, ast.Attribute):
            name = func.attr
        else:
            return None
        if "factory" not in name.lower():
            return None
        return _attr_chain(func) or name

    def _loop_has_factory_call(self, loop):
        stack = list(ast.iter_child_nodes(loop))
        while stack:
            sub = stack.pop()
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                continue
            if self._ul118_factory_call(sub) is not None:
                return True
            stack.extend(ast.iter_child_nodes(sub))
        return False

    def _check_unbounded_replica_growth(self, loop):
        """UL118 over one outermost factory-calling loop: find every
        store that GROWS the fleet with a factory result — an
        ``.append``/``.add``/``.insert`` of it, or a subscript store
        keyed by anything but a loop variable (in a ``while`` loop
        there IS no loop variable, so every store counts) — then
        silence them all if the loop carries a scale gate anywhere: a
        comparison involving a ``*max*`` name or a ``len()`` bound, a
        ``*cooldown*`` gate, or a breaker ``.ready()`` check.  The replacement shape
        ``engines[rid] = factory(rid)`` keyed by the loop variable
        (rolling restart) swaps a slot without growing the fleet and
        is exempt.  Closures defined in the loop are fresh scopes, as
        everywhere in this linter."""
        loop_vars = set()
        factory_names = set()  # names bound from a factory call
        grow_calls = []        # (.append/.add/.insert node, recv, args)
        sub_stores = []        # (Assign node, Subscript target)
        has_gate = False
        stack = [loop]
        while stack:
            sub = stack.pop()
            if sub is not loop and isinstance(
                    sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)):
                continue
            frag = None
            if isinstance(sub, ast.Name):
                frag = sub.id
            elif isinstance(sub, ast.Attribute):
                frag = sub.attr
            if frag and "cooldown" in frag.lower():
                has_gate = True
            if isinstance(sub, (ast.For, ast.AsyncFor)):
                for n in ast.walk(sub.target):
                    if isinstance(n, ast.Name):
                        loop_vars.add(n.id)
            elif isinstance(sub, ast.Compare):
                for n in ast.walk(sub):
                    nm = (n.id if isinstance(n, ast.Name)
                          else n.attr if isinstance(n, ast.Attribute)
                          else None)
                    if nm and "max" in nm.lower():
                        has_gate = True
                    # comparing a len() anywhere bounds the growth
                    # (``while len(fleet) < cap``), same as UL109
                    if (isinstance(n, ast.Call)
                            and isinstance(n.func, ast.Name)
                            and n.func.id == "len"):
                        has_gate = True
            elif isinstance(sub, ast.Call):
                if (isinstance(sub.func, ast.Attribute)
                        and sub.func.attr == "ready"):
                    has_gate = True
                if (isinstance(sub.func, ast.Attribute)
                        and sub.func.attr in _UL118_GROW_TAILS):
                    recv = _attr_chain(sub.func.value)
                    if recv:
                        grow_calls.append((sub, recv))
            elif isinstance(sub, ast.Assign):
                for tgt in sub.targets:
                    if isinstance(tgt, ast.Subscript):
                        sub_stores.append((sub, tgt))
                    elif (isinstance(tgt, ast.Name)
                          and any(self._ul118_factory_call(n) is not None
                                  for n in ast.walk(sub.value))):
                        factory_names.add(tgt.id)
            stack.extend(ast.iter_child_nodes(sub))
        if has_gate:
            return

        def from_factory(value):
            # the value subtree boots a replica — a direct factory
            # call, or a name bound from one in this loop
            for n in ast.walk(value):
                if self._ul118_factory_call(n) is not None:
                    return True
                if isinstance(n, ast.Name) and n.id in factory_names:
                    return True
            return False

        growth = [(node, recv) for node, recv in grow_calls
                  if any(from_factory(a) for a in node.args)]
        for node, tgt in sub_stores:
            if not from_factory(node.value):
                continue
            key = tgt.slice
            if isinstance(key, ast.Name) and key.id in loop_vars:
                continue  # replacement, not growth: rolling restart
            growth.append((node, _attr_chain(tgt.value) or "<fleet>"))
        for node, recv in growth:
            self.emit(
                "UL118", "unbounded-replica-growth", "error", node,
                f"replica factory boot grows '{recv}' inside a fleet "
                f"loop with no max-replicas bound, cooldown gate, or "
                f"breaker .ready() check in sight — each entry is a "
                f"whole ServeEngine (params + KV pool + compiled "
                f"step), so a pressure/retry loop boots replicas "
                f"until the host OOMs and the checkpoint store takes "
                f"a boot storm; gate boots on the autoscale envelope "
                f"(serving + booting < max_replicas, per-direction "
                f"cooldown, bounded boot budget — fleet/autoscaler.py "
                f"FleetAutoscaler) and route them through the "
                f"breaker-gated canary (FleetRouter.scale_up)",
            )

    def _check_blocking_in_router_loop(self, node):
        """UL111: a blocking host call inside a router dispatch loop
        serializes the whole fleet behind one replica."""
        if self._router_loop_depth == 0:
            return
        chain = _attr_chain(node.func)
        if chain is None:
            return
        tail = chain.split(".")[-1]
        if tail == "sleep":
            self.emit(
                "UL111", "blocking-in-router-loop", "error", node,
                f"'{chain}' inside a router dispatch loop — every "
                f"fan-out cycle stalls while queued requests age "
                f"toward their deadlines; pace the loop with the "
                f"virtual-time trace replay (fleet/trace.py) or let "
                f"the caller pace, never the dispatch path",
            )
        elif (isinstance(node.func, ast.Attribute) and tail == "join"
                and not node.args):
            self.emit(
                "UL111", "blocking-in-router-loop", "error", node,
                f"'{chain}()' inside a router dispatch loop — a "
                f"thread/process join parks the router behind ONE "
                f"replica while every other replica's queue ages; "
                f"poll load_snapshot()/serve_step() cooperatively "
                f"instead of joining",
            )
        elif isinstance(node.func, ast.Attribute) and tail == "generate":
            self.emit(
                "UL111", "blocking-in-router-loop", "error", node,
                f"synchronous '{chain}(...)' inside a router dispatch "
                f"loop — generate() runs one replica's whole batch to "
                f"completion, serializing the fleet; routers must "
                f"interleave submit()/serve_step()/collect_finished()",
            )

    def _visit_loop(self, node):
        is_step = self._loop_is_step_loop(node)
        is_router = self._loop_is_router_loop(node)
        if (self._serve_loop_depth == 0
                and self._loop_is_serve_loop(node)):
            # scan once from the OUTERMOST serve loop: its subtree
            # covers nested loops' growth sites and bound checks alike
            self._check_unbounded_growth(node)
            self._serve_loop_depth += 1
            is_serve = True
        else:
            is_serve = False
        if self._ul113_depth == 0 and self._loop_has_replica_step(node):
            # scan once from the OUTERMOST replica-stepping loop: its
            # subtree carries the fan-out classification (iterables,
            # receivers) and the guards/health calls alike
            self._check_unguarded_replica_step(node)
            self._ul113_depth += 1
            is_replica_loop = True
        else:
            is_replica_loop = False
        if self._ul118_depth == 0 and self._loop_has_factory_call(node):
            # scan once from the OUTERMOST factory-calling loop: its
            # subtree carries the growth sites and the scale gates alike
            self._check_unbounded_replica_growth(node)
            self._ul118_depth += 1
            is_factory_loop = True
        else:
            is_factory_loop = False
        if is_step:
            if self._step_loop_depth == 0:
                # scan once from the OUTERMOST step loop (UL109 pattern):
                # its subtree covers nested loops' step bindings and
                # sync sites alike
                self._check_sync_on_current_step(node)
            self._step_loop_depth += 1
        if is_router:
            self._router_loop_depth += 1
        self.generic_visit(node)
        if is_step:
            self._step_loop_depth -= 1
        if is_router:
            self._router_loop_depth -= 1
        if is_serve:
            self._serve_loop_depth -= 1
        if is_replica_loop:
            self._ul113_depth -= 1
        if is_factory_loop:
            self._ul118_depth -= 1

    def visit_For(self, node):
        self._visit_loop(node)

    def visit_While(self, node):
        self._visit_loop(node)

    def _visit_scope_reset(self, node):
        # a function/lambda DEFINED inside a step/serve/router loop
        # does not run per iteration — its body is a fresh scope for
        # UL108/UL109/UL111
        saved, self._step_loop_depth = self._step_loop_depth, 0
        saved_serve, self._serve_loop_depth = self._serve_loop_depth, 0
        saved_router, self._router_loop_depth = self._router_loop_depth, 0
        saved_ul113, self._ul113_depth = self._ul113_depth, 0
        saved_ul118, self._ul118_depth = self._ul118_depth, 0
        self.generic_visit(node)
        self._step_loop_depth = saved
        self._serve_loop_depth = saved_serve
        self._router_loop_depth = saved_router
        self._ul113_depth = saved_ul113
        self._ul118_depth = saved_ul118

    def visit_FunctionDef(self, node):
        self._visit_scope_reset(node)

    def visit_AsyncFunctionDef(self, node):
        self._visit_scope_reset(node)

    def visit_Lambda(self, node):
        self._visit_scope_reset(node)

    # -- UL107 ---------------------------------------------------------

    def _is_io_call(self, node):
        chain = _attr_chain(node.func)
        if chain is None:
            return False
        parts = chain.split(".")
        if parts[0] == "open" or parts[-1] == "open":
            return True
        if parts[0] in _IO_MODULE_ROOTS and len(parts) > 1:
            return True
        return (isinstance(node.func, ast.Attribute)
                and parts[-1] in _IO_METHOD_TAILS)

    def _try_touches_io(self, try_node):
        for stmt in try_node.body:
            for sub in ast.walk(stmt):
                if isinstance(sub, ast.Call) and self._is_io_call(sub):
                    return True
        return False

    @staticmethod
    def _handler_swallows(handler):
        """Body is pure pass/continue/constant — the error vanishes."""
        return all(
            isinstance(stmt, (ast.Pass, ast.Continue))
            or (isinstance(stmt, ast.Expr)
                and isinstance(stmt.value, ast.Constant))
            for stmt in handler.body
        )

    def _handler_is_broad(self, handler):
        types = []
        if handler.type is None:
            return True, True  # bare except: also eats KeyboardInterrupt
        if isinstance(handler.type, ast.Tuple):
            types = list(handler.type.elts)
        else:
            types = [handler.type]
        names = {
            _attr_chain(t).split(".")[-1]
            for t in types if _attr_chain(t) is not None
        }
        return bool(names & _BROAD_EXC_NAMES), False

    def visit_Try(self, node):
        if self._try_touches_io(node):
            for handler in node.handlers:
                broad, bare = self._handler_is_broad(handler)
                if not broad:
                    continue
                if bare:
                    self.emit(
                        "UL107", "swallowed-io-error", "error", handler,
                        "bare 'except:' around IO calls — it catches "
                        "KeyboardInterrupt/SystemExit too, and in a "
                        "checkpoint path a swallowed write error means "
                        "the run believes a save landed that never hit "
                        "the disk; catch OSError (or log and re-raise)",
                    )
                elif self._handler_swallows(handler):
                    self.emit(
                        "UL107", "swallowed-io-error", "error", handler,
                        "'except Exception: pass' around IO calls "
                        "swallows the error — in a checkpoint path the "
                        "run believes a save landed that never hit the "
                        "disk and the failure surfaces days later as a "
                        "missing resume point; narrow the type, log, or "
                        "re-raise",
                    )
        self.generic_visit(node)

    # -- UL114 ---------------------------------------------------------

    def _collect_zero1_plumbing(self):
        """Module precondition for UL114: the zero1 flag is *plumbed*
        here — some Name/Attribute/argument mentions zero1.  Modules
        that never see the flag (the optimizer zoo itself, plain
        harnesses) are exempt: without ZeRO-1 in play a replicated
        moment allocation is just the normal dp layout."""
        self._zero1_plumbed = False
        self._ul114_wrapped = set()
        for node in ast.walk(self._tree):
            name = None
            if isinstance(node, ast.Name):
                name = node.id
            elif isinstance(node, ast.Attribute):
                name = node.attr
            elif isinstance(node, ast.arg):
                name = node.arg
            elif isinstance(node, ast.keyword):
                name = node.arg
            if name and "zero1" in str(name).lower():
                self._zero1_plumbed = True
            if isinstance(node, ast.Call):
                chain = _attr_chain(node.func)
                if (chain is not None
                        and chain.split(".")[-1] in _UL114_SHARDED_WRAPPERS):
                    for arg in node.args:
                        self._ul114_wrapped.add(id(arg))

    def _check_replicated_optim_init(self, node):
        """UL114 pattern (a): a bare ``<optimizer>.init(params)`` call in
        a zero1-plumbed module.  The sanctioned creation path routes
        through ``jax.jit(opt.init, out_shardings=...)`` (whose ``init``
        is an argument, not a call — silent by shape) or wraps the
        result in a sharding constraint; anything else materializes a
        full replicated fp32 moment tree on every replica before the
        install re-shards it — the transient allocation ZeRO-1 exists
        to avoid."""
        if not self._zero1_plumbed or id(node) in self._ul114_wrapped:
            return
        chain = _attr_chain(node.func)
        if chain is None or not chain.endswith(".init"):
            return
        parts = chain.split(".")
        if len(parts) < 2:
            return
        recv = parts[-2].lower()
        if not any(recv.startswith(r) for r in _UL114_OPTIM_RECEIVERS):
            return
        self.emit(
            "UL114", "replicated-optim-state", "error", node,
            f"bare '{chain}(...)' in a module that plumbs the zero1 "
            f"flag — the optimizer state is created OUTSIDE a "
            f"sharding-constraint context, so a full replicated fp32 "
            f"moment tree materializes on every replica before any "
            f"re-shard (the allocation --zero1 exists to avoid); "
            f"create it through jax.jit(opt.init, out_shardings=...) "
            f"(Trainer._init_opt_state) or wrap the result in "
            f"with_sharding_constraint/device_put",
        )

    def _check_optim_init_allocations(self, fn):
        """UL114 pattern (b): inside a function named ``init`` in a
        zero1-plumbed module, a full-shape moment allocation
        (``zeros_like(param)`` or ``zeros(param.shape, ...)``) outside
        a sharding wrapper."""
        if not self._zero1_plumbed:
            return
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call) or id(node) in self._ul114_wrapped:
                continue
            chain = _attr_chain(node.func)
            if chain is None:
                continue
            tail = chain.split(".")[-1]
            shaped = (
                tail in _UL114_ALLOC_SHAPE_TAILS and node.args
                and isinstance(node.args[0], ast.Attribute)
                and node.args[0].attr == "shape"
            )
            if tail == "tree_map":
                # tree_map(jnp.zeros_like, params) — the allocator rides
                # as a bare function reference, not a call
                for arg in node.args:
                    ref = _attr_chain(arg)
                    if (ref is not None
                            and ref.split(".")[-1] in _UL114_ALLOC_TAILS):
                        shaped = True
                        chain = ref
                        break
            if tail in _UL114_ALLOC_TAILS or shaped:
                self.emit(
                    "UL114", "replicated-optim-state", "error", node,
                    f"'{chain}' builds a full-shape moment leaf inside "
                    f"'{fn.name}()' in a module that plumbs the zero1 "
                    f"flag, outside any sharding-constraint context — "
                    f"under --zero1 the moments must be *created* "
                    f"sharded (jit the init with out_shardings, or "
                    f"constrain each leaf) or every replica briefly "
                    f"holds the full replicated tree",
                )

    # -- UL110 ---------------------------------------------------------

    def _ul110_io_kind(self, call):
        """Classify a call inside a dataset fetch body as raw record IO:
        ``open``, pickle/numpy byte loads, or an LMDB-style ``.get``
        (receiver goes through ``begin()`` or names a txn/env)."""
        chain = _attr_chain(call.func)
        if chain is not None:
            parts = chain.split(".")
            if parts[0] == "open" or parts[-1] == "open":
                return "open()"
            if len(parts) > 1 and parts[-1] in _UL110_IO_TAILS:
                return f"'{chain}'"
        if isinstance(call.func, ast.Attribute) and call.func.attr == "get":
            for sub in ast.walk(call.func.value):
                name = None
                if isinstance(sub, ast.Attribute):
                    name = sub.attr
                elif isinstance(sub, ast.Name):
                    name = sub.id
                if name and ("begin" == name or "txn" in name
                             or "env" in name.lstrip("_")):
                    return "an LMDB get"
        return None

    @staticmethod
    def _handler_reraises(handler):
        return any(isinstance(s, ast.Raise) for s in ast.walk(handler))

    def _check_dataset_fetch_guard(self, fn):
        """UL110 over one ``__getitem__``/``__iter__`` body: every raw IO
        call must sit under a ``try`` whose handler re-raises (the typed
        ``DataIntegrityError`` translation), and no broad handler may
        swallow without re-raising.  Nested function defs are fresh
        scopes, as everywhere in this linter."""
        def walk(node, guarded):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.Lambda)):
                    continue
                if isinstance(child, ast.Try):
                    covers = guarded or any(
                        self._handler_reraises(h) for h in child.handlers
                    )
                    for stmt in child.body:
                        walk(stmt, covers)
                    for h in child.handlers:
                        broad, _ = self._handler_is_broad(h)
                        if broad and not self._handler_reraises(h):
                            self.emit(
                                "UL110", "unguarded-dataset-io", "error", h,
                                f"broad except in dataset '{fn.name}' "
                                f"swallows the failure without a typed "
                                f"re-raise — a torn record becomes a "
                                f"silent garbage sample the guarded "
                                f"fetch layer can never see; re-raise "
                                f"DataIntegrityError",
                            )
                        for stmt in h.body:
                            walk(stmt, guarded)
                    for stmt in child.orelse + child.finalbody:
                        walk(stmt, guarded)
                    continue
                if isinstance(child, ast.Call) and not guarded:
                    kind = self._ul110_io_kind(child)
                    if kind:
                        self.emit(
                            "UL110", "unguarded-dataset-io", "error", child,
                            f"{kind} in dataset '{fn.name}' with no "
                            f"typed re-raise around it — a torn record "
                            f"surfaces as a raw decode error (or silent "
                            f"truncation) instead of the "
                            f"DataIntegrityError the input-pipeline "
                            f"fault ladder keys on "
                            f"(data/resilient.py)",
                        )
                walk(child, guarded)

        walk(fn, False)

    # -- traversal -----------------------------------------------------

    def visit_With(self, node):
        scoped = self._is_numpy_seed_with(node)
        if scoped:
            self._with_seed_depth += 1
        self.generic_visit(node)
        if scoped:
            self._with_seed_depth -= 1

    def visit_Call(self, node):
        if self._is_jax_jit(node.func):
            self._check_jit_call(node)
        if self.dataset_file:
            self._check_dataset_rng(node)
        self._check_blocking(node)
        self._check_dropout_rate(node)
        self._check_where_nan(node)
        self._check_sync_in_step_loop(node)
        self._check_blocking_in_router_loop(node)
        self._check_replicated_optim_init(node)
        self._check_wall_clock(node)
        self.generic_visit(node)

    # -- UL117 ---------------------------------------------------------

    def _check_wall_clock(self, node):
        if not self.decision_file:
            return
        if not self._is_wall_clock(node.func):
            return
        if id(node) in self._ul117_clean:
            return
        chain = _attr_chain(node.func) or "<wall clock>"
        self.emit(
            "UL117", "wall-clock-in-decision-path", "warning", node,
            f"{chain}() read in a decision module outside the "
            f"injectable-clock idiom — a deadline, health verdict, or "
            f"rollout gate keyed on the real clock cannot be replayed "
            f"by the chaos/failover oracles or the Pass-5 determinism "
            f"harness; take a clock=None parameter and read "
            f"self._clock() (fleet/health.py, serve/engine.py), or use "
            f"the t0/elapsed measurement shape for pure timing",
        )

    # -- UL115 ---------------------------------------------------------

    def _is_thread_ctor(self, func):
        chain = _attr_chain(func)
        if chain is None:
            return False
        head, _, tail = chain.rpartition(".")
        return ((tail == "Thread" and head in self.threading_aliases)
                or (head == "" and tail in self.thread_ctors))

    @staticmethod
    def _spawns_daemon(call):
        return any(
            kw.arg == "daemon" and isinstance(kw.value, ast.Constant)
            and kw.value.value is True
            for kw in call.keywords
        )

    def _check_daemon_threads(self):
        """UL115 over the whole module: every ``threading.Thread(...,
        daemon=True)`` spawn must have a reachable shutdown path — a
        ``.join`` on the receiver it was bound to, or a shutdown-named
        method on the owning class.  Whole-module scan rather than a
        visitor hook: the sanction (a join in ``close()``, a ``stop``
        method) usually lives far from the spawn."""
        spawns = [n for n in ast.walk(self._tree)
                  if isinstance(n, ast.Call)
                  and self._is_thread_ctor(n.func)
                  and self._spawns_daemon(n)]
        if not spawns:
            return
        # chained `Thread(...).start()`: the reference is dropped on
        # the spot — no shutdown path can ever reach it
        chained = set()
        # receivers the spawn is bound to: `self._thread = Thread(...)`
        assigned = {}
        # receiver tails a `.join(...)` is called on anywhere here
        joined = set()
        for node in ast.walk(self._tree):
            if isinstance(node, ast.Call) and isinstance(
                    node.func, ast.Attribute):
                if (node.func.attr == "start"
                        and isinstance(node.func.value, ast.Call)):
                    chained.add(id(node.func.value))
                elif node.func.attr == "join":
                    chain = _attr_chain(node.func)
                    if chain and "." in chain:
                        joined.add(chain.split(".")[-2])
            elif isinstance(node, ast.Assign) and isinstance(
                    node.value, ast.Call):
                for t in node.targets:
                    if isinstance(t, ast.Attribute):
                        assigned[id(node.value)] = t.attr
                    elif isinstance(t, ast.Name):
                        assigned[id(node.value)] = t.id
        # owning class per spawn (ast.walk is outer-first, so nested
        # classes overwrite with the innermost owner)
        owner_methods = {}
        for cls in ast.walk(self._tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            methods = {n.name for n in ast.walk(cls)
                       if isinstance(n, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))}
            for n in ast.walk(cls):
                if isinstance(n, ast.Call):
                    owner_methods[id(n)] = methods
        for call in spawns:
            if id(call) in chained:
                self.emit(
                    "UL115", "unjoined-daemon-thread", "warning", call,
                    "threading.Thread(..., daemon=True).start() drops "
                    "the only reference to the thread — no shutdown "
                    "path can ever join or stop it, and its in-flight "
                    "work dies silently at interpreter exit; bind it "
                    "and join/stop it on shutdown",
                )
                continue
            recv = assigned.get(id(call))
            if recv is None:
                continue  # passed along, never started here: not provable
            if recv in joined:
                continue
            methods = owner_methods.get(id(call), set())
            if methods & _UL115_SHUTDOWN_METHODS:
                continue
            self.emit(
                "UL115", "unjoined-daemon-thread", "warning", call,
                f"daemon thread bound to '{recv}' has no reachable "
                f"shutdown path — no .join() on '{recv}' in this "
                f"module and no stop/close/drain/shutdown method on "
                f"the owning class; a daemon worker dies silently at "
                f"interpreter exit, losing whatever it had buffered "
                f"(the async-writer/prefetch-pump shape owns a stop "
                f"flag or joins on close)",
            )

    def _visit_functions(self):
        for node in ast.walk(self._tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if self._fn_is_jitted(node):
                    self._check_numpy_in_jit(node)
                self._check_jit_decorators(node)
                if (self.dataset_file
                        and node.name in ("__getitem__", "__iter__")):
                    self._check_dataset_fetch_guard(node)
                if node.name == "init":
                    self._check_optim_init_allocations(node)

    # -- UL116 ---------------------------------------------------------

    def _ul116_io_kind(self, call):
        """Classify a call as raw checkpoint-bytes IO: ``open`` or a
        pickle ``load``/``loads``."""
        chain = _attr_chain(call.func)
        if chain is None:
            return None
        parts = chain.split(".")
        if parts[0] == "open" or parts[-1] == "open":
            return "open()"
        if (len(parts) > 1 and parts[-1] in ("load", "loads")
                and "pickle" in parts[0].lower()):
            return f"'{chain}'"
        return None

    @staticmethod
    def _ul116_hinted(call):
        """Does any argument name checkpoint/manifest bytes?  Matches
        name fragments on identifiers/attributes and ``.pt``/fragment
        hits in string literals (f-string pieces included)."""
        for arg in list(call.args) + [kw.value for kw in call.keywords]:
            for sub in ast.walk(arg):
                if (isinstance(sub, ast.Constant)
                        and isinstance(sub.value, str)):
                    s = sub.value.lower()
                    if (s.endswith(".pt") or ".pt" in s
                            or any(h in s for h in _UL116_NAME_HINTS)):
                        return True
                name = None
                if isinstance(sub, ast.Name):
                    name = sub.id
                elif isinstance(sub, ast.Attribute):
                    name = sub.attr
                if name and any(h in name.lower()
                                for h in _UL116_NAME_HINTS):
                    return True
        return False

    @staticmethod
    def _ul116_verified(call):
        """Sanctioned shape: the bytes come straight out of
        ``read_verified(...)`` (``pickle.loads(read_verified(p))``)."""
        for arg in call.args:
            for sub in ast.walk(arg):
                if isinstance(sub, ast.Call):
                    chain = _attr_chain(sub.func)
                    if chain and chain.split(".")[-1] == "read_verified":
                        return True
        return False

    def _check_checkpoint_reads(self):
        """UL116 over the whole module (deploy/serve/fleet files only):
        every checkpoint/manifest read must go through
        ``read_verified`` or sit under a ``try`` whose handler
        re-raises the typed integrity error."""
        def enter(node, guarded):
            # a def inside a try runs LATER, outside the guard
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                guarded = False
            walk(node, guarded)

        def walk(node, guarded):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.Try):
                    covers = guarded or any(
                        self._handler_reraises(h) for h in child.handlers
                    )
                    for stmt in child.body:
                        enter(stmt, covers)
                    for h in child.handlers:
                        for stmt in h.body:
                            enter(stmt, guarded)
                    for stmt in child.orelse + child.finalbody:
                        enter(stmt, guarded)
                    continue
                if isinstance(child, ast.Call) and not guarded:
                    kind = self._ul116_io_kind(child)
                    if (kind and self._ul116_hinted(child)
                            and not self._ul116_verified(child)):
                        self.emit(
                            "UL116", "unverified-checkpoint-read",
                            "error", child,
                            f"{kind} reads checkpoint/manifest bytes "
                            f"outside read_verified and any typed "
                            f"re-raise — a torn or tampered file "
                            f"bypasses the integrity ladder on the "
                            f"path that hot-swaps weights into live "
                            f"traffic; load through read_verified "
                            f"(deploy/loader.py, deploy/publish.py) "
                            f"or re-raise CheckpointIntegrityError",
                        )
                enter(child, guarded)

        if self.deploy_file:
            walk(self._tree, False)

    def run(self):
        self.visit(self._tree)
        self._visit_functions()
        self._check_daemon_threads()
        self._check_checkpoint_reads()
        return self.findings


def _is_dataset_file(path):
    norm = path.replace(os.sep, "/")
    return ("/data/" in norm or norm.endswith("_dataset.py")
            or "dataset" in os.path.basename(norm))


def _is_deploy_file(path):
    """UL116 scope: the serve-side code a checkpoint flows through on
    its way into live traffic (train-side reads are guarded by the
    checkpoint_utils load path itself)."""
    norm = path.replace(os.sep, "/")
    return any(f"/{d}/" in norm or norm.startswith(f"{d}/")
               for d in ("deploy", "serve", "fleet"))


def _is_decision_file(path):
    """UL117 scope: host modules whose control decisions feed device
    programs or live traffic — admission/row planning, replica routing,
    health verdicts, rollout gates, kernel-variant dispatch.  Everything
    under fleet/ and deploy/ is decision code wholesale; elsewhere the
    basename names the role."""
    norm = path.replace(os.sep, "/")
    if any(f"/{d}/" in norm or norm.startswith(f"{d}/")
           for d in ("fleet", "deploy")):
        return True
    return any(f in os.path.basename(norm)
               for f in _UL117_DECISION_FRAGS)


def lint_file(path, *, rel_to=None):
    with open(path, encoding="utf-8") as fh:
        source = fh.read()
    rel = os.path.relpath(path, rel_to) if rel_to else path
    try:
        linter = _ModuleLint(
            rel, source,
            dataset_file=_is_dataset_file(rel),
            deploy_file=_is_deploy_file(rel),
            lines=source.splitlines(),
            decision_file=_is_decision_file(rel),
        )
    except SyntaxError as e:
        return [Finding(
            "UL100", "syntax-error", "error", f"{rel}:{e.lineno or 0}",
            f"file does not parse: {e.msg}",
        )]
    return linter.run()


def lint_paths(roots, *, rel_to=None, exclude=("__pycache__",)):
    """Lint every .py file under ``roots`` (files or directories)."""
    findings = []
    for root in roots:
        if os.path.isfile(root):
            findings.extend(lint_file(root, rel_to=rel_to))
            continue
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = [d for d in sorted(dirnames)
                           if d not in exclude]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    findings.extend(
                        lint_file(os.path.join(dirpath, fn), rel_to=rel_to)
                    )
    return findings
