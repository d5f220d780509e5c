"""Kernel autotuning: measured per-bucket config selection with a
persistent cache and eager-crossover dispatch.

The static heuristics in the Pallas tier guess block shapes from VMEM
budgets; this package measures instead.  Per (kernel, shape-bucket,
dtype, bias/mask variant, device kind) the tuner benchmarks a bounded
candidate set on-device — **eager is always a candidate** — and records
the winner in a persistent JSON cache (``tools/kernel_tune_cache.json``
+ a ``~/.cache/unicore_tpu`` overlay).  Dispatch sites consult
:func:`flash_decision` / :func:`softmax_dropout_decision` at trace time:

- a cached config dict overrides the heuristic block choice;
- a cached ``"eager"`` skips the kernel entirely (the crossover case —
  a fused kernel that times slower than XLA's own fusion is a
  regression, not a feature);
- a miss, a stale entry (environment fingerprint mismatch), or any
  error falls back to the existing heuristics.  Nothing here can make
  dispatch fail.

Modes (``--kernel-autotune`` / ``UNICORE_TPU_KERNEL_AUTOTUNE``):

- ``off``   — heuristics only; the cache is never read.
- ``cache`` — (default) read the cache, never time.
- ``tune``  — like ``cache``, but a single-host TPU process times
  unseen buckets at first dispatch and records them to the overlay.

Decisions are MEMOIZED per process the first time a bucket is consulted
and frozen thereafter: the forward and backward of one ``custom_vjp``
must trace identical block choices (the dropout seed/mask layouts are
grid-dependent), so a cache write can never flip a decision mid-trace.
``reset_memo()`` (tests, post-tune) starts fresh.

Multi-host runs read ONLY the committed repo cache and never tune:
per-host overlays could disagree and trace different programs into one
SPMD step.
"""

import contextlib
import logging
import os

from unicore_tpu.ops.tuning import cache as _cache_mod
from unicore_tpu.ops.tuning.cache import (  # noqa: F401
    TuneCache, bucket_key, env_fingerprint,
)
from unicore_tpu.ops.tuning.candidates import (  # noqa: F401
    OPS, PRESETS, ce_workload, describe_config, flash_workload, ln_workload,
    pow2_bucket, ragged_workload, sd_workload, sr_cast_workload,
)

logger = logging.getLogger(__name__)


def _static_verdict_keys():
    """Buckets with a COMMITTED measured verdict, applied on a cache
    miss (after the cache, before the heuristics/tuner).  Unlike cache
    entries these are fingerprint-independent: they encode a structural
    result, not a device timing.

    The one entry today: the r5-era evoformer softmax_dropout shape
    ([1,128,4,128,128] bf16, 5-D broadcast mask/bias) measured
    0.985-0.994x eager across rounds — the kernel's 128x128 row blocks
    leave only 16K elements per grid program, under the fixed-cost
    crossover.  Recording "eager" here retires the kernel path for that
    bucket out of the box (both dropout states); an explicit `unicore
    tune` run on the bucket still wins, since the cache is consulted
    first."""
    keys = []
    for dropout_on in (True, False):
        wl = sd_workload(
            (1, 128, 4, 128, 128), "bfloat16",
            mask=((1, 128, 1, 1, 128), "bfloat16"),
            bias=((1, 1, 4, 128, 128), "bfloat16"),
            dropout_on=dropout_on,
        )
        keys.append(bucket_key(OPS["softmax_dropout"].bucket(wl)))
    return keys


STATIC_VERDICTS = {k: "eager" for k in _static_verdict_keys()}

MODES = ("off", "cache", "tune")

_MODE = os.environ.get("UNICORE_TPU_KERNEL_AUTOTUNE", "cache")
if _MODE not in MODES:  # a typo'd env var must not silently disable tuning
    logger.warning("UNICORE_TPU_KERNEL_AUTOTUNE=%r is not one of %s; "
                   "using 'cache'", _MODE, "/".join(MODES))
    _MODE = "cache"

_CACHE = None
_MEMO = {}
_FORCED = {}


def set_autotune_mode(mode):
    """``off`` | ``cache`` | ``tune`` (see module docstring)."""
    global _MODE
    assert mode in MODES, mode
    _MODE = mode


def autotune_mode():
    return _MODE


def get_cache():
    global _CACHE
    if _CACHE is None:
        import jax

        if jax.process_count() > 1:
            # repo cache only: identical file contents on every host ->
            # identical decisions; per-host overlays could diverge
            _CACHE = TuneCache(paths=[_cache_mod.repo_cache_path()])
        else:
            _CACHE = TuneCache()
    return _CACHE


def reset_memo():
    """Forget memoized decisions (and re-read cache files next lookup).
    Only safe between traces: programs already compiled keep the blocks
    they traced with."""
    _MEMO.clear()
    if _CACHE is not None:
        _CACHE.reload()


def reset(mode=None):
    """Full reset for tests: memo, cache handle, forced overrides."""
    global _CACHE, _MODE
    _MEMO.clear()
    _FORCED.clear()
    _CACHE = None
    if mode is not None:
        _MODE = mode


@contextlib.contextmanager
def use_cache(cache):
    """Temporarily swap the dispatch cache (bench A/B comparisons tune
    into a scratch cache so the persistent overlay is never polluted);
    clears the decision memo on entry and exit so traces inside see
    exactly the swapped layer."""
    global _CACHE
    prev = _CACHE
    _CACHE = cache
    _MEMO.clear()
    try:
        yield cache
    finally:
        _CACHE = prev
        _MEMO.clear()


@contextlib.contextmanager
def forced_config(op_name, config):
    """Pin the decision for ``op_name`` while tracing a tuner candidate
    (must wrap the trace: block choices run at trace time)."""
    prev = _FORCED.get(op_name, _FORCED)  # sentinel: absent
    _FORCED[op_name] = config
    try:
        yield
    finally:
        if prev is _FORCED:
            _FORCED.pop(op_name, None)
        else:
            _FORCED[op_name] = prev


def _can_tune_here():
    import jax

    from unicore_tpu.ops.backend import _on_tpu

    return jax.process_count() == 1 and _on_tpu()


def _decision(op_name, workload, allow_tune=False):
    """The dispatch entry point: ``None`` (use heuristics), ``"eager"``,
    or a config dict.  Never raises.

    ``allow_tune``: whether a tune-mode miss may trigger on-device
    tuning of this bucket.  Only the MODULE-LEVEL dispatch gates pass
    True — their workloads carry the real batch/head extents, which the
    timing needs even though the bucket key drops them (per-program
    fixed costs amortize completely differently on a B=1, H=1 grid).
    Inner consults (``picked_blocks`` synthesizes a degenerate q_shape)
    are lookup-only; a bucket first seen by one simply stays on the
    heuristics this process."""
    if op_name in _FORCED:
        forced = _FORCED[op_name]
        return None if forced == "eager" else forced
    if _MODE == "off":
        return None
    try:
        spec = OPS[op_name]
        key = bucket_key(spec.bucket(workload))
    except Exception:  # noqa: BLE001 - malformed workload -> heuristics
        return None
    if key in _MEMO:
        return _MEMO[key]
    decision = None
    try:
        decision = get_cache().lookup(key)
        if decision is None:
            # committed structural verdicts (see STATIC_VERDICTS): a
            # measured cache entry beats them, the heuristics don't
            decision = STATIC_VERDICTS.get(key)
        if (decision is None and allow_tune and _MODE == "tune"
                and _can_tune_here()):
            from unicore_tpu.ops.tuning.tuner import tune_bucket

            logger.info("autotuning %s (first dispatch of this bucket)", key)
            _, _, entry = tune_bucket(spec, workload, get_cache())
            winner = entry.get("winner")
            decision = winner if (winner == "eager"
                                  or isinstance(winner, dict)) else None
    except Exception as e:  # noqa: BLE001 - fail open to the heuristics
        logger.warning("autotune lookup for %s failed (%s); heuristics",
                       op_name, str(e)[:300])
        decision = None
    _MEMO[key] = decision
    return decision


def describe_decision(op_name, workload):
    """Human-readable decision string for reports/bench: e.g.
    ``"eager[cache]"``, ``"block_q=512,block_k=2048[cache]"``, or
    ``"heuristic"`` when nothing is cached (or mode is off)."""
    d = _decision(op_name, workload)
    if d is None:
        return "heuristic"
    return f"{describe_config(d)}[{_MODE}]"


# ---------------------------------------------------------------------------
# per-op dispatch helpers (thin workload builders over _decision)
# ---------------------------------------------------------------------------


def softmax_dropout_decision(x_shape, dtype, mask=None, bias=None,
                             dropout_on=False, allow_tune=False):
    """mask/bias: (shape, dtype-name) tuples or None."""
    return _decision("softmax_dropout", sd_workload(
        x_shape, dtype, mask=mask, bias=bias, dropout_on=dropout_on,
    ), allow_tune=allow_tune)


def flash_decision(q_shape, kv_len, dtype, bias=None, has_pad=False,
                   causal=False, dropout_on=False, allow_tune=False):
    """q_shape: module layout [B, T, H, D]; bias: (shape4, dtype) or
    None.  Pass ``allow_tune=True`` only with the REAL q_shape (see
    ``_decision``)."""
    return _decision("flash_attention", flash_workload(
        q_shape, kv_len, dtype, bias=bias, has_pad=has_pad, causal=causal,
        dropout_on=dropout_on,
    ), allow_tune=allow_tune)


def tuned_flash_blocks(tq, tk, decision):
    """Validate a cached flash config against the ACTUAL lengths (a
    pow2 bucket can cover lengths its blocks don't divide) and Mosaic's
    tiling rules; None -> use the heuristic."""
    if not isinstance(decision, dict):
        return None
    try:
        bq, bk = int(decision["block_q"]), int(decision["block_k"])
    except (KeyError, TypeError, ValueError):
        return None
    if bq < 8 or bk < 128 or bq % 8 or bk % 128:
        return None
    if bq > tq or bk > tk or tq % bq or tk % bk:
        return None
    return bq, bk


def tuned_q_blk(q, decision):
    """Same validation for a softmax_dropout row-block config."""
    if not isinstance(decision, dict):
        return None
    try:
        blk = int(decision["q_blk"])
    except (KeyError, TypeError, ValueError):
        return None
    if blk < 1 or blk > q or q % blk:
        return None
    return blk


def fused_ce_decision(rows, hidden, vocab, dtype, tied=True, has_bias=True,
                      allow_tune=False):
    """Fused chunked linear+cross-entropy head (ops/fused_cross_entropy):
    ``"eager"`` = unfused materialized logits, ``{"chunk": n}`` = fused
    with that row chunk, None = the op's static byte heuristics."""
    return _decision("fused_cross_entropy", ce_workload(
        rows, hidden, vocab, dtype, tied=tied, has_bias=has_bias,
    ), allow_tune=allow_tune)


def tuned_ce_chunk(rows, decision):
    """Validate a cached fused-CE config against the actual row count
    (chunks need not divide N — the op pads — but a chunk above N is
    just the unchunked program); None -> use the heuristic."""
    if not isinstance(decision, dict):
        return None
    try:
        chunk = int(decision["chunk"])
    except (KeyError, TypeError, ValueError):
        return None
    if chunk < 1:
        return None
    return min(chunk, int(rows))


def sr_cast_decision(n, dtype="float32", allow_tune=False):
    """Stochastic-rounding fp32->bf16 cast (op ``optim_sr_cast``, used
    by the bf16-moment optimizer store and the --bf16-sr master sync):
    ``"eager"`` = the threefry jnp reference, ``{"impl": "pallas"}`` =
    the VMEM-tiled kernel, None = the backend's use_pallas heuristic.
    NOTE the two impls draw from different random streams (threefry vs
    counter-hash) — fine for dispatch because decisions are trace-time
    memoized per process, so one run never mixes streams mid-flight."""
    return _decision("optim_sr_cast", sr_cast_workload(n, dtype),
                     allow_tune=allow_tune)


def ragged_paged_decision(q_shape, table_pages, page_size, dtype,
                          allow_tune=False):
    """Serve-tier unified ragged prefill+decode attention (q_shape
    [B, T, H, D]; T = the engine's prefill-chunk width, 1 for the
    pure-decode dispatch)."""
    return _decision("ragged_paged_attention", ragged_workload(
        q_shape, table_pages, page_size, dtype,
    ), allow_tune=allow_tune)


def tuned_pages_per_block(table_pages, decision):
    """Validate a cached ragged-paged-attention config against the
    actual table width; None -> use the heuristic."""
    if not isinstance(decision, dict):
        return None
    try:
        pp = int(decision["pages_per_block"])
    except (KeyError, TypeError, ValueError):
        return None
    if pp < 1 or pp > table_pages:
        return None
    return pp


def tuned_prefill_chunk(decision, max_chunk):
    """Prefill-chunk width a measured ragged-step verdict recommends
    (a ``{"prefill_chunk": c}`` candidate beat the full-width dispatch
    for the bucket); None -> no measured preference.  Candidates are
    only ever generated BELOW the consulted width, so a verdict above
    ``max_chunk`` is a stale/corrupt cache entry and is rejected — the
    same validation idiom as :func:`tuned_pages_per_block` (silently
    widening the compiled step would destroy the bounded-TTFT property
    the chunk knob exists to guarantee)."""
    if not isinstance(decision, dict):
        return None
    try:
        c = int(decision["prefill_chunk"])
    except (KeyError, TypeError, ValueError):
        return None
    if c < 1 or c > int(max_chunk):
        return None
    return c
