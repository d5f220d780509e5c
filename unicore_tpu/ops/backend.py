"""Kernel backend selection.

The reference gates each CUDA extension on import success + compute
capability >= 7 (``unicore/utils.py:18-34``).  The TPU analogue: the Pallas
path is eligible when the default jax backend is TPU; tests force either
backend explicitly (the ``jnp`` implementations are the oracles).
"""

import contextlib
import functools
import logging

logger = logging.getLogger(__name__)

_BACKEND = "auto"  # auto | pallas | reference


def set_kernel_backend(name):
    """Force the kernel backend: ``auto`` (default), ``pallas``, or
    ``reference``."""
    global _BACKEND
    assert name in ("auto", "pallas", "reference"), name
    _BACKEND = name
    _on_tpu.cache_clear()


def get_kernel_backend():
    return _BACKEND


@contextlib.contextmanager
def kernel_backend(name):
    prev = _BACKEND
    set_kernel_backend(name)
    try:
        yield
    finally:
        set_kernel_backend(prev)


@functools.lru_cache(None)
def _on_tpu():
    import jax

    return jax.default_backend() == "tpu"


def use_pallas():
    """Whether an op should take its Pallas kernel path."""
    if _BACKEND == "pallas":
        return True
    if _BACKEND == "reference":
        return False
    return _on_tpu()


def pallas_interpret():
    """Interpret-mode setting for pallas_call: off-TPU (CPU tests) return
    TPU InterpretParams so TPU-specific primitives (prng_seed,
    stochastic_round, ...) are emulated; on TPU compile normally."""
    if _on_tpu():
        return False
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.InterpretParams()


# The multi-device mesh of the GSPMD-partitioned step being traced, or
# None.  Mosaic kernels cannot be partitioned automatically (the TPU
# lowering raises "wrap the call in a shard_map"), so under such a mesh
# a dispatch site either runs its kernel per shard through shard_map
# (flash attention, over the batch axes) or takes the reference path,
# which XLA partitions like any other op.  The Trainer sets it next to
# the sequence/tensor-parallel contexts; the serve engine is one device.
_SPMD_MESH = None


def set_spmd_mesh(mesh):
    global _SPMD_MESH
    _SPMD_MESH = mesh if mesh is not None and mesh.devices.size > 1 else None


def spmd_mesh():
    return _SPMD_MESH


def needs_shard_map():
    """Whether a bare ``pallas_call`` traced now would hand GSPMD a
    Mosaic kernel to partition (interpret mode lowers to plain XLA ops,
    which it partitions)."""
    return _SPMD_MESH is not None and not pallas_interpret()


# op -> {shape description -> "pallas" | "reference"}: the path every
# dispatch site took at trace time.  There is no compile probe and no
# fallback behind it — a kernel the chip's compiler refuses fails the
# compile of the step that holds it — so this is a faithful account of
# which kernels are in the compiled programs.
_DISPATCH = {}


def note_dispatch(op, desc, took_kernel):
    """Record (and log once) which path ``op`` took for the shape
    ``desc``; returns ``took_kernel`` so a dispatch site can wrap its
    verdict."""
    path = "pallas" if took_kernel else "reference"
    seen = _DISPATCH.setdefault(op, {})
    if seen.get(desc) != path:
        seen[desc] = path
        logger.info("kernel dispatch: %s [%s] -> %s", op, desc, path)
    return took_kernel


def dispatch_report():
    """A copy of the dispatch record, for reports and logs."""
    return {op: dict(seen) for op, seen in _DISPATCH.items()}
