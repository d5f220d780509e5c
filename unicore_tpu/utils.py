"""Framework-wide utilities (TPU/jax-native).

Covers the role of the reference's ``unicore/utils.py`` (tree mapping,
device moves, RNG scoping, user-dir plugin import, activation checkpointing,
tensor helpers used by Uni-Fold) re-designed for jax: tree ops are
``jax.tree_util`` based, RNG scoping is explicit ``jax.random.fold_in``
chains instead of stateful seeds, and device movement is ``jax.device_put``.
"""

import importlib
import logging
import os
import sys
import warnings

import numpy as np

logger = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# Lazy jax import guard: data-pipeline-only users (e.g. preprocessing on a
# CPU box) shouldn't pay jax import cost. Modules that need jax import it
# directly; utils keeps host-side helpers importable stand-alone.
# ---------------------------------------------------------------------------


def _jax():
    import jax

    return jax


def configure_compile_cache():
    """Point jax's persistent compilation cache at a place that stays
    put, and return that place.  Where ``JAX_COMPILATION_CACHE_DIR`` is
    set jax reads it itself and nothing is set here; otherwise the cache
    is ``<checkout>/.jax_cache`` (git-ignored) — a fixed path, because
    the path is part of the cache key and a directory that moves never
    hits.  Called by every entry point before its first compile."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(checkout, ".jax_cache")
    _jax().config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------------
# Tree utilities (reference: apply_to_sample utils.py:38, tree_map :386,
# tensor_tree_map :402)
# ---------------------------------------------------------------------------


def apply_to_sample(f, sample):
    """Apply ``f`` to every array leaf of a nested sample structure."""
    if sample is None or (hasattr(sample, "__len__") and len(sample) == 0):
        return {}

    def _apply(x):
        if isinstance(x, np.ndarray):
            return f(x)
        if type(x).__module__.startswith("jax"):
            return f(x)
        if isinstance(x, dict):
            return {k: _apply(v) for k, v in x.items()}
        if isinstance(x, (list, tuple)):
            return type(x)(_apply(v) for v in x)
        return x

    return _apply(sample)


def tree_map(fn, tree, leaf_type=None):
    if leaf_type is not None and isinstance(tree, leaf_type):
        return fn(tree)
    if isinstance(tree, dict):
        return {k: tree_map(fn, v, leaf_type) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(tree_map(fn, v, leaf_type) for v in tree)
    if leaf_type is None:
        return fn(tree)
    raise ValueError(f"Not supported leaf type {type(tree)}")


def tensor_tree_map(fn, tree):
    return _jax().tree_util.tree_map(fn, tree)


def move_to_device(sample, device=None, sharding=None):
    """Host->device transfer for a sample tree (reference move_to_cuda
    utils.py:59). With a sharding, places the global batch across the mesh."""
    jax = _jax()
    target = sharding if sharding is not None else device

    def _move(x):
        return jax.device_put(x, target) if target is not None else jax.device_put(x)

    return apply_to_sample(_move, sample)


def move_to_cpu(sample, upcast=True):
    """Device->host; bf16/fp16 leaves upcast to fp32 for stable serialization
    (reference utils.py:70-79)."""

    def _move(x):
        x = np.asarray(x)
        if upcast and x.dtype in (np.float16, _ml_dtype("bfloat16")):
            x = x.astype(np.float32)
        return x

    return apply_to_sample(_move, sample)


def _ml_dtype(name):
    import ml_dtypes

    return np.dtype(getattr(ml_dtypes, name))


# ---------------------------------------------------------------------------
# RNG scoping. The reference scopes stateful torch seeds as
# (seed, num_updates, micro_batch, rank) for dropout decorrelation
# (trainer.py:610-616). jax equivalent: fold_in chains on an explicit key.
# ---------------------------------------------------------------------------


def make_rng(seed, *scope):
    """Build a PRNG key deterministically scoped by integers, e.g.
    ``make_rng(seed, num_updates, micro_batch_idx, dp_rank)``."""
    jax = _jax()
    key = jax.random.PRNGKey(seed)
    for s in scope:
        key = jax.random.fold_in(key, s)
    return key


def numpy_seed(seed, *addl_seeds):
    """Context manager that forks the global numpy RNG state. Single source
    of truth lives in data_utils (re-exported here for convenience)."""
    from unicore_tpu.data.data_utils import numpy_seed as _numpy_seed

    return _numpy_seed(seed, *addl_seeds)


# ---------------------------------------------------------------------------
# --user-dir plugin loading (reference utils.py:133-164)
# ---------------------------------------------------------------------------


def import_user_module(args):
    raw_path = getattr(args, "user_dir", None)
    if raw_path is None:
        return
    module_path = os.path.abspath(raw_path)
    if not os.path.exists(module_path):
        # fall back to resolving the *raw* path relative to the package root
        pkg_rel_path = os.path.abspath(
            os.path.join(os.path.dirname(__file__), "..", raw_path)
        )
        if os.path.exists(pkg_rel_path):
            module_path = pkg_rel_path
        else:
            raise FileNotFoundError(module_path)
    module_parent, module_name = os.path.split(module_path)
    if module_name not in sys.modules:
        sys.path.insert(0, module_parent)
        importlib.import_module(module_name)
        sys.path.pop(0)


# ---------------------------------------------------------------------------
# Gradient / parameter norms
# ---------------------------------------------------------------------------


def global_norm(tree):
    """L2 norm over all leaves of a pytree, computed in fp32 (the analogue of
    the reference's multi-tensor L2 norm, utils.py:81-103 — XLA fuses the
    per-leaf reductions into one pass)."""
    jax = _jax()
    import jax.numpy as jnp

    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.zeros((), dtype=jnp.float32)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)
    )


def clip_grad_norm(grads, max_norm):
    """Clip a gradient pytree to a max global norm. Returns (grads, norm).
    max_norm <= 0 means no clipping (norm still computed for logging)."""
    import jax.numpy as jnp

    norm = global_norm(grads)
    if max_norm is None or max_norm <= 0:
        return grads, norm
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-6))
    jax = _jax()
    return jax.tree_util.tree_map(lambda g: (g * scale).astype(g.dtype), grads), norm


# ---------------------------------------------------------------------------
# Activation checkpointing (reference checkpoint_sequential utils.py:296-322)
# ---------------------------------------------------------------------------


def checkpoint_sequential(functions, input_x, enabled=True):
    """Apply a list of fns sequentially, rematerializing each on the backward
    pass when enabled (jax.checkpoint is the TPU-native equivalent)."""
    jax = _jax()
    if enabled:
        functions = [jax.checkpoint(f) for f in functions]
    for f in functions:
        input_x = f(input_x)
    return input_x


# ---------------------------------------------------------------------------
# Tensor helpers used by Uni-Fold-style models (reference utils.py:325-383)
# ---------------------------------------------------------------------------


def permute_final_dims(tensor, inds):
    import jax.numpy as jnp

    zero_index = -1 * len(inds)
    first_inds = list(range(tensor.ndim + zero_index))
    return jnp.transpose(tensor, first_inds + [zero_index + i for i in inds])


def flatten_final_dims(tensor, num_dims):
    return tensor.reshape(tensor.shape[:-num_dims] + (-1,))


def masked_mean(mask, value, axis, eps=1e-10):
    import jax.numpy as jnp

    mask = mask.astype(value.dtype)
    return jnp.sum(mask * value, axis=axis) / (eps + jnp.sum(mask, axis=axis))


def one_hot(x, num_classes, dtype=None):
    import jax

    return jax.nn.one_hot(x, num_classes, dtype=dtype)


def batched_gather(data, inds, axis=0, num_batch_dims=0):
    import jax.numpy as jnp

    assert axis < 0 or axis - num_batch_dims >= 0
    ranges = []
    for i, s in enumerate(data.shape[:num_batch_dims]):
        r = jnp.arange(s)
        r = r.reshape(*(*((1,) * i), -1, *((1,) * (len(inds.shape) - i - 1))))
        ranges.append(r)
    remaining_dims = [slice(None) for _ in range(len(data.shape) - num_batch_dims)]
    remaining_dims[axis - num_batch_dims if axis >= 0 else axis] = inds
    ranges.extend(remaining_dims)
    return data[tuple(ranges)]


def causal_iota_mask(tq, tk, neg=-1e30, dtype=None):
    """Additive ``[tq, tk]`` causal mask from iota compares — XLA fuses
    the comparison into the consumer, so no ``[T, T]`` buffer ever lives
    in HBM (a ``jnp.triu(jnp.full(...))`` is 256 MB fp32 at T=8192).
    ``neg`` defaults to a large finite value (a literal -inf NaNs any
    softmax row that ends up fully masked).  Shared by the materialized
    attention fallback and the Ulysses local attention.

    Alignment is BOTTOM-RIGHT (query i attends keys ``<= i + tk - tq``):
    for ``tq == tk`` this is the ordinary causal triangle; for ``tq < tk``
    (KV-cache incremental decode, where the queries are the LAST ``tq``
    positions of the key stream) each query still sees exactly its own
    prefix — top-left alignment would silently widen it."""
    import jax
    import jax.numpy as jnp

    rows = jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    m = jnp.where(cols > rows + (tk - tq), neg, 0.0)
    return m if dtype is None else m.astype(dtype)


# ---------------------------------------------------------------------------
# Misc host helpers
# ---------------------------------------------------------------------------


def get_host_memory_gb():
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable"):
                    return int(line.split()[1]) / 1024 / 1024
    except OSError:
        pass
    return None


def eval_str_list(x, type=float):
    """Parse ``"(0.9, 0.999)"`` / ``"[1e-4]"`` / ``"0.5"`` into a typed list.
    Uses ``ast.literal_eval`` — CLI input must never execute code."""
    import ast

    if x is None:
        return None
    if isinstance(x, str):
        x = ast.literal_eval(x)
    try:
        return list(map(type, x))
    except TypeError:
        return [type(x)]


def eval_bool(x, default=False):
    """Parse a boolean-ish CLI/config value.  Text matching, NOT eval():
    CLI input must never execute code, ``"false"``/``"False"``/``"0"``
    must all mean False, and unknown text falls back to ``default``."""
    if x is None:
        return default
    if isinstance(x, bool):
        return x
    s = str(x).strip().lower()
    if s in ("true", "t", "yes", "y", "1"):
        return True
    if s in ("false", "f", "no", "n", "0", ""):
        return False
    return default


def arg_bool(x):
    """STRICT boolean argparse type: unknown text raises instead of
    silently falling back (``--some-flag Ture`` must not parse as False,
    and a positional path accidentally bound to a ``nargs='?'`` bool flag
    must error loudly)."""
    import argparse

    if isinstance(x, bool):
        return x
    s = str(x).strip().lower()
    if s in ("true", "t", "yes", "y", "1"):
        return True
    if s in ("false", "f", "no", "n", "0"):
        return False
    raise argparse.ArgumentTypeError(f"expected a boolean, got {x!r}")


def has_parameters(obj):
    """True when a loss/task carries trainable parameters of its own."""
    params = getattr(obj, "params", None)
    return params is not None and len(_jax().tree_util.tree_leaves(params)) > 0


def warn_once(msg, _seen=set()):
    if msg not in _seen:
        _seen.add(msg)
        warnings.warn(msg)


def get_activation_fn(activation):
    """Activation by name (reference: unicore/utils.py:166-178)."""
    import jax
    import jax.numpy as jnp

    fns = {
        # torch F.gelu is the exact (erf) variant
        "gelu": lambda x: jax.nn.gelu(x, approximate=False),
        "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
        "relu": jax.nn.relu,
        "tanh": jnp.tanh,
        "silu": jax.nn.silu,
        "linear": lambda x: x,
    }
    if activation not in fns:
        raise RuntimeError(f"--activation-fn {activation} not supported")
    return fns[activation]


def tree_map_arrays(fn, tree):
    """Map ``fn`` over array leaves (numpy / jax / scalars with shape),
    passing other leaves through unchanged."""
    import numpy as _np

    jax = _jax()

    def _apply(x):
        if hasattr(x, "shape") or isinstance(x, (_np.generic, int, float)):
            return fn(x)
        return x

    return jax.tree_util.tree_map(_apply, tree)
