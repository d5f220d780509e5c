"""Seeded weights, made on the device in one jitted call.

The program supplies only the NAMES and SHAPES of its parameter tree
(``jax.eval_shape`` of its init, or the tree a trainer built); every
value comes from here, from ``--seed``, in the type the tree states.
The program and its plain reference are both handed the result of
:func:`make`, so neither takes anything the other has made.

Every leaf is drawn, biases and LayerNorm gains too, so that a fault in
how either is applied shows in the comparison: matrices, embeddings and
biases from N(0, 0.02^2) (BERT's and OPT's ``initializer_range`` /
``init_std``), a one-dimensional leaf called ``weight`` (a LayerNorm
gain) from 1 + N(0, 0.02^2).  A configuration may scale the spread of
named leaves (``scales``: ``{name in the leaf's path: factor}``), where
the plain draw makes a model whose outputs no comparison can tell apart.
"""

import jax
import jax.numpy as jnp

STD = 0.02


def fold_seed(seed):
    """A key for any whole number up to a little over 2**31 (and beyond):
    the low 31 bits seed the key, the rest is folded in."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def abstract_of(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _names(path):
    return [getattr(p, "key", getattr(p, "name", str(p))) for p in path]


def _is_gain(path, leaf):
    return _names(path)[-1] == "weight" and len(leaf.shape) == 1


def make(abstract, seed, shardings=None, scales=None):
    """The tree ``abstract`` filled from ``seed``.  One jitted call; with
    ``shardings`` (a tree of the same structure) each leaf is created
    where it will live."""
    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    scales = scales or {}

    def build(key):
        out = []
        for i, (path, leaf) in enumerate(paths_leaves):
            std = STD
            for name, factor in scales.items():
                if name in _names(path):
                    std *= factor
            draw = std * jax.random.normal(
                jax.random.fold_in(key, i), leaf.shape, jnp.float32)
            if _is_gain(path, leaf):
                draw = 1.0 + draw
            out.append(draw.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    fn = jax.jit(build, out_shardings=shardings) if shardings is not None \
        else jax.jit(build)
    return fn(fold_seed(seed))


def as_dict(tree):
    """Plain nested dicts (a reference indexes by name)."""
    if hasattr(tree, "items"):
        return {k: as_dict(v) for k, v in tree.items()}
    return tree
