"""Shared dropout-seed helpers for the sequence-parallel schemes: ring
and Ulysses must fold the SAME batch-shard identity into their streams or
their shard decorrelation rules drift apart."""

import jax
import jax.numpy as jnp


def batch_shard_index(batch_axes):
    """Linear index of this device's batch shard over the batch axes (0
    when the batch is unsharded) — folded into dropout seeds so
    data-sharded shards draw decorrelated masks.  Only valid inside
    shard_map."""
    lin = 0
    for ax in (batch_axes or ()):
        lin = lin * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    return lin


def require_dropout_rng(dropout_p, rng, who):
    """Derive the replicated base seed for attention dropout; a missing
    rng with dropout on is an ERROR, not a silent skip (the exact
    unregularized-training failure the r2/r3 escape hatch existed to
    surface — flash_attention raises the same way)."""
    if dropout_p <= 0.0:
        return None
    if rng is None:
        raise ValueError(
            f"{who}: rng is required when dropout_p > 0 (attention "
            f"dropout is implemented; it must not silently skip)"
        )
    return jax.random.randint(rng, (), 0, 2 ** 31 - 1, jnp.int32)
