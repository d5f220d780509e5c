"""Stochastic rounding fp32 -> bf16.

Bit-exact analogue of the reference CUDA kernel
(``csrc/rounding/fp32_to_bf16.cu:30-38``): add a uniform 16-bit random value
to the fp32 bit pattern, then truncate the mantissa (round-toward-zero into
bf16).  Used when syncing the fp32 master copy back to bf16 params under
``--bf16-sr`` (``unicore/optim/fp16_optimizer.py:146-148``).

The jnp reference uses ``jax.random.bits`` (threefry); the Pallas kernel
(``ops/pallas/rounding.py``) uses the counter-hash PRNG and tiles through
VMEM — same rounding math, different random streams.  ``use_pallas()``
selects between them.
"""

import jax
import jax.numpy as jnp

from .backend import needs_shard_map, note_dispatch, use_pallas


def fp32_to_bf16_sr_reference(x, rng):
    x32 = x.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(x32, jnp.uint32)
    noise = jax.random.bits(rng, shape=x32.shape, dtype=jnp.uint32) & jnp.uint32(0xFFFF)
    # NaN/Inf must pass through unperturbed (the CUDA kernel's
    # __float2bfloat16_rz on a finite+noise value can't overflow the
    # exponent because the add below is capped by the carry into bit 16).
    rounded = bits + noise
    rounded = jnp.where(jnp.isfinite(x32), rounded, bits)
    truncated = rounded & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(truncated, jnp.float32).astype(jnp.bfloat16)


def fp32_to_bf16_sr(x, rng):
    # under a multi-device mesh GSPMD partitions the reference and cannot
    # partition a Mosaic kernel.  The two impls draw different random
    # streams; the choice reads only the mesh and the backend, so one
    # process never mixes them.
    take_kernel = not needs_shard_map() and use_pallas()
    if note_dispatch("fp32_to_bf16_sr", "n%d" % x.size, take_kernel):
        from .pallas import rounding as pl_impl

        return pl_impl.fp32_to_bf16_sr(x, rng)
    return fp32_to_bf16_sr_reference(x, rng)
