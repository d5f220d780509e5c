"""Stochastic rounding fp32 -> bf16 Pallas kernel.

Bit-exact analogue of ``csrc/rounding/fp32_to_bf16.cu:30-38``: add 16 random
bits below the bf16 mantissa boundary to the fp32 bit pattern, truncate
(round-toward-zero into bf16).  Random bits come from the portable
counter-hash PRNG (see ``prng.py``), so the kernel behaves identically
compiled and interpreted.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from unicore_tpu.ops.backend import pallas_interpret
from unicore_tpu.ops.pallas.prng import random_bits

_LANE = 1024
_SUBLANE = 8


def pick_layout(n):
    """(rows, r_blk) for an n-element flat array: rows padded to a sublane
    multiple of [rows, _LANE] tiles, block = 256 rows when divisible else
    one sublane.  Shared by the kernel and the dispatch wrapper's
    compile-probe so the probed BlockSpec can never drift from the real
    one."""
    rows = -(-n // _LANE)
    rows = -(-rows // _SUBLANE) * _SUBLANE
    r_blk = 256 if rows % 256 == 0 else _SUBLANE
    return rows, r_blk


def _kernel(seed_ref, x_ref, out_ref):
    x = x_ref[...]
    seed = seed_ref[0] + pl.program_id(0)
    noise = random_bits(seed, x.shape) & jnp.uint32(0xFFFF)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    rounded = jnp.where(jnp.isfinite(x), bits + noise, bits)
    truncated = rounded & jnp.uint32(0xFFFF0000)
    out_ref[...] = jax.lax.bitcast_convert_type(truncated, jnp.float32).astype(
        jnp.bfloat16
    )


def fp32_to_bf16_sr(x, rng):
    shape = x.shape
    n = x.size
    # pad to [rows, _LANE] with rows a sublane multiple for clean tiling
    rows, r_blk = pick_layout(n)
    flat = jnp.zeros((rows * _LANE,), dtype=jnp.float32).at[:n].set(
        x.astype(jnp.float32).ravel()
    )
    x2d = flat.reshape(rows, _LANE)
    seed = jax.random.randint(rng, (1,), 0, 2**31 - 1, dtype=jnp.int32)
    out = pl.pallas_call(
        _kernel,
        grid=(rows // r_blk,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((r_blk, _LANE), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((r_blk, _LANE), lambda i: (i, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, _LANE), jnp.bfloat16),
        interpret=pallas_interpret(),
        name="fp32_to_bf16_sr",
    )(seed, x2d)
    return out.ravel()[:n].reshape(shape)
