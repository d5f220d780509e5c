"""The precisions a reference can compute in.

``fp32`` is the plain reference: float32 operands, matmuls at
``highest`` (on a TPU a float32 matmul otherwise runs in lower
precision).  The others exist for the controls, the reference put in the
program's place one precision below what a configuration states:

- ``bf16``: weights, activations and cache in bfloat16 (float32
  accumulation inside a matmul, float32 statistics in softmax and
  LayerNorm, as every bf16 program keeps them);
- ``fp8``: as ``bf16``, and both operands of every matmul rounded to
  float8 e4m3 with one scale per tensor (amax / 448).
"""

import jax
import jax.numpy as jnp

PRECISIONS = ("fp32", "bf16", "fp8")


def act_dtype(precision):
    return jnp.float32 if precision == "fp32" else jnp.bfloat16


@jax.custom_vjp
def _fp8(x):
    """Round to float8 e4m3 with one scale per tensor.  Straight through
    in the backward pass: the cotangent is not quantised (an unscaled
    float8 cast would flush a gradient to zero and make the control fail
    for a reason no fp8 program would)."""
    x32 = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x32)), 1e-30) / 448.0
    q = (x32 / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return (q * scale).astype(jnp.bfloat16)


_fp8.defvjp(lambda x: (_fp8(x), jnp.zeros((), x.dtype)),
            lambda res, g: (g.astype(res.dtype),))


def einsum(spec, a, b, precision):
    """``jnp.einsum`` of two operands in the named precision; the result
    comes back in that precision's activation type."""
    if precision == "fp32":
        return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    out = jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return out.astype(jnp.bfloat16)


def layer_norm(x, weight, bias, eps=1e-5):
    """Statistics in float32 whatever the activation type."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    out = ((xf - mean) / jnp.sqrt(var + eps)).astype(dtype)
    return out * weight.astype(dtype) + bias.astype(dtype)


def softmax(scores):
    """Over the last axis, in float32."""
    return jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
