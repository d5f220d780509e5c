"""LayerNorm with fp32 statistics.

Behavioral spec from the reference (``unicore/modules/layer_norm.py:22-83``,
``csrc/layernorm/layernorm.cu``): normalize over the last dim with fp32
statistics (mean/invvar computed in fp32 even for bf16/fp16 inputs), affine
weight/bias stored fp32 and cast to the input dtype for the multiply.

NO Pallas kernel — a deliberate, measured decision (r5).  The reference
ships a fused CUDA LayerNorm because eager torch materializes the
unfused chain; XLA already fuses the whole normalize+affine into one
loop over the row, and the custom kernel NEVER durably beat it at
transformer shapes in the rounds that had a chip (0.875x and 0.671x at
[32*512, 768] bf16; those records are gone and nothing has been measured
on this machine).  The r4 single-pass backward, multi-row grid blocks, and
bf16-I/O variants were all tried on hardware and none closed a 1.5x gap
rooted in XLA's fusion simply being the right program for a
bandwidth-bound row reduction.  The kernel and its timed-dispatch gate
are deleted; ``layer_norm`` IS the fp32-stats jnp formulation, which XLA
fuses optimally on TPU.  (See docs/performance.md for the measurement
history.)
"""

import jax.numpy as jnp


def layer_norm_reference(x, weight=None, bias=None, eps=1e-5):
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    inv = jnp.reciprocal(jnp.sqrt(var + eps))
    out = (xf - mean) * inv
    out = out.astype(dtype)
    if weight is not None:
        out = out * weight.astype(dtype)
    if bias is not None:
        out = out + bias.astype(dtype)
    return out


# one implementation: XLA's fusion is the fast path (see module docstring)
layer_norm = layer_norm_reference
