"""Fused bias+mask+softmax+dropout.

Behavioral spec from the reference (``unicore/modules/softmax_dropout.py:100-144``
and the CUDA kernel ``csrc/softmax_dropout/softmax_dropout_kernel.cu``):

    out = dropout(softmax(input + mask + bias), p)

- ``mask``/``bias`` are additive and broadcast against ``input`` — including
  the 5-D triangle-attention patterns Uni-Fold needs (masks ``[b,g,1,1,k]`` /
  ``[b,g,h,1,k]``, biases ``[1,1,h,q,k]`` / ``[1,g,h,q,k]``; see
  ``tests/test_softmax.py:81-170`` in the reference).  jax/numpy broadcasting
  subsumes the reference's ``_check_mask``/``_check_bias`` stride tricks.
- The softmax reduction runs in fp32 regardless of input dtype (the CUDA
  kernel's ``acc_t``), output is cast back to the input dtype.
- The CUDA kernel's in-place softmax + bit-packed dropout mask are memory
  optimizations for *storing* the residuals; under XLA the analogous saving
  comes from fusion + rematerialization, and the Pallas kernel recomputes in
  the backward instead of storing a packed mask.

The reference's eager fallback ``F.dropout(F.softmax(...))`` is exactly
``softmax_dropout_reference`` below.
"""

import jax
import jax.numpy as jnp

from .backend import needs_shard_map, use_pallas


def softmax_dropout_reference(
    x,
    dropout_prob,
    rng=None,
    is_training=True,
    mask=None,
    bias=None,
    return_softmax=False,
):
    """Plain-jnp spec: ``dropout(softmax(x + mask + bias))``."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    if mask is not None:
        x = x + mask.astype(jnp.float32)
    if bias is not None:
        x = x + bias.astype(jnp.float32)
    sm = jax.nn.softmax(x, axis=-1).astype(dtype)
    out = sm
    if is_training and dropout_prob > 0.0:
        if rng is None:
            raise ValueError("softmax_dropout: rng required when training with dropout")
        keep = 1.0 - dropout_prob
        keep_mask = jax.random.bernoulli(rng, keep, shape=out.shape)
        out = jnp.where(keep_mask, out / keep, jnp.zeros_like(out)).astype(dtype)
    if return_softmax:
        return out, sm
    return out


def softmax_dropout(
    x,
    dropout_prob,
    rng=None,
    is_training=True,
    mask=None,
    bias=None,
    return_softmax=False,
):
    """Fused softmax+dropout; dispatches to the Pallas kernel on TPU when the
    shape is eligible, else the jnp reference (which XLA fuses well anyway).
    Under a multi-device mesh it is always the reference: GSPMD partitions
    that, and cannot partition a Mosaic kernel.

    Under the auto backend the choice is :func:`_heuristic_kernel_win`'s
    elements-per-program gate, a function of the shapes alone: the same
    shapes always trace the same program.  A forced ``"pallas"`` backend
    always takes the kernel (the parity/test override)."""
    if (use_pallas() and not needs_shard_map() and not return_softmax
            and _pallas_eligible(x, mask, bias)):
        from .backend import get_kernel_backend, note_dispatch
        from .pallas import softmax_dropout as pl_impl

        dropout_on = is_training and float(dropout_prob) > 0.0
        take_kernel = (get_kernel_backend() == "pallas"
                       or _heuristic_kernel_win(x, mask, bias))
        desc = "x%s %s mask=%s bias=%s dropout=%s" % (
            tuple(x.shape), x.dtype.name,
            None if mask is None else tuple(mask.shape),
            None if bias is None else tuple(bias.shape), dropout_on,
        )
        if note_dispatch("softmax_dropout", desc, take_kernel):
            return pl_impl.softmax_dropout(
                x, dropout_prob, rng=rng, is_training=is_training,
                mask=mask, bias=bias,
            )
    return softmax_dropout_reference(
        x,
        dropout_prob,
        rng=rng,
        is_training=is_training,
        mask=mask,
        bias=bias,
        return_softmax=return_softmax,
    )


def _heuristic_kernel_win(x, mask, bias):
    """Static crossover gate: the kernel pays a fixed cost per grid
    program plus its streaming setup, so when each program's row block
    is small the eager XLA fusion wins and the kernel must NOT lower.
    The gate is elements per program (row_block x k): the 5-D evoformer
    shape (batched mask/bias, 128x128 blocks, 512 programs, 16K elements
    each) sits below the 64K threshold, the BERT and k=2048 shapes (131K
    elements per program) above it.  Where the crossover really lies has
    not been measured on this machine."""
    from .pallas.softmax_dropout import _pick_q_blk_for

    return _pick_q_blk_for(x, mask, bias) * x.shape[-1] >= (1 << 16)


def _pallas_eligible(x, mask, bias):
    # Lane-dim constraint: the kernel tiles the softmax axis into VMEM; keep
    # to 128-multiples and bounded row length (mirrors the reference kernel's
    # k <= 2048 warp/block split, softmax_fast.h:470-508).  Operands
    # broadcast over the k axis are NOT supported by the kernel's BlockSpec
    # layout (full-k blocks) — those fall back to the jnp reference.
    k = x.shape[-1]
    if not (k % 128 == 0 and k <= 8192 and x.ndim >= 2):
        return False
    for op in (mask, bias):
        if op is not None and op.shape[-1] != k:
            return False
    return True
