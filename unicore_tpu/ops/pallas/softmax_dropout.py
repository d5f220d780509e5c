"""Fused bias+mask+softmax+dropout Pallas kernel.

TPU-native analogue of ``csrc/softmax_dropout/softmax_dropout_kernel.cu``.
Differences by design:

- The CUDA kernel stores a bit-packed dropout mask for the backward; here the
  backward *recomputes* the mask from the same PRNG seed (TPU PRNG is cheap,
  HBM bandwidth is not — recompute beats store on TPU).
- The CUDA kernel is in-place to save the ``[B*H, q, k]`` activation copy;
  the Pallas forward saves only the softmax result (same residual set as the
  reference: ``SoftmaxDropoutFast`` saves softmax_results + packed mask).
- Broadcast masks/biases (the 5-D triangle-attention contracts of
  ``_check_mask``/``_check_bias``) are expressed through BlockSpec index
  maps: broadcast dims pin block index 0 with block size 1, and in-kernel
  jnp broadcasting does the rest.

Grid: one program per (leading-dims..., q-block); each program owns full
softmax rows (``[q_blk, k]`` in VMEM), so the reduction never crosses
programs — mirroring the warp-per-row design of ``softmax_fast.h``.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from unicore_tpu.ops.backend import pallas_interpret
from unicore_tpu.ops.pallas.prng import keep_mask


def _pick_q_blk(q, k, n_streams=4, itemsize=4):
    """Row-block size bounded by the Mosaic scoped-VMEM stack: every
    stream (inputs + outputs) is double-buffered across grid steps, so
    the stack holds ``2 * n_streams`` blocks of ``q_blk x k`` at once.
    The 6MB budget keeps well under the 16MB limit (measured: 4 fp32
    streams at k=2048 with the old fixed element budget stacked 17.83M
    and failed to compile)."""
    budget_bytes = 6 << 20
    denom = max(1, 2 * n_streams * k * itemsize)
    blk = min(q, max(8, budget_bytes // denom))
    for cand in (256, 128, 64, 32, 16, 8, 1):
        if cand <= blk and q % cand == 0:
            return cand
    return 1


def _softmax_rows(x):
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    return e / jnp.sum(e, axis=-1, keepdims=True)




def _program_seed(seed_ref, n_grid):
    pid = 0
    for d in range(n_grid):
        pid = pid * pl.num_programs(d) + pl.program_id(d)
    return seed_ref[0] + pid


def _fwd_kernel(seed_ref, x_ref, *rest, has_mask, has_bias, dropout_prob,
                n_grid, save_softmax):
    refs = list(rest)
    mask_ref = refs.pop(0) if has_mask else None
    bias_ref = refs.pop(0) if has_bias else None
    out_ref = refs.pop(0)
    sm_ref = refs.pop(0) if save_softmax else None

    x = x_ref[...].astype(jnp.float32)
    if mask_ref is not None:
        x = x + mask_ref[...].astype(jnp.float32)
    if bias_ref is not None:
        x = x + bias_ref[...].astype(jnp.float32)
    y = _softmax_rows(x)
    if sm_ref is not None:
        sm_ref[...] = y.astype(sm_ref.dtype)
    if dropout_prob > 0.0:
        keep_prob = 1.0 - dropout_prob
        keep = keep_mask(_program_seed(seed_ref, n_grid), y.shape, keep_prob)
        y = jnp.where(keep, y * (1.0 / keep_prob), 0.0)
    out_ref[...] = y.astype(out_ref.dtype)


def _bwd_kernel(seed_ref, g_ref, sm_ref, dx_ref, *, dropout_prob, n_grid):
    g = g_ref[...].astype(jnp.float32)
    y = sm_ref[...].astype(jnp.float32)
    if dropout_prob > 0.0:
        keep_prob = 1.0 - dropout_prob
        keep = keep_mask(_program_seed(seed_ref, n_grid), g.shape, keep_prob)
        g = jnp.where(keep, g * (1.0 / keep_prob), 0.0)
    # d softmax: dz = y * (g - sum(g * y))
    dx = y * (g - jnp.sum(g * y, axis=-1, keepdims=True))
    dx_ref[...] = dx.astype(dx_ref.dtype)


def _canon(x, mask, bias):
    """Pad mask/bias to x.ndim with leading 1s (jnp broadcast alignment)."""

    def pad(a):
        if a is None:
            return None
        return a.reshape((1,) * (x.ndim - a.ndim) + a.shape)

    return pad(mask), pad(bias)


_SEED_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _x_spec(shape, n_lead, q_blk):
    k = shape[-1]

    def imap(*pids):
        return tuple(pids[:n_lead]) + (pids[-1], 0)

    return pl.BlockSpec((1,) * n_lead + (q_blk, k), imap, memory_space=pltpu.VMEM)


def _bcast_spec(shape, n_lead, q_blk, k):
    """BlockSpec for a mask/bias broadcast against x [lead..., q, k]."""
    blk = tuple(1 for _ in range(n_lead)) + (
        1 if shape[-2] == 1 else q_blk,
        k,
    )

    def imap(*pids):
        idx = [0 if shape[d] == 1 else pids[d] for d in range(n_lead)]
        idx.append(0 if shape[-2] == 1 else pids[-1])
        idx.append(0)
        return tuple(idx)

    return pl.BlockSpec(blk, imap, memory_space=pltpu.VMEM)


def _grid_of(shape, q_blk):
    n_lead = len(shape) - 2
    return tuple(shape[:n_lead]) + (shape[-2] // q_blk,)


def _pick_q_blk_for(x, mask, bias):
    """ONE q-block size for the forward (with or without grad) and the
    backward: the per-program dropout seed and mask shape depend on the
    grid, so every pass MUST tile identically or the backward would drop
    different elements than the forward did.  Streams are counted for the
    widest pass (grad-mode forward: x, out, sm + mask/bias); the backward
    (g, sm, dx) needs no more."""
    n_streams = (
        3  # x, out, saved softmax
        + (1 if mask is not None else 0)
        + (1 if bias is not None else 0)
    )
    return _pick_q_blk(x.shape[-2], x.shape[-1], n_streams=n_streams,
                       itemsize=x.dtype.itemsize)


def _softmax_dropout_fwd_impl(x, mask, bias, dropout_prob, q_blk, seed,
                              save_softmax):
    n_lead = x.ndim - 2
    k = x.shape[-1]
    grid = _grid_of(x.shape, q_blk)
    xs = _x_spec(x.shape, n_lead, q_blk)
    in_specs = [_SEED_SPEC, xs]
    args = [jnp.atleast_1d(jnp.asarray(seed, dtype=jnp.int32)), x]
    for op in (mask, bias):
        if op is not None:
            in_specs.append(_bcast_spec(op.shape, n_lead, q_blk, k))
            args.append(op)
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    out_specs = [xs]
    if save_softmax:
        out_shape.append(jax.ShapeDtypeStruct(x.shape, x.dtype))
        out_specs.append(xs)
    kernel = functools.partial(
        _fwd_kernel,
        has_mask=mask is not None,
        has_bias=bias is not None,
        dropout_prob=dropout_prob,
        n_grid=len(grid),
        save_softmax=save_softmax,
    )
    results = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=pallas_interpret(),
        name="softmax_dropout_fwd",
        compiler_params=pltpu.CompilerParams(
            # every softmax row block is independent
            dimension_semantics=("parallel",) * len(grid),
        ),
    )(*args)
    if save_softmax:
        return results[0], results[1]
    return results[0], None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _softmax_dropout_p(x, mask, bias, dropout_prob, q_blk, seed):
    out, _ = _softmax_dropout_fwd_impl(
        x, mask, bias, dropout_prob, q_blk, seed, save_softmax=False
    )
    return out


def _fwd(x, mask, bias, dropout_prob, q_blk, seed):
    out, sm = _softmax_dropout_fwd_impl(
        x, mask, bias, dropout_prob, q_blk, seed, save_softmax=True
    )
    return out, (sm, seed, None if mask is None else mask.shape,
                 None if bias is None else bias.shape)


def _bwd(dropout_prob, q_blk, residuals, g):
    sm, seed, mask_shape, bias_shape = residuals
    x_shape = sm.shape
    n_lead = sm.ndim - 2
    grid = _grid_of(x_shape, q_blk)
    xs = _x_spec(x_shape, n_lead, q_blk)
    dx = pl.pallas_call(
        functools.partial(
            _bwd_kernel, dropout_prob=dropout_prob, n_grid=len(grid)
        ),
        grid=grid,
        in_specs=[_SEED_SPEC, xs, xs],
        out_specs=[xs],
        out_shape=[jax.ShapeDtypeStruct(x_shape, sm.dtype)],
        interpret=pallas_interpret(),
        name="softmax_dropout_bwd",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * len(grid),
        ),
    )(jnp.atleast_1d(jnp.asarray(seed, dtype=jnp.int32)), g, sm)[0]

    def reduce_to(shape):
        if shape is None:
            return None
        axes = tuple(
            i for i, (s, xs_) in enumerate(zip(shape, dx.shape)) if s == 1 and xs_ != 1
        )
        r = jnp.sum(dx.astype(jnp.float32), axis=axes, keepdims=True)
        return r.reshape(shape).astype(dx.dtype)

    return dx, reduce_to(mask_shape), reduce_to(bias_shape), None


_softmax_dropout_p.defvjp(_fwd, _bwd)


def softmax_dropout(x, dropout_prob, rng=None, is_training=True, mask=None,
                    bias=None):
    """Entry point matching ``ops.softmax_dropout`` (minus return_softmax)."""
    mask, bias = _canon(x, mask, bias)
    p = float(dropout_prob) if is_training else 0.0
    if p > 0.0:
        if rng is None:
            raise ValueError("softmax_dropout: rng required when training with dropout")
        seed = jax.random.randint(rng, (1,), 0, 2**31 - 1, dtype=jnp.int32)
    else:
        seed = jnp.zeros((1,), dtype=jnp.int32)
    q_blk = _pick_q_blk_for(x, mask, bias)
    return _softmax_dropout_p(x, mask, bias, p, q_blk, seed)
