"""Flash (blockwise, online-softmax) attention Pallas kernel.

The long-context replacement for materialized ``[B,H,Tq,Tk]`` attention —
new capability relative to the reference, whose attention is plain
``torch.bmm`` over full sequences (``unicore/modules/multihead_attention.py:83``,
SURVEY §5.7).  Design:

- additive bias (e.g. the T5 rel-pos bias, broadcastable over batch) and the
  key-padding mask are SEPARATE inputs, so the combined ``[B,H,Tq,Tk]``
  tensor is never built;
- attention dropout rides inside the kernel via the counter-hash PRNG
  (``prng.py``); the backward recomputes the identical mask;
- backward is recompute-based (saves only out + logsumexp), split into a
  dq pass and a dkv pass, with dbias accumulated across the sequential TPU
  grid;
- online softmax carries (m, l, acc) in VMEM scratch across the k-block
  grid dimension (TPU grids execute sequentially).

Layout: [B, H, T, D] inside the kernel; the public wrapper takes the
module-standard [B, T, H, D].
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from unicore_tpu.ops.backend import pallas_interpret
from unicore_tpu.ops.pallas.prng import keep_mask

NEG_INF = -1e30


def _bias_spec(bias_shape, block_q, block_k):
    """BlockSpec for a bias broadcastable to [B, H, Tq, Tk]."""
    bB, bH, bQ, bK = bias_shape

    def imap(b, h, i, j):
        return (
            0 if bB == 1 else b,
            0 if bH == 1 else h,
            0 if bQ == 1 else i,
            j,
        )

    blk = (1, 1, 1 if bQ == 1 else block_q, block_k)
    return pl.BlockSpec(blk, imap, memory_space=pltpu.VMEM)


def _pad_spec(block_k):
    # key padding mask [B, 1, Tk] -> block [1, 1, block_k] (the middle
    # singleton keeps Mosaic's sublane tiling rule satisfied)
    return pl.BlockSpec(
        (1, 1, block_k), lambda b, h, i, j: (b, 0, j), memory_space=pltpu.VMEM
    )


def _causal_mask(i, j, block_q, block_k, dtype):
    rows = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return jnp.where(cols > rows, jnp.asarray(NEG_INF, dtype), 0.0)


def _scores(q, k, scale, bias_ref, pad_ref, causal, i, j, block_q, block_k):
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if bias_ref is not None:
        b = bias_ref[0, 0].astype(jnp.float32)  # [1 or Bq, Bk]
        s = s + b
    if pad_ref is not None:
        pad = pad_ref[0, 0].astype(jnp.float32)  # [Bk]
        s = s + jnp.where(pad > 0, NEG_INF, 0.0)[None, :]
    if causal:
        s = s + _causal_mask(i, j, block_q, block_k, jnp.float32)
    return s


def _mb_seed(seed_ref, b, h, i, j, n_i, n_j):
    """Per-(head, q-block, k-block) offset on this batch row's seed —
    identical across the forward and all backward passes regardless of
    their grid layouts.  ``seed_ref`` is the FULL [B] seed array in SMEM
    (unblocked — Mosaic rejects rank-1 (1,) blocks whose length isn't a
    lane multiple), indexed here by the grid's batch id.  The per-row
    seeds carry GLOBAL row identity so data-sharded shards derive
    decorrelated masks (the analogue of the reference's per-rank dropout
    seed scoping, trainer.py:610-616)."""
    return seed_ref[b] + (h * n_i + i) * n_j + j


def _pick_hb(heads, tq, tk, want_dbias):
    """Heads per grid step for the SINGLE-BLOCK kernels.

    Measured on v5e: each grid step carries ~2us of fixed overhead, so
    the (B, H) = 768-step BERT forward spent ~45% of its time between
    blocks; batching heads into one step amortizes it.  The bound is the
    fp32 [hb, Tq, Tk] working set (scores/probs/dp live together, plus a
    dbias scratch in the backward) against Mosaic's ~16MB scoped VMEM.
    Deterministic by shape only — forward and backward MUST agree (the
    dropout masks are per-head streams reproduced on both sides)."""
    per_head = (16 if want_dbias else 12) * tq * tk
    for hb in (8, 6, 4, 3, 2):
        if heads % hb == 0 and hb * per_head <= (10 << 20):
            return hb
    return 1


def _hb_seed_masks(seed_ref, b, h0, hb, shape, keep_prob, n_q, n_k):
    """[hb, Tq, Tk] keep masks, one PER-HEAD seed each — bit-identical to
    the masks the per-head kernels draw, so head-batched and per-head
    passes can mix freely."""
    return jnp.stack([
        keep_mask(_mb_seed(seed_ref, b, h0 + hh, 0, 0, n_q, n_k), shape,
                  keep_prob)
        for hh in range(hb)
    ])


def _fwd_hb_kernel(seed_ref, q_ref, k_ref, v_ref, *rest, has_bias, has_pad,
                   scale, causal, dropout_prob, hb, block_q, block_k):
    """Single-block forward over grid (H//hb, B): hb heads per step, no
    online-softmax machinery (one k block = one pass), no scratch."""
    refs = list(rest)
    bias_ref = refs.pop(0) if has_bias else None
    pad_ref = refs.pop(0) if has_pad else None
    out_ref, lse_ref = refs
    g, b = pl.program_id(0), pl.program_id(1)

    q = q_ref[0]  # [hb, Tq, D]
    k = k_ref[0]
    v = v_ref[0]
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
    ) * scale  # [hb, Tq, Tk]
    if bias_ref is not None:
        s = s + bias_ref[0].astype(jnp.float32)  # [hb, 1 or Tq, Tk]
    if pad_ref is not None:
        pad = pad_ref[0, 0].astype(jnp.float32)  # [Tk]
        s = s + jnp.where(pad > 0, NEG_INF, 0.0)[None, None, :]
    if causal:
        s = s + _causal_mask(0, 0, block_q, block_k, jnp.float32)[None]

    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    if dropout_prob > 0.0:
        keep_prob = 1.0 - dropout_prob
        keep = _hb_seed_masks(seed_ref, b, g * hb, hb, (block_q, block_k),
                              keep_prob, 1, 1)
        p_use = jnp.where(keep, p * (1.0 / keep_prob), 0.0)
    else:
        p_use = p
    out = jax.lax.dot_general(
        p_use.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) / l_safe
    out_ref[0] = out.astype(out_ref.dtype)
    lse_ref[0] = m + jnp.log(l_safe)


def _bwd_hb_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, *rest, has_bias, has_pad, scale, causal,
                   dropout_prob, hb, block_q, block_k, n_b, want_dbias):
    """Single-block fused backward over grid (H//hb, B), batch innermost:
    hb heads per step, dbias accumulated in scratch over the batch."""
    refs = list(rest)
    bias_ref = refs.pop(0) if has_bias else None
    pad_ref = refs.pop(0) if has_pad else None
    if want_dbias:
        dq_ref, dk_ref, dv_ref, dbias_ref, db_scr = refs
    else:
        dq_ref, dk_ref, dv_ref = refs
        dbias_ref = db_scr = None
    g, b = pl.program_id(0), pl.program_id(1)

    if db_scr is not None:
        @pl.when(b == 0)
        def _():
            db_scr[...] = jnp.zeros_like(db_scr)

    q = q_ref[0]   # [hb, Tq, D]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0]
    lse = lse_ref[0]     # [hb, Tq, 1]
    delta = delta_ref[0]

    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
    ) * scale
    if bias_ref is not None:
        s = s + bias_ref[0].astype(jnp.float32)
    if pad_ref is not None:
        pad = pad_ref[0, 0].astype(jnp.float32)
        s = s + jnp.where(pad > 0, NEG_INF, 0.0)[None, None, :]
    if causal:
        s = s + _causal_mask(0, 0, block_q, block_k, jnp.float32)[None]
    p = jnp.exp(s - lse)

    if dropout_prob > 0.0:
        keep_prob = 1.0 - dropout_prob
        keep = _hb_seed_masks(seed_ref, b, g * hb, hb, (block_q, block_k),
                              keep_prob, 1, 1)
        p_drop = jnp.where(keep, p * (1.0 / keep_prob), 0.0)
    else:
        keep = None
        p_drop = p

    # compute-dtype matmul operands, fp32 accumulation (see _dkv_kernel)
    dv_ref[0] = jax.lax.dot_general(
        p_drop.astype(q.dtype), do, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ).astype(dv_ref.dtype)
    dp = jax.lax.dot_general(
        do, v, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    if keep is not None:
        dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_prob)), 0.0)
    ds_f32 = p * (dp - delta)
    ds = ds_f32.astype(q.dtype)
    dq_ref[0] = (jax.lax.dot_general(
        ds, k, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * scale).astype(dq_ref.dtype)
    dk_ref[0] = (jax.lax.dot_general(
        ds, q, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * scale).astype(dk_ref.dtype)
    if db_scr is not None:
        db_scr[...] += ds_f32

        @pl.when(b == n_b - 1)
        def _():
            dbias_ref[...] = db_scr[...].astype(dbias_ref.dtype)


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, *rest, has_bias, has_pad,
                scale, causal, dropout_prob, block_q, block_k, n_h, n_q, n_k):
    refs = list(rest)
    bias_ref = refs.pop(0) if has_bias else None
    pad_ref = refs.pop(0) if has_pad else None
    out_ref, lse_ref, m_scr, l_scr, acc_scr = refs

    # fwd grid is (H, B, qi, kj) — heads outermost (bias-block residency);
    # the (b, h) pair fed to the dropout seed is unchanged, so fwd and
    # bwd kernels (which keep batch at grid position 0) draw identical
    # per-block masks
    h, b = pl.program_id(0), pl.program_id(1)
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]  # [Bq, D]
    k = k_ref[0, 0]  # [Bk, D]
    v = v_ref[0, 0]  # [Bk, D]
    s = _scores(q, k, scale, bias_ref, pad_ref, causal, i, j, block_q, block_k)

    m_prev = m_scr[:, :1]  # [Bq, 1]
    l_prev = l_scr[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)  # [Bq, Bk]
    corr = jnp.exp(m_prev - m_new)  # [Bq, 1]
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)

    if dropout_prob > 0.0:
        keep_prob = 1.0 - dropout_prob
        seed = _mb_seed(seed_ref, b, h, i, j, n_q, n_k)
        keep = keep_mask(seed, p.shape, keep_prob)
        p_use = jnp.where(keep, p * (1.0 / keep_prob), 0.0)
    else:
        p_use = p

    pv = jax.lax.dot_general(
        p_use.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_scr[...] = acc_scr[...] * corr + pv
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == n_k - 1)
    def _():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out_ref[0, 0] = (acc_scr[...] / l_safe).astype(out_ref.dtype)
        lse_ref[0, 0] = m_scr[:, :1] + jnp.log(l_safe)


def _dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                *rest, has_bias, has_pad, scale, causal, dropout_prob,
                block_q, block_k, n_h, n_q, n_k):
    refs = list(rest)
    bias_ref = refs.pop(0) if has_bias else None
    pad_ref = refs.pop(0) if has_pad else None
    dk_ref, dv_ref, dk_scr, dv_scr = refs

    b, h = pl.program_id(0), pl.program_id(1)
    j, i = pl.program_id(2), pl.program_id(3)  # grid: k blocks outer, q inner

    @pl.when(i == 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0]  # [Bq, D] compute dtype (fp32 accum via preferred)
    lse = lse_ref[0, 0]  # [Bq, 1]
    delta = delta_ref[0, 0]  # [Bq, 1] = rowsum(dO * O)

    s = _scores(q, k, scale, bias_ref, pad_ref, causal, i, j, block_q, block_k)
    p = jnp.exp(s - lse)  # normalized probs [Bq, Bk]

    if dropout_prob > 0.0:
        keep_prob = 1.0 - dropout_prob
        seed = _mb_seed(seed_ref, b, h, i, j, n_q, n_k)
        keep = keep_mask(seed, p.shape, keep_prob)
        p_drop = jnp.where(keep, p * (1.0 / keep_prob), 0.0)
    else:
        keep = None
        p_drop = p

    # matmul operands ride the COMPUTE dtype (bf16 in training): fp32
    # MXU matmuls run at a fraction of the bf16 rate and were the bulk
    # of the kernel's 10%-utilization backward; accumulation stays fp32
    # via preferred_element_type
    # dv += p_drop^T @ dO
    dv_scr[...] += jax.lax.dot_general(
        p_drop.astype(q.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # dp~ = dO @ v^T ; dp = mask(dp~)/keep
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if keep is not None:
        dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_prob)), 0.0)
    ds = (p * (dp - delta)).astype(q.dtype)  # [Bq, Bk]
    # dk += ds^T @ q * scale
    dk_scr[...] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale

    @pl.when(i == n_q - 1)
    def _():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               *rest, has_bias, has_pad, scale, causal,
               dropout_prob, block_q, block_k, n_h, n_q, n_k):
    refs = list(rest)
    bias_ref = refs.pop(0) if has_bias else None
    pad_ref = refs.pop(0) if has_pad else None
    dq_ref, dq_scr = refs

    b, h = pl.program_id(0), pl.program_id(1)
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]

    s = _scores(q, k, scale, bias_ref, pad_ref, causal, i, j, block_q, block_k)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if dropout_prob > 0.0:
        keep_prob = 1.0 - dropout_prob
        seed = _mb_seed(seed_ref, b, h, i, j, n_q, n_k)
        keep = keep_mask(seed, p.shape, keep_prob)
        dp = jnp.where(keep, dp * (1.0 / keep_prob), 0.0)
    ds = (p * (dp - delta)).astype(q.dtype)
    dq_scr[...] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale

    @pl.when(j == n_k - 1)
    def _():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _joint_bwd_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, *rest, has_bias, has_pad, scale, causal,
                      dropout_prob, block_q, block_k, n_h, n_q, n_k):
    """dq + dk + dv in ONE pass for the n_k == 1, n_q > 1 regime (e.g.
    T=2048 at blocks (512, 2048)): grid (B, H, qi, kj=1).  dq accumulates
    per q block exactly like the old dq pass; dk/dv accumulate over qi in
    a full-K (Tk, D) fp32 scratch and are written on the final qi step —
    with one k block their output block index is constant per (b, h), so
    the output window is only ever revisited consecutively (Pallas
    forbids non-consecutive output revisits; that is what limits this
    kernel to n_k == 1).  Scores/probs are recomputed once instead of
    twice, cutting the backward's matmuls from 7 to 5 — the VERDICT r4
    flash regression at T=2048 (0.888x vs materialized) came down to
    exactly that second recompute sweep."""
    refs = list(rest)
    bias_ref = refs.pop(0) if has_bias else None
    pad_ref = refs.pop(0) if has_pad else None
    dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr = refs

    b, h = pl.program_id(0), pl.program_id(1)
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(j == 0)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]

    s = _scores(q, k, scale, bias_ref, pad_ref, causal, i, j, block_q, block_k)
    p = jnp.exp(s - lse)

    if dropout_prob > 0.0:
        keep_prob = 1.0 - dropout_prob
        seed = _mb_seed(seed_ref, b, h, i, j, n_q, n_k)
        keep = keep_mask(seed, p.shape, keep_prob)
        p_drop = jnp.where(keep, p * (1.0 / keep_prob), 0.0)
    else:
        keep = None
        p_drop = p

    # compute-dtype matmul operands, fp32 accumulation (see _dkv_kernel)
    ks = pl.ds(j * block_k, block_k)
    dv_scr[ks, :] += jax.lax.dot_general(
        p_drop.astype(q.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if keep is not None:
        dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_prob)), 0.0)
    ds = (p * (dp - delta)).astype(q.dtype)
    dk_scr[ks, :] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    dq_scr[...] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale

    @pl.when(j == n_k - 1)
    def _():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)

    @pl.when(i == n_q - 1)
    def _():
        dk_ref[0, 0] = dk_scr[ks, :].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[ks, :].astype(dv_ref.dtype)


def _dbias_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                  *rest, has_bias, has_pad, scale, causal, dropout_prob,
                  block_q, block_k, n_h, n_q, n_k, n_b):
    """dbias pass: grid (H, nQ, nK, B) — batch innermost, accumulated in
    scratch (output blocks are written once, at b == B-1; accumulating into
    output refs across grid steps is not portable)."""
    refs = list(rest)
    bias_ref = refs.pop(0) if has_bias else None
    pad_ref = refs.pop(0) if has_pad else None
    dbias_ref, scr = refs

    h, i = pl.program_id(0), pl.program_id(1)
    j, b = pl.program_id(2), pl.program_id(3)

    @pl.when(b == 0)
    def _():
        scr[...] = jnp.zeros_like(scr)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]

    s = _scores(q, k, scale, bias_ref, pad_ref, causal, i, j, block_q, block_k)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if dropout_prob > 0.0:
        keep_prob = 1.0 - dropout_prob
        seed = _mb_seed(seed_ref, b, h, i, j, n_q, n_k)
        keep = keep_mask(seed, p.shape, keep_prob)
        dp = jnp.where(keep, dp * (1.0 / keep_prob), 0.0)
    scr[...] += p * (dp - delta)

    @pl.when(b == n_b - 1)
    def _():
        dbias_ref[0] = scr[...].astype(dbias_ref.dtype)


def _pick_blocks(tq, tk, bias_itemsize=0):
    """Largest divisible blocks with the fp32 score block (bq x bk) held
    to ~4MB of VMEM: measured on v5e at T=8192, (512, 2048) runs the
    fwd+bwd 1.65x faster than the original (256, 512) — bigger blocks
    amortize the online-softmax rescale and per-block overhead — while
    (1024, 2048) exceeds the 16MB scoped-vmem stack and fails to compile.
    A bias adds a double-buffered (bq, bk)-shaped stream on top of the
    fp32 score block, so its presence scales the element budget by
    2/(2 + bias_itemsize) — 1/2 for a bf16 bias, 1/3 for fp32 (a bq=512,
    bk=2048 fp32 bias block alone is 4MB x2 buffers)."""
    def pick(t, cands):
        for c in cands:
            if c <= t and t % c == 0:
                return c
        return t

    bq = pick(tq, (512, 384, 256, 128))
    budget_el = (1 << 20) if bias_itemsize == 0 else (
        (1 << 20) * 2 // (2 + bias_itemsize)
    )
    budget = budget_el // bq  # score-block element budget
    # non-power-of-two 128-multiples matter: T=384/640/768/1536 would
    # otherwise shatter into 128-blocks (a 3x3+ grid and the two-pass
    # backward).  tk itself leads the candidates: a single k block both
    # minimizes online-softmax rescales and enables the joint one-pass
    # backward
    bk = pick(tk, tuple(
        c for c in (tk, 2048, 1536, 1024, 768, 512, 384, 256, 128)
        if c <= budget
    ))
    return bq, bk


def eligible(q_shape, k_shape, bias_shape):
    """Whether the flash kernel supports these shapes ([B,H,T,D] layout)."""
    _, _, tq, d = q_shape
    tk = k_shape[2]
    if tq % 128 != 0 or tk % 128 != 0:
        return False
    if d > 256 or d % 8 != 0:
        return False
    if bias_shape is not None:
        if len(bias_shape) != 4:
            return False
        bB, bH, bQ, bK = bias_shape
        # batch-broadcast bias only (the dbias pass accumulates over batch);
        # batched biases fall back to the materialized path
        if bB != 1 or bK != tk or bQ not in (1, tq):
            return False
    return True


def _q_spec(block_q, d):
    return pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0),
                        memory_space=pltpu.VMEM)


def _kv_spec(block_k, d):
    return pl.BlockSpec((1, 1, block_k, d), lambda b, h, i, j: (b, h, j, 0),
                        memory_space=pltpu.VMEM)


def _lse_spec(block_q):
    return pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0),
                        memory_space=pltpu.VMEM)


# The full [B] int32 per-row seed array rides into SMEM unblocked (no
# block shape / index map); kernels index it by the grid's batch id.
# A (1,)-blocked rank-1 spec is NOT portable: Mosaic requires rank-1
# block lengths to equal the array length or be a 128-multiple.
_SEED_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def picked_blocks(tq, tk, bias_shape=None, bias_dtype=None):
    """The (block_q, block_k) the kernel will use for these shapes —
    THE block-choice authority, shared by `_common` and the module-level
    dispatch gate (`_flash_ok` predicts the single-block regime with it;
    a drifted duplicate would silently misroute dispatch).  A function
    of its arguments alone, so the forward and backward of one
    custom_vjp always agree.  A bQ==1 broadcast bias streams only
    (1, block_k) per step (~KBs) — shrinking the score block for it
    would multiply grid steps for no VMEM relief; only a full
    (block_q, block_k) bias stream costs budget."""
    bias_itemsize = (
        jnp.dtype(bias_dtype).itemsize
        if bias_shape is not None and bias_shape[2] != 1
        else 0
    )
    return _pick_blocks(tq, tk, bias_itemsize)


def _common(q, k, bias=None):
    bsz, heads, tq, d = q.shape
    tk = k.shape[2]
    block_q, block_k = picked_blocks(
        tq, tk,
        None if bias is None else bias.shape,
        None if bias is None else bias.dtype,
    )
    grid = (bsz, heads, tq // block_q, tk // block_k)
    return bsz, heads, tq, tk, d, block_q, block_k, grid


def _flash_fwd_impl(q, k, v, bias, pad, dropout_prob, seed, causal, scale):
    bsz, heads, tq, tk, d, block_q, block_k, grid = _common(q, k, bias)
    if grid[2] == 1 and grid[3] == 1:
        return _flash_fwd_hb(
            q, k, v, bias, pad, dropout_prob, seed, causal, scale,
            block_q, block_k,
        )
    # grid is (H, B, qi, kj) — HEADS OUTERMOST: a batch-broadcast bias
    # block depends only on (h, i, j), so with b sweeping inside h the
    # block index is unchanged across consecutive steps and Mosaic keeps
    # it resident instead of re-streaming it per batch row (measured on
    # BERT-base: the [1, H, T, T] fp32 rel-pos bias was ~[B x 12 MB] of
    # HBM reads per layer per forward with batch outermost)
    hb_grid = (heads, bsz, grid[2], grid[3])

    def swap(spec):
        return pl.BlockSpec(
            spec.block_shape,
            lambda h, b, i, j, _m=spec.index_map: _m(b, h, i, j),
            memory_space=pltpu.VMEM,
        )

    in_specs = [_SEED_SPEC, swap(_q_spec(block_q, d)),
                swap(_kv_spec(block_k, d)), swap(_kv_spec(block_k, d))]
    args = [seed, q, k, v]
    if bias is not None:
        in_specs.append(swap(_bias_spec(bias.shape, block_q, block_k)))
        args.append(bias)
    if pad is not None:
        in_specs.append(swap(_pad_spec(block_k)))
        args.append(pad)
    kernel = functools.partial(
        _fwd_kernel, has_bias=bias is not None, has_pad=pad is not None,
        scale=scale, causal=causal, dropout_prob=dropout_prob,
        block_q=block_q, block_k=block_k, n_h=heads, n_q=grid[2], n_k=grid[3],
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=hb_grid,
        in_specs=in_specs,
        out_specs=[swap(_q_spec(block_q, d)), swap(_lse_spec(block_q))],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bsz, heads, tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=pallas_interpret(),
        name="flash_attention_fwd",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
    )(*args)
    return out, lse


def _flash_fwd_hb(q, k, v, bias, pad, dropout_prob, seed, causal, scale,
                  block_q, block_k):
    """Single-block forward: grid (H//hb, B), hb heads per step."""
    bsz, heads, tq, d = q.shape
    tk = k.shape[2]
    hb = _pick_hb(heads, tq, tk, bias is not None)
    spec4, lse_spec, bias_spec, pad_spec = _hb_specs(
        hb, d, block_q, block_k, bias, pad
    )
    in_specs = [_SEED_SPEC, spec4(block_q), spec4(block_k), spec4(block_k)]
    args = [seed, q, k, v]
    if bias is not None:
        in_specs.append(bias_spec)
        args.append(bias)
    if pad is not None:
        in_specs.append(pad_spec)
        args.append(pad)
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_hb_kernel, has_bias=bias is not None,
            has_pad=pad is not None, scale=scale, causal=causal,
            dropout_prob=dropout_prob, hb=hb, block_q=block_q,
            block_k=block_k,
        ),
        grid=(heads // hb, bsz),
        in_specs=in_specs,
        out_specs=[spec4(block_q), lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bsz, heads, tq, 1), jnp.float32),
        ],
        interpret=pallas_interpret(),
        name="flash_attention_fwd_hb",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,  # see the backward's note
        ),
    )(*args)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 7, 8))
def _flash(q, k, v, bias, pad, dropout_prob, seed, causal, scale):
    out, _ = _flash_fwd_impl(q, k, v, bias, pad, dropout_prob, seed, causal, scale)
    return out


def _flash_fwd(q, k, v, bias, pad, dropout_prob, seed, causal, scale):
    out, lse = _flash_fwd_impl(q, k, v, bias, pad, dropout_prob, seed, causal, scale)
    return out, (q, k, v, bias, pad, seed, out, lse)


def _flash_bwd(dropout_prob, causal, scale, residuals, g):
    q, k, v, bias, pad, seed, out, lse = residuals
    bsz, heads, tq, tk, d, block_q, block_k, grid = _common(q, k, bias)
    n_q, n_k = grid[2], grid[3]
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True
    )  # [B,H,Tq,1]

    if n_q == 1 and n_k == 1:
        return _flash_bwd_fused(
            q, k, v, bias, pad, seed, lse, delta, g, dropout_prob, causal,
            scale, block_q, block_k,
        )

    common_in = [
        _SEED_SPEC, _q_spec(block_q, d), _kv_spec(block_k, d),
        _kv_spec(block_k, d), _q_spec(block_q, d), _lse_spec(block_q),
        _lse_spec(block_q),
    ]
    common_args = [seed, q, k, v, g, lse, delta]
    extra_in, extra_args = [], []
    if bias is not None:
        extra_in.append(_bias_spec(bias.shape, block_q, block_k))
        extra_args.append(bias)
    if pad is not None:
        extra_in.append(_pad_spec(block_k))
        extra_args.append(pad)

    # joint dq+dk+dv pass (one score recompute instead of two) for the
    # single-k-block regime: with n_k == 1 the dk/dv output block index is
    # CONSTANT within each (b, h), so the consecutive-revisit rule holds
    # for all three outputs (dq's block advances with the i runs).  With
    # n_k > 1 dk/dv blocks would be revisited non-consecutively across i —
    # illegal in Pallas — so longer sequences keep the two-pass form.
    if n_k == 1 and n_q > 1 and 2 * tk * d * 4 <= (6 << 20):
        kv_out_spec = pl.BlockSpec(
            (1, 1, block_k, d), lambda b, h, i, j: (b, h, j, 0),
            memory_space=pltpu.VMEM,
        )
        dq, dk, dv = pl.pallas_call(
            functools.partial(
                _joint_bwd_kernel, has_bias=bias is not None,
                has_pad=pad is not None, scale=scale, causal=causal,
                dropout_prob=dropout_prob, block_q=block_q, block_k=block_k,
                n_h=heads, n_q=n_q, n_k=n_k,
            ),
            grid=grid,
            in_specs=common_in + extra_in,
            out_specs=[_q_spec(block_q, d), kv_out_spec, kv_out_spec],
            out_shape=[
                jax.ShapeDtypeStruct(q.shape, q.dtype),
                jax.ShapeDtypeStruct(k.shape, k.dtype),
                jax.ShapeDtypeStruct(v.shape, v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((tk, d), jnp.float32),
                pltpu.VMEM((tk, d), jnp.float32),
            ],
            interpret=pallas_interpret(),
            name="flash_attention_bwd_joint",
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary",
                                     "arbitrary"),
            ),
        )(*(common_args + extra_args))
        dbias = None
        if bias is not None:
            dbias = _dbias_pass(
                q, k, v, bias, pad, seed, lse, delta, g, dropout_prob,
                causal, scale, block_q, block_k, bsz, heads, n_q, n_k, tq, tk,
            )
        return dq, dk, dv, dbias, None, None

    # ---- dq pass: grid (b, h, qi, kj), scratch accumulation over kj ----
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, has_bias=bias is not None, has_pad=pad is not None,
            scale=scale, causal=causal, dropout_prob=dropout_prob,
            block_q=block_q, block_k=block_k, n_h=heads, n_q=n_q, n_k=n_k,
        ),
        grid=grid,
        in_specs=common_in + extra_in,
        out_specs=_q_spec(block_q, d),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=pallas_interpret(),
        name="flash_attention_bwd_dq",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
    )(*(common_args + extra_args))

    # ---- dk/dv pass: grid (b, h, kj, qi), scratch accumulation over qi ----
    dkv_grid = (bsz, heads, n_k, n_q)
    q_spec_t = pl.BlockSpec((1, 1, block_q, d), lambda b, h, j, i: (b, h, i, 0),
                            memory_space=pltpu.VMEM)
    kv_spec_t = pl.BlockSpec((1, 1, block_k, d), lambda b, h, j, i: (b, h, j, 0),
                             memory_space=pltpu.VMEM)
    lse_spec_t = pl.BlockSpec((1, 1, block_q, 1), lambda b, h, j, i: (b, h, i, 0),
                              memory_space=pltpu.VMEM)
    dkv_in = [_SEED_SPEC, q_spec_t, kv_spec_t, kv_spec_t, q_spec_t,
              lse_spec_t, lse_spec_t]
    if bias is not None:
        bB, bH, bQ, bK = bias.shape
        dkv_in.append(pl.BlockSpec(
            (1, 1, 1 if bQ == 1 else block_q, block_k),
            lambda b, h, j, i: (0 if bB == 1 else b, 0 if bH == 1 else h,
                                0 if bQ == 1 else i, j),
            memory_space=pltpu.VMEM,
        ))
    if pad is not None:
        dkv_in.append(pl.BlockSpec(
            (1, 1, block_k), lambda b, h, j, i: (b, 0, j),
            memory_space=pltpu.VMEM,
        ))
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, has_bias=bias is not None, has_pad=pad is not None,
            scale=scale, causal=causal, dropout_prob=dropout_prob,
            block_q=block_q, block_k=block_k, n_h=heads, n_q=n_q, n_k=n_k,
        ),
        grid=dkv_grid,
        in_specs=dkv_in,
        out_specs=[kv_spec_t, kv_spec_t],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=pallas_interpret(),
        name="flash_attention_bwd_dkv",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
    )(*(common_args + extra_args))

    # ---- dbias pass: grid (h, qi, kj, b), scratch accumulation over b ----
    dbias = None
    if bias is not None:
        dbias = _dbias_pass(
            q, k, v, bias, pad, seed, lse, delta, g, dropout_prob, causal,
            scale, block_q, block_k, bsz, heads, n_q, n_k, tq, tk,
        )

    return dq, dk, dv, dbias, None, None


def _dbias_pass(q, k, v, bias, pad, seed, lse, delta, g, dropout_prob,
                causal, scale, block_q, block_k, bsz, heads, n_q, n_k,
                tq, tk):
    d = q.shape[3]

    def hmap4(sel):
        # index maps for the (h, i, j, b) grid
        return {
            "q": lambda h, i, j, b: (b, h, i, 0),
            "kv": lambda h, i, j, b: (b, h, j, 0),
            "lse": lambda h, i, j, b: (b, h, i, 0),
            "pad": lambda h, i, j, b: (b, 0, j),
        }[sel]

    q_spec_b = pl.BlockSpec((1, 1, block_q, d), hmap4("q"),
                            memory_space=pltpu.VMEM)
    kv_spec_b = pl.BlockSpec((1, 1, block_k, d), hmap4("kv"),
                             memory_space=pltpu.VMEM)
    lse_spec_b = pl.BlockSpec((1, 1, block_q, 1), hmap4("lse"),
                              memory_space=pltpu.VMEM)
    db_in = [_SEED_SPEC,
             q_spec_b, kv_spec_b, kv_spec_b, q_spec_b,
             lse_spec_b, lse_spec_b]
    db_args = [seed, q, k, v, g, lse, delta]
    bB, bH, bQ, bK = bias.shape
    db_in.append(pl.BlockSpec(
        (1, 1, 1 if bQ == 1 else block_q, block_k),
        lambda h, i, j, b: (0, 0 if bH == 1 else h, 0 if bQ == 1 else i, j),
        memory_space=pltpu.VMEM,
    ))
    db_args.append(bias)
    if pad is not None:
        db_in.append(pl.BlockSpec((1, 1, block_k), hmap4("pad"),
                                  memory_space=pltpu.VMEM))
        db_args.append(pad)
    dbias_full = pl.pallas_call(
        functools.partial(
            _dbias_kernel, has_bias=True, has_pad=pad is not None,
            scale=scale, causal=causal, dropout_prob=dropout_prob,
            block_q=block_q, block_k=block_k, n_h=heads, n_q=n_q,
            n_k=n_k, n_b=bsz,
        ),
        grid=(heads, n_q, n_k, bsz),
        in_specs=db_in,
        out_specs=pl.BlockSpec(
            (1, block_q, block_k), lambda h, i, j, b: (h, i, j),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((heads, tq, tk), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, block_k), jnp.float32)],
        interpret=pallas_interpret(),
        name="flash_attention_bwd_dbias",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
    )(*db_args)
    return _reduce_dbias(dbias_full, bias)


def _reduce_dbias(dbias_full, bias):
    """Reduce the kernel's [H, Tq, Tk] batch-summed dbias to the bias's
    broadcast shape [1, bH, bQ, Tk] (shared by the multi-block and fused
    backward paths)."""
    _, bH, bQ, _ = bias.shape
    db = dbias_full[None]  # [1, H, Tq, Tk]
    if bH == 1:
        db = jnp.sum(db, axis=1, keepdims=True)
    if bQ == 1:
        db = jnp.sum(db, axis=2, keepdims=True)
    return db.astype(bias.dtype)


def _hb_specs(hb, d, block_q, block_k, bias, pad):
    """Shared BlockSpecs for the head-batched single-block kernels: grid
    (H//hb, B); q/k/v/out blocks carry hb heads; a bias with bH == 1
    broadcasts one head row, otherwise it is blocked per hb heads (THE
    spec forward and backward must agree on)."""
    def spec4(blk_t):
        return pl.BlockSpec((1, hb, blk_t, d), lambda g_, b: (b, g_, 0, 0),
                            memory_space=pltpu.VMEM)

    lse_spec = pl.BlockSpec((1, hb, block_q, 1), lambda g_, b: (b, g_, 0, 0),
                            memory_space=pltpu.VMEM)
    bias_spec = None
    if bias is not None:
        bB, bH, bQ, bK = bias.shape
        bias_spec = pl.BlockSpec(
            (1, 1 if bH == 1 else hb, bQ, block_k),
            lambda g_, b: (0, 0 if bH == 1 else g_, 0, 0),
            memory_space=pltpu.VMEM,
        )
    pad_spec = None
    if pad is not None:
        pad_spec = pl.BlockSpec(
            (1, 1, block_k), lambda g_, b: (b, 0, 0),
            memory_space=pltpu.VMEM,
        )
    return spec4, lse_spec, bias_spec, pad_spec


def _flash_bwd_fused(q, k, v, bias, pad, seed, lse, delta, g, dropout_prob,
                     causal, scale, block_q, block_k):
    """dq/dk/dv(/dbias) in ONE kernel over grid (H//hb, B), batch
    innermost, hb heads per step (amortizes the ~2us fixed cost of each
    grid step; hb is shape-deterministic so fwd/bwd agree)."""
    bsz, heads, tq, tk, d = q.shape[0], q.shape[1], q.shape[2], k.shape[2], q.shape[3]
    want_dbias = bias is not None
    hb = _pick_hb(heads, tq, tk, want_dbias)
    spec4, lse_spec, bias_spec, pad_spec = _hb_specs(
        hb, d, block_q, block_k, bias, pad
    )
    in_specs = [_SEED_SPEC, spec4(block_q), spec4(block_k), spec4(block_k),
                spec4(block_q), lse_spec, lse_spec]
    args = [seed, q, k, v, g, lse, delta]
    if bias is not None:
        in_specs.append(bias_spec)
        args.append(bias)
    if pad is not None:
        in_specs.append(pad_spec)
        args.append(pad)

    out_specs = [spec4(block_q), spec4(block_k), spec4(block_k)]
    out_shape = [
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        jax.ShapeDtypeStruct(k.shape, k.dtype),
        jax.ShapeDtypeStruct(v.shape, v.dtype),
    ]
    scratch = []
    if want_dbias:
        out_specs.append(pl.BlockSpec(
            (hb, block_q, block_k), lambda g_, b: (g_, 0, 0),
            memory_space=pltpu.VMEM,
        ))
        out_shape.append(
            jax.ShapeDtypeStruct((heads, tq, tk), jnp.float32)
        )
        scratch.append(pltpu.VMEM((hb, block_q, block_k), jnp.float32))

    results = pl.pallas_call(
        functools.partial(
            _bwd_hb_kernel, has_bias=bias is not None,
            has_pad=pad is not None, scale=scale, causal=causal,
            dropout_prob=dropout_prob, hb=hb, block_q=block_q,
            block_k=block_k, n_b=bsz, want_dbias=want_dbias,
        ),
        grid=(heads // hb, bsz),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=pallas_interpret(),
        name="flash_attention_bwd_hb",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # the hb-batched working set legitimately exceeds the 16MB
            # default scoped-vmem (v5e has 128MB physical); measured
            # 16.25MB at hb=2, T=512 with dbias inside the full train step
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
    )(*args)
    dq, dk, dv = results[0], results[1], results[2]
    dbias = _reduce_dbias(results[3], bias) if want_dbias else None
    return dq, dk, dv, dbias, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q, k, v,
    bias=None,
    key_padding_mask=None,
    causal=False,
    dropout_prob=0.0,
    rng=None,
    is_training=True,
    scale=None,
    batch_seed_offset=None,
    seed_offset=None,
):
    """Blockwise attention.  q/k/v: [B, T, H, D] (module layout); ``bias``
    broadcastable to [B, H, Tq, Tk]; ``key_padding_mask``: [B, Tk] with
    nonzero = pad.  Returns [B, Tq, H, D].

    Dropout seeds are PER BATCH ROW (base seed + global row id x odd
    constant), so data-sharded invocations under one jit derive
    decorrelated masks.  ``batch_seed_offset`` lets an explicit-SPMD
    caller (shard_map) pass its shard's global row origin
    (``axis_index * local_batch``); ``seed_offset`` is added to the BASE
    seed — a head-sharded caller (Ulysses) passes a per-device offset so
    the same local head index on different devices (= different global
    heads) draws decorrelated masks."""
    bsz, tq, heads, d = q.shape
    if causal and tq != k.shape[1]:
        # the kernel's causal triangle compares GLOBAL q/k indices over one
        # shared sequence grid (top-left alignment); with tq != tk that
        # silently mis-masks — an incremental-decode caller must slice the
        # bias path instead (utils.causal_iota_mask is bottom-right aligned)
        raise ValueError(
            f"flash_attention(causal=True) requires tq == tk, got "
            f"{tq} != {k.shape[1]}"
        )
    if scale is None:
        scale = d ** -0.5
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    if bias is not None and bias.ndim < 4:
        bias = bias.reshape((1,) * (4 - bias.ndim) + bias.shape)
    p = float(dropout_prob) if is_training else 0.0
    if p > 0.0:
        if rng is None:
            raise ValueError("flash_attention: rng required for dropout")
        base = jax.random.randint(rng, (), 0, 2 ** 31 - 1, dtype=jnp.int32)
        if seed_offset is not None:
            base = base + jnp.asarray(seed_offset, dtype=jnp.int32)
        rows = jax.lax.iota(jnp.int32, bsz)
        if batch_seed_offset is not None:
            rows = rows + jnp.asarray(batch_seed_offset, dtype=jnp.int32)
        # Knuth multiplicative-hash constant (odd): distinct rows land in
        # well-separated seed neighborhoods mod 2^32
        seed = base + rows * jnp.int32(-1640531527)
    else:
        seed = jnp.zeros((bsz,), dtype=jnp.int32)
    pad = None
    if key_padding_mask is not None:
        pad = key_padding_mask.astype(jnp.int32)[:, None, :]  # [B, 1, Tk]
    out = _flash(qt, kt, vt, bias, pad, p, seed, causal, float(scale))
    return jnp.transpose(out, (0, 2, 1, 3))
