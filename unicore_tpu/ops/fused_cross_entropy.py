"""Fused chunked linear + cross-entropy head (Liger-style, arxiv
2410.10989): per-row nll of a vocab projection WITHOUT the ``[N, V]``
logits tensor ever existing in HBM.

The MLM/LM head is the single largest allocation of a training step:
``[rows, vocab]`` logits (954 MB fp32 for 8192 slots x 30k vocab at the
BERT-base bench shape) materialized by the model, cast to fp32 by the
loss, and saved as a backward residual — exactly the UL002
giant-intermediate class ``unicore_tpu.analysis`` flags.  This op moves
the projection INTO the loss and computes it chunk-by-chunk over rows
inside a ``lax.scan``:

- forward: per chunk, ``logits = f_c @ W(+b)`` (bf16 operands, fp32 MXU
  accumulation via ``preferred_element_type``), reduced immediately to
  ``logsumexp - picked`` — the same residual-free idiom
  ``losses/masked_lm.py`` uses — so only the ``[N]`` nll leaves the scan;
- backward (``custom_vjp``): residuals are just the INPUTS; each chunk's
  logits are recomputed, ``softmax - onehot`` scaled by the incoming
  per-row cotangent yields the chunk's dlogits, and the weight/bias
  cotangents accumulate in an fp32 scan carry while d(features) streams
  out per chunk.  Peak head memory drops from O(N*V) to
  O(chunk*V + V*D).

The per-row-cotangent contract (callers weight the nll themselves, e.g.
``sum(nll * mask)``) keeps one op serving all three loss forms: the
full-sequence weighted-mask MLM loss, the static-slot ``[K, V]`` head,
and plain cross-entropy.

Dispatch: an explicit ``chunk_size`` wins, else a static byte rule (fuse
only when the logits tensor would exceed ``FUSE_MIN_BYTES``; chunk sized
so the per-chunk fp32 logits stay inside ``CHUNK_TARGET_BYTES``).
Neither constant has been swept on this machine.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

# below this full-logits size the unfused matmul + logsumexp is one big
# MXU call with no scan fixed costs, and irrelevant to peak HBM
FUSE_MIN_BYTES = 16 << 20
# per-chunk fp32 logits budget the chunk heuristic targets: big enough
# that the [chunk, V] matmul amortizes scan overhead (~256 rows at a 30k
# vocab), small enough that the freed HBM is real
CHUNK_TARGET_BYTES = 32 << 20
MIN_CHUNK = 16


def pick_chunk(rows, vocab):
    """Largest power-of-two chunk whose fp32 logits fit the budget,
    clamped to [MIN_CHUNK, 8192] (and never above ``rows``)."""
    rows, vocab = int(rows), int(vocab)
    c = CHUNK_TARGET_BYTES // max(vocab * 4, 1)
    c = 1 << max(c.bit_length() - 1, 0)  # pow2 floor
    return max(MIN_CHUNK, min(c, 8192, max(rows, 1)))


def linear_nll_reference(features, kernel, targets, bias=None, *,
                         tied=False):
    """Unfused spec: materialized logits -> fp32 ``logsumexp - picked``.
    Bit-for-bit the path the losses took before this op existed (the
    matmul runs in the compute dtype, the reduction in fp32), so the
    unfused branch is a no-op relative to the legacy head."""
    kernel = kernel.astype(features.dtype)
    logits = features @ (kernel.T if tied else kernel)
    if bias is not None:
        logits = logits + bias.astype(logits.dtype)
    logits32 = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits32, axis=-1)
    picked = jnp.take_along_axis(logits32, targets[..., None], axis=-1)
    return lse - picked[..., 0]


def _chunk_logits32(f_c, kernel_c, bias, tied):
    """One chunk's fp32 logits: low-precision operands, fp32 MXU
    accumulation (both operands share the compute dtype — the UL001
    contract — and ``preferred_element_type`` keeps the fp32 accuracy
    the losses' fp32 cast used to provide)."""
    eq = "cd,vd->cv" if tied else "cd,dv->cv"
    logits = jnp.einsum(eq, f_c, kernel_c,
                        preferred_element_type=jnp.float32)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    return logits


def _pad_rows(x, pad):
    if pad == 0:
        return x
    width = ((0, pad),) + ((0, 0),) * (x.ndim - 1)
    return jnp.pad(x, width)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _chunked_nll(chunk, tied, features, kernel, bias, targets):
    nll, _ = _chunked_nll_fwd(chunk, tied, features, kernel, bias, targets)
    return nll


def _chunked_nll_fwd(chunk, tied, features, kernel, bias, targets):
    n = features.shape[0]
    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    f = _pad_rows(features, pad).reshape(n_chunks, chunk, -1)
    t = _pad_rows(targets, pad).reshape(n_chunks, chunk)
    kernel_c = kernel.astype(features.dtype)

    def body(_, xs):
        f_c, t_c = xs
        logits32 = _chunk_logits32(f_c, kernel_c, bias, tied)
        lse = jax.nn.logsumexp(logits32, axis=-1)
        picked = jnp.take_along_axis(logits32, t_c[:, None], axis=-1)
        return 0, lse - picked[:, 0]

    _, nll = jax.lax.scan(body, 0, (f, t))
    return nll.reshape(-1)[:n], (features, kernel, bias, targets)


def _chunked_nll_bwd(chunk, tied, res, g):
    features, kernel, bias, targets = res
    n, d = features.shape
    v = kernel.shape[0] if tied else kernel.shape[1]
    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    f = _pad_rows(features, pad).reshape(n_chunks, chunk, d)
    t = _pad_rows(targets, pad).reshape(n_chunks, chunk)
    # padded rows carry zero cotangent, so they contribute nothing to any
    # accumulator below
    gg = _pad_rows(g.astype(jnp.float32), pad).reshape(n_chunks, chunk)
    kernel_c = kernel.astype(features.dtype)

    dk0 = jnp.zeros(kernel.shape, jnp.float32)
    db0 = None if bias is None else jnp.zeros(bias.shape, jnp.float32)

    def body(carry, xs):
        dk, db = carry
        f_c, t_c, g_c = xs
        logits32 = _chunk_logits32(f_c, kernel_c, bias, tied)
        p = jax.nn.softmax(logits32, axis=-1)
        dlog32 = (p - jax.nn.one_hot(t_c, v, dtype=jnp.float32)) \
            * g_c[:, None]
        if db is not None:
            db = db + jnp.sum(dlog32, axis=0)
        # the two backward matmuls run in the compute dtype (the naive
        # path's d(logits) passes through the loss's fp32->bf16 cast the
        # same way); the weight cotangent still ACCUMULATES in fp32
        dlog = dlog32.astype(f_c.dtype)
        if tied:
            df_c = jnp.einsum("cv,vd->cd", dlog, kernel_c)
            dk = dk + jnp.einsum("cv,cd->vd", dlog, f_c,
                                 preferred_element_type=jnp.float32)
        else:
            df_c = jnp.einsum("cv,dv->cd", dlog, kernel_c)
            dk = dk + jnp.einsum("cd,cv->dv", f_c, dlog,
                                 preferred_element_type=jnp.float32)
        return (dk, db), df_c

    (dk, db), df = jax.lax.scan(body, (dk0, db0), (f, t, gg))
    dfeatures = df.reshape(n_chunks * chunk, d)[:n].astype(features.dtype)
    dkernel = dk.astype(kernel.dtype)
    dbias = None if bias is None else db.astype(bias.dtype)
    dtargets = np.zeros(targets.shape, dtype=jax.dtypes.float0)
    return dfeatures, dkernel, dbias, dtargets


_chunked_nll.defvjp(_chunked_nll_fwd, _chunked_nll_bwd)


def _resolve_chunk(rows, vocab):
    """None -> eager (unfused), int -> fused chunk size: the static byte
    rule, a function of the logits' shape alone."""
    if rows * vocab * 4 < FUSE_MIN_BYTES:
        return None
    chunk = pick_chunk(rows, vocab)
    if chunk >= rows:
        # a single chunk IS the full-logits program plus scan overhead —
        # nothing to save; let the one big MXU call win (an explicit
        # chunk_size can still force the chunked path)
        return None
    return chunk


def fused_linear_cross_entropy(features, kernel, targets, bias=None, *,
                               tied=False, chunk_size=None):
    """Per-row nll ``[N] fp32`` of ``features @ kernel(+bias)`` against
    ``targets`` — chunked so the full logits never materialize.

    - ``features``: ``[N, D]`` hidden states (post head-MLP/LayerNorm).
    - ``kernel``: ``[D, V]``, or the tied-embedding ``[V, D]`` ``attend``
      form with ``tied=True``.
    - ``targets``: ``[N]`` int labels; ``bias``: optional ``[V]``.
    - ``chunk_size``: rows per scan step.  ``None``/0 = auto (the byte
      rule, with an eager crossover for small vocab*rows); an explicit
      value always takes the chunked path.

    Callers weight the returned nll themselves (``sum(nll * w)``): the
    per-row cotangent flows into the chunked backward, so masked/slot
    weighting costs nothing extra.
    """
    n, d = features.shape
    v = kernel.shape[0] if tied else kernel.shape[1]
    if chunk_size is not None and int(chunk_size) > 0:
        chunk = int(chunk_size)
    else:
        # 0/negative/None all mean auto — a negative explicit chunk
        # would otherwise clamp to 1 and scan N single-row matvecs
        chunk = _resolve_chunk(n, v)
        if chunk is None:
            return linear_nll_reference(features, kernel, targets,
                                        bias=bias, tied=tied)
    chunk = max(1, min(int(chunk), n))
    return _chunked_nll(chunk, bool(tied), features, kernel, bias, targets)


def fused_head_nll(out, targets, chunk_size=None):
    """nll for a model's fused-head dict (``{"features", "kernel",
    "bias", "tied"}``; see ``examples/bert/model.py``) against flat
    ``targets`` — the one call every loss form shares.  ``chunk_size``
    (threaded from ``--fused-ce-chunk``) overrides dispatch."""
    features = out["features"]
    features = features.reshape(-1, features.shape[-1])
    return fused_linear_cross_entropy(
        features, out["kernel"], targets.reshape(-1), bias=out.get("bias"),
        tied=bool(out.get("tied", True)), chunk_size=chunk_size,
    )
