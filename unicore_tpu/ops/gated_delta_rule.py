"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464) and the causal
short convolution that feeds it: the linear-attention mixer's two ops.

Per head the layer keeps a state ``S`` in ``R^{dk x dv}`` (float32
whatever the weights) and reads/writes it once per token::

    S_t = a_t S_{t-1} + b_t k_t (v_t - a_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

with ``a_t = exp(g_t)`` in (0, 1] and ``b_t`` in [0, 2].  Two forms of
the same recurrence:

- **one step** (a decode row: ``T == 1``), the two lines above;
- **chunked** (a prefill chunk): inside a chunk of ``C`` tokens the
  per-token corrections ``u_j = b_j (v_j - a_j S_{j-1}^T k_j)`` solve one
  unit-lower-triangular system ``(I + A) U = b V - diag(b G) K S_0``
  with ``A[j, m] = b_j (G_j / G_m) (k_j . k_m)`` for ``m < j`` and ``G``
  the running product of ``a``, so a chunk is a handful of matmuls and
  the state is carried from chunk to chunk, never from token to token.

A column with ``g = 0`` and ``b = 0`` leaves the state as it was: that
is how the serve step's padded columns and empty rows change nothing.

Plain XLA on every backend (``dispatch_report()`` says ``reference``).
The op moves little (the state is read and written once per row and
step, a few tens of MB a layer) but runs as ~130 small operations a
layer: 2.0 ms a layer of a 74 ms mixed step of 12 rows x 64 columns at
the published widths, 3.7% of the state's memory roofline, a sixth of
the device's busy time (PERF.md, PR 27).  A Pallas kernel for the
chunked form has to keep several heads in flight per program to beat
that; it is not written.  The matmuls inside run at ``highest``
precision: they are small, and the state integrates their error over a
whole context.
"""

import jax
import jax.numpy as jnp

from .backend import note_dispatch

_HI = jax.lax.Precision.HIGHEST
CHUNK = 32  # tokens solved as one triangular system (tests shrink it)


def gated_delta_step(q, k, v, g, beta, state):
    """One token per row.  ``q``/``k`` [B, H, dk], ``v`` [B, H, dv],
    ``g``/``beta`` [B, H], ``state`` [B, H, dk, dv] float32.  Returns
    ``(o [B, H, dv] float32, new state)``."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    decayed = state * jnp.exp(g.astype(f32))[..., None, None]
    read = jnp.einsum("bhkv,bhk->bhv", decayed, k, precision=_HI)
    u = beta.astype(f32)[..., None] * (v - read)
    new = decayed + k[..., :, None] * u[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", new, q, precision=_HI)
    return o, new


def _unit_lower_inverse(A):
    """``(I + A)^-1`` of a strictly lower-triangular ``A`` [..., C, C] by
    recursive doubling over diagonal blocks: the inverse of ``[[L11, 0],
    [L21, L22]]`` is ``[[X11, 0], [-X22 L21 X11, X22]]``, so the inverses
    of the blocks of size ``b`` give those of size ``2b`` in two batched
    matmuls, ``log2 C`` levels in all and no loop over rows.  (XLA's
    triangular solve took a quarter of a mixed step here and row-by-row
    substitution a tenth, my chip runs, PR 27; a product of powers of
    ``A`` is cheaper still but loses its digits when keys repeat and the
    powers grow before they vanish.)"""
    C = A.shape[-1]
    P = 1 << (C - 1).bit_length()
    if P != C:  # a block of the identity below: its inverse is itself
        A = jnp.pad(A, [(0, 0)] * (A.ndim - 2) + [(0, P - C), (0, P - C)])
    X = jnp.ones(A.shape[:-2] + (P, 1, 1), A.dtype)   # blocks of size 1
    b = 1
    while b < P:
        # L21 of every diagonal block of size 2b: rows b.., columns ..b
        L21 = jnp.stack([A[..., i + b:i + 2 * b, i:i + b]
                         for i in range(0, P, 2 * b)], axis=-3)
        X11, X22 = X[..., 0::2, :, :], X[..., 1::2, :, :]
        X21 = -jnp.einsum("...ij,...jk,...kl->...il", X22, L21, X11,
                          precision=_HI)
        X = jnp.concatenate([
            jnp.concatenate([X11, jnp.zeros_like(X11)], axis=-1),
            jnp.concatenate([X21, X22], axis=-1)], axis=-2)
        b *= 2
    return X[..., 0, :C, :C]


def _chunk_operands(q, k, v, g, beta, chunk):
    """Everything of the chunked form that does not depend on the carried
    state, for all chunks at once.  Inputs [B, H, N, C, ...]."""
    G = jnp.cumsum(g, axis=-1)                          # log running decay
    C = chunk
    rows = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    # exp(G_i - G_j) where j <= i, masked BEFORE the exp: above the
    # diagonal the difference is positive and may overflow
    diff = G[..., :, None] - G[..., None, :]
    ratio = jnp.exp(jnp.where(cols <= rows, diff, -jnp.inf))
    kk = jnp.einsum("...ik,...jk->...ij", k, k, precision=_HI)
    A = jnp.where(cols < rows, beta[..., :, None] * kk * ratio, 0.0)
    T = _unit_lower_inverse(A)
    u = jnp.einsum("...ij,...jv->...iv", T, beta[..., None] * v,
                   precision=_HI)
    w = jnp.einsum("...ij,...jk->...ik", T,
                   (beta * jnp.exp(G))[..., None] * k, precision=_HI)
    qk = jnp.einsum("...ik,...jk->...ij", q, k, precision=_HI) * ratio
    return G, u, w, qk


def gated_delta_chunked(q, k, v, g, beta, state):
    """A run of ``T`` tokens per row.  ``q``/``k`` [B, T, H, dk], ``v``
    [B, T, H, dv], ``g``/``beta`` [B, T, H], ``state`` [B, H, dk, dv]
    float32.  Returns ``(o [B, T, H, dv] float32, new state)``."""
    f32 = jnp.float32
    B, T, H, dk = q.shape
    C = min(CHUNK, T)
    pad = -T % C
    heads_first = lambda x: jnp.moveaxis(x.astype(f32), 1, 2)
    q, k, v, g, beta = (heads_first(x) for x in (q, k, v, g, beta))
    if pad:
        # g = 0, beta = 0: the padded tail leaves the state alone
        widen = lambda x: jnp.pad(
            x, [(0, 0), (0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 3))
        q, k, v, g, beta = (widen(x) for x in (q, k, v, g, beta))
    N = (T + pad) // C
    split = lambda x: x.reshape(B, H, N, C, *x.shape[3:])
    q, k, v, g, beta = (split(x) for x in (q, k, v, g, beta))
    G, u, w, qk = _chunk_operands(q, k, v, g, beta, C)

    def one_chunk(S, xs):
        q_c, k_c, G_c, u_c, w_c, qk_c = xs
        u_new = u_c - jnp.einsum("bhck,bhkv->bhcv", w_c, S, precision=_HI)
        o = jnp.einsum("bhck,bhkv->bhcv", q_c * jnp.exp(G_c)[..., None], S,
                       precision=_HI)
        o = o + jnp.einsum("bhij,bhjv->bhiv", qk_c, u_new, precision=_HI)
        last = G_c[..., -1:]
        S = S * jnp.exp(last)[..., None] + jnp.einsum(
            "bhck,bhcv->bhkv", k_c * jnp.exp(last - G_c)[..., None], u_new,
            precision=_HI)
        return S, o

    chunks_first = lambda x: jnp.moveaxis(x, 2, 0)
    state, o = jax.lax.scan(
        one_chunk, state.astype(f32),
        tuple(chunks_first(x) for x in (q, k, G, u, w, qk)))
    o = jnp.moveaxis(o, 0, 2).reshape(B, H, N * C, -1)[:, :, :T]
    return jnp.moveaxis(o, 1, 2), state


def gated_delta_rule(q, k, v, g, beta, state):
    """The rule over ``[B, T, ...]`` operands from ``state`` [B, H, dk,
    dv]: the one-step form at ``T == 1``, the chunked form otherwise.
    Returns ``(o [B, T, H, dv] float32, new state float32)``."""
    B, T, H, dk = q.shape
    note_dispatch("gated_delta_rule",
                  "b%d w%d h%d k%d v%d %s" % (B, T, H, dk, v.shape[-1],
                                              q.dtype.name), False)
    with jax.named_scope("gated_delta_rule"):
        if T == 1:
            o, state = gated_delta_step(
                q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                state.astype(jnp.float32))
            return o[:, None], state
        return gated_delta_chunked(q, k, v, g, beta, state)


def short_conv(x, kernel, tail, valid):
    """Depthwise causal convolution over time with a carried tail.

    ``x`` [B, T, C] this step's inputs, ``kernel`` [K, C] (tap ``K - 1``
    multiplies the current token, tap 0 the one ``K - 1`` back), ``tail``
    [B, K - 1, C] the last ``K - 1`` inputs before ``x``, ``valid`` [B]
    how many leading columns of each row are real.  Returns ``(y [B, T,
    C], new tail)``: the new tail is the last ``K - 1`` REAL inputs, so
    padded columns and empty rows carry nothing forward."""
    K = kernel.shape[0]
    T = x.shape[1]
    note_dispatch("short_conv", "b%d w%d c%d k%d %s" % (
        x.shape[0], T, x.shape[2], K, x.dtype.name), False)
    with jax.named_scope("short_conv"):
        ext = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
        y = sum(ext[:, i:i + T] * kernel[i].astype(x.dtype)
                for i in range(K))
        at = valid[:, None] + jnp.arange(K - 1, dtype=valid.dtype)[None]
        new_tail = jnp.take_along_axis(ext, at[:, :, None], axis=1)
    return y, new_tail.astype(tail.dtype)
