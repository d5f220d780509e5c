"""Rotary position embeddings (RoPE, Su et al. 2021).

New capability relative to the reference (whose only position schemes are
learned absolute embeddings and the bucketed T5 relative bias,
``unicore/modules/transformer_encoder.py:100-124``): RoPE encodes
positions as a rotation of the q/k vectors BEFORE the score contraction,
so attention depends only on relative offsets while costing O(T·D)
elementwise work — no ``[1, H, T, T]`` bias tensor, which is what makes
it the long-context-scalable choice next to the quadratic rel-pos bias
(see docs/performance.md "Long context").  Applied outside the attention
kernel, it composes with every dispatch path: flash (causal in-block),
ring/Ulysses sequence parallelism, and the materialized fallback.

Layout [B, T, H, D]; rotate-half formulation: the head dim is split in
two halves (x1, x2) and rotated as (x1·cos − x2·sin, x2·cos + x1·sin).

A :class:`RotarySpec` says what ONE layer rotates, for a model whose
layers differ: the first ``lanes`` of the head (rotate-half within them;
the other lanes pass through), at ``theta``, plain or with YaRN's
frequencies (Peng et al. 2023, arXiv:2309.00071: the slow frequencies
divided by ``yarn_factor``, the fast ones kept, a linear ramp between
the two corrections) and cos / sin times ``attention_factor``.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class RotarySpec:
    """One layer's rotary: ``lanes`` of the head rotated (0: all of it)
    at ``theta``; ``yarn_factor`` > 0: YaRN over
    ``yarn_original_positions`` with the corrections ``yarn_beta_fast`` /
    ``yarn_beta_slow``; cos and sin are multiplied by
    ``attention_factor``."""
    theta: float = 10000.0
    lanes: int = 0
    yarn_factor: float = 0.0
    yarn_original_positions: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    attention_factor: float = 1.0


def yarn_inv_freq(dim, theta, factor, original_positions, beta_fast,
                  beta_slow):
    """YaRN's ``dim // 2`` inverse frequencies (float64): with ``f_i =
    theta^(-2i/dim)`` and ``c(r) = dim ln(original_positions / (2 pi r))
    / (2 ln theta)`` (the index whose wavelength turns ``r`` times in
    the original context), ``low = floor(c(beta_fast))``, ``high =
    ceil(c(beta_slow))`` held to ``[0, dim - 1]``, ``ramp_i = clip((i -
    low) / (high - low), 0, 1)``: ``f_i (1 - ramp_i) + (f_i / factor)
    ramp_i``.  Below ``low`` a frequency is kept, above ``high`` it is
    divided by ``factor``."""
    half = dim // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / dim)

    def turns_at(r):
        return dim * math.log(original_positions / (2 * math.pi * r)) / (
            2 * math.log(theta))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    if high == low:
        high += 0.001  # a ramp of no width is a step
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    return freq * (1.0 - ramp) + freq / factor * ramp


def spec_cos_sin(spec, head_dim, seq_len, positions=None):
    """cos / sin ``[..., T, lanes // 2]`` (float32) of one layer's
    :class:`RotarySpec`, positions as :func:`rotary_cos_sin` takes
    them."""
    lanes = spec.lanes or head_dim
    if spec.yarn_factor:
        inv_freq = yarn_inv_freq(
            lanes, spec.theta, spec.yarn_factor,
            spec.yarn_original_positions, spec.yarn_beta_fast,
            spec.yarn_beta_slow)
    else:
        inv_freq = spec.theta ** (
            -np.arange(lanes // 2, dtype=np.float64) * 2.0 / lanes)
    inv_freq = jnp.asarray(inv_freq, jnp.float32)
    if positions is None:
        positions = jnp.arange(seq_len, dtype=jnp.float32)
    else:
        positions = jnp.maximum(positions, 0).astype(jnp.float32)
    angles = positions[..., None] * inv_freq
    factor = jnp.float32(spec.attention_factor)
    return jnp.cos(angles) * factor, jnp.sin(angles) * factor


def apply_rotary_spec(q, k, spec, positions=None):
    """Rotate q and k ([B, T, H, D]) as ``spec`` says: the first
    ``spec.lanes`` of each head, the rest untouched."""
    lanes = spec.lanes or q.shape[-1]
    assert lanes % 2 == 0 and lanes <= q.shape[-1], (lanes, q.shape)
    with jax.named_scope("rope_yarn" if spec.yarn_factor else "rope"):
        cos, sin = spec_cos_sin(spec, q.shape[-1], q.shape[1], positions)

        def rotate(x):
            if lanes == x.shape[-1]:
                return apply_rotary(x, cos, sin)
            return jnp.concatenate(
                [apply_rotary(x[..., :lanes], cos, sin), x[..., lanes:]],
                axis=-1)

        return rotate(q), rotate(k)


def rotary_cos_sin(seq_len, dim, base=10000.0, positions=None,
                   dtype=jnp.float32):
    """cos/sin tables ``[T, dim//2]`` (or ``[B, T, dim//2]`` for per-
    sequence positions).  ``positions`` (optional ``[T]`` shared, or
    ``[B, T]`` ragged — incremental decode over right-padded prompts
    rotates each sequence at its own offset) overrides ``arange(T)`` —
    sequence-parallel callers pass their shard's global offsets.
    Negative positions (inactive/padded rows, masked downstream) clamp
    to 0 so the angle tables stay finite."""
    half = dim // 2
    inv_freq = 1.0 / (base ** (np.arange(0, half, dtype=np.float64) / half))
    inv_freq = jnp.asarray(inv_freq, jnp.float32)
    if positions is None:
        positions = jnp.arange(seq_len, dtype=jnp.float32)
    else:
        positions = jnp.maximum(positions, 0).astype(jnp.float32)
    angles = positions[..., None] * inv_freq  # [..., T, half]
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def apply_rotary(x, cos, sin):
    """Rotate ``x`` [B, T, H, D] by per-position angles (cos/sin
    [T, D//2] shared, or [B, T, D//2] per-sequence).

    fp32 rotation regardless of input dtype (the angle tables lose too
    much phase accuracy in bf16 at long T), cast back on return."""
    half = x.shape[-1] // 2
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    c = cos.astype(jnp.float32)[..., None, :]
    s = sin.astype(jnp.float32)[..., None, :]
    if c.ndim == 3:  # shared [T, 1, half]: add the batch axis
        c, s = c[None], s[None]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def apply_rotary_qk(q, k, base=10000.0, positions=None):
    """Rotate q and k ([B, T, H, D]) with shared tables; D must be even."""
    assert q.shape[-1] % 2 == 0, "rotary needs an even head dim"
    cos, sin = rotary_cos_sin(q.shape[1], q.shape[-1], base=base,
                              positions=positions)
    return apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)
