"""A decoder assembled from a per-layer pattern.

``layer_types`` names each layer's MIXER (``"full_attention"`` or
``"linear_attention"``); every layer shares one block shape, the
Olmo 2/3 convention: the norm sits on the sub-layer's OUTPUT,

    h = x + RMSNorm(Mixer(x))
    y = h + RMSNorm(W_down(silu(W_gate h) * W_up h))

with no bias anywhere.  The two mixers hold two kinds of cache in the
serve tier's ``"pagedkv"`` collection, both addressed through
:class:`~unicore_tpu.serve.attention.PagedMeta`:

- ``full_attention``: K/V pages, one slot per token (``k_pages`` /
  ``v_pages``, ``[num_slots, H * D]``), written at ``slot_mapping`` and
  read through the page table by the ragged paged attention op, which
  sorts the step's flat token list into its ``[rows, width]`` rectangle
  (``serve/attention.py`` ``write_and_attend``).  QK-norm over the whole
  projection, no rotary.
- ``linear_attention``: one fixed-size recurrent state per SEQUENCE
  (``ssm_state`` ``[num_state_slots, H, dk, dv]`` float32) and the short
  convolution's tail (``conv_tail`` ``[num_state_slots, K - 1, channels]``),
  gathered by ``state_slots`` for the rows of a step and scattered back
  in the same program.  The projections, the output norm and its gate
  run on the tokens as they come (a serve step's flat list, ``[1, N,
  ...]``); only the chain (short convolution, the rule) gets rows: with
  ``paged.rect_token`` its inputs are gathered into ``[rows, width]``
  and its output goes back by ``paged.token_cell``, and when the tokens
  ARE the rectangle (the decode step's one token a row) they are only
  reshaped.  A row whose first column is position 0 starts from zeros,
  so a slot needs no host-side clearing; a padded column (position -1,
  and every cell no token fills) changes neither state nor tail, and an
  empty row's slot is out of range, so its write is dropped.

Without ``paged`` a call is one full causal pass from zero state (init,
training-style forwards, tests): ``[B, T]`` is the rectangle.
"""

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from unicore_tpu.ops.gated_delta_rule import gated_delta_rule, short_conv

from .multihead_attention import bert_init

FULL, LINEAR = "full_attention", "linear_attention"


class Linear(nn.Module):
    """``x @ kernel``, no bias (the parameter tree of ``nn.Dense``).

    Float32 operands are multiplied in float32: on a TPU the default
    float32 matmul rounds both operands to bfloat16 first, and with
    output-normed residuals and unit-norm q/k every sub-layer hands that
    rounding on at full size: at the published widths the widest logit
    gap to the plain reference was 0.04-0.2, as with bfloat16 weights
    (0.18), and is under 0.002 at ``HIGH`` (three bfloat16 passes, 2^-16
    of a product; PERF.md, PR 27).  Operands that are bfloat16 already
    take the default, one exact pass."""
    features: int

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", bert_init,
                            (x.shape[-1], self.features), jnp.float32)
        dtype = jnp.result_type(x.dtype, kernel.dtype)
        precision = jax.lax.Precision.HIGH if dtype == jnp.float32 else None
        return jnp.dot(x.astype(dtype), kernel.astype(dtype),
                       precision=precision)


class RMSNorm(nn.Module):
    dim: int
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        weight = self.param("weight", nn.initializers.ones, (self.dim,),
                            jnp.float32)
        xf = x.astype(jnp.float32)
        inv = jax.lax.rsqrt(
            jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + self.eps)
        return (xf * inv).astype(x.dtype) * weight.astype(x.dtype)


class GatedFFN(nn.Module):
    """SwiGLU: ``W_down(silu(W_gate x) * W_up x)``."""
    embed_dim: int
    ffn_embed_dim: int

    @nn.compact
    def __call__(self, x):
        gate = Linear(self.ffn_embed_dim, name="gate_proj")(x)
        up = Linear(self.ffn_embed_dim, name="up_proj")(x)
        return Linear(self.embed_dim, name="down_proj")(
            jax.nn.silu(gate) * up)


class FullAttentionMixer(nn.Module):
    embed_dim: int
    num_heads: int
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x, positions=None, paged=None):
        B, T, D = x.shape
        H, hd = self.num_heads, self.embed_dim // self.num_heads
        q = RMSNorm(D, self.eps, name="q_norm")(Linear(D, name="q_proj")(x))
        k = RMSNorm(D, self.eps, name="k_norm")(Linear(D, name="k_proj")(x))
        v = Linear(D, name="v_proj")(x)
        q, k, v = (t.reshape(B, T, H, hd) for t in (q, k, v))
        scale = hd ** -0.5
        ready = paged is not None and self.has_variable("pagedkv", "k_pages")
        if paged is not None:
            nslots = None if ready else int(paged.num_slots)
            k_pages = self.variable("pagedkv", "k_pages", jnp.zeros,
                                    (nslots, D), k.dtype)
            v_pages = self.variable("pagedkv", "v_pages", jnp.zeros,
                                    (nslots, D), v.dtype)
        if ready:
            from unicore_tpu.serve.attention import write_and_attend

            o = write_and_attend(q, k, v, k_pages, v_pages, paged, positions,
                                 scale)
        else:
            from unicore_tpu.utils import causal_iota_mask

            s = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k)
            s = s + causal_iota_mask(T, T)[None, None]
            p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
            o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        return Linear(D, name="o_proj")(o.reshape(B, T, D))


class LinearAttentionMixer(nn.Module):
    """The gated-delta-rule mixer (module docstring; equations in
    ``ops/gated_delta_rule.py``)."""
    embed_dim: int
    num_heads: int
    key_head_dim: int
    value_head_dim: int
    conv_kernel_dim: int = 4
    allow_neg_eigval: bool = True
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x, positions=None, paged=None):
        lead = x.shape[:-1]
        H, dk, dv = self.num_heads, self.key_head_dim, self.value_head_dim
        K = self.conv_kernel_dim
        nk, nv = H * dk, H * dv
        u = jnp.concatenate(
            [Linear(nk, name="q_proj")(x), Linear(nk, name="k_proj")(x),
             Linear(nv, name="v_proj")(x)], axis=-1)
        conv_kernel = self.param("conv_kernel", bert_init,
                                 (K, 2 * nk + nv), jnp.float32)
        A_log = self.param("A_log", nn.initializers.zeros, (H,), jnp.float32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (H,),
                             jnp.float32)
        xf = x.astype(jnp.float32)
        beta = jax.nn.sigmoid(Linear(H, name="b_proj")(xf))
        if self.allow_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(A_log) * jax.nn.softplus(Linear(H, name="a_proj")(xf)
                                              + dt_bias)

        ready = paged is not None and self.has_variable("pagedkv", "ssm_state")
        if paged is not None:
            nstate = None if ready else int(paged.num_state_slots)
            ssm = self.variable("pagedkv", "ssm_state", jnp.zeros,
                                (nstate, H, dk, dv), jnp.float32)
            tails = self.variable("pagedkv", "conv_tail", jnp.zeros,
                                  (nstate, K - 1, 2 * nk + nv), u.dtype)
        if ready:
            # the chain is the one part that needs rows (module
            # docstring); position -1 keeps a cell no token fills out of
            # state, tail and every real column's output
            u, g, beta = (paged.to_rows(t) for t in (u, g, beta))
            positions = paged.row_positions(positions)
            slots = paged.state_slots
            real = positions >= 0                             # [B, T]
            fresh = positions[:, 0] == 0
            state = jnp.where(
                fresh[:, None, None, None], 0.0,
                jnp.take(ssm.value, slots, axis=0, mode="clip"))
            tail = jnp.where(
                fresh[:, None, None], 0.0,
                jnp.take(tails.value, slots, axis=0, mode="clip"))
            valid = jnp.sum(real, axis=1, dtype=jnp.int32)
            g = jnp.where(real[..., None], g, 0.0)
            beta = jnp.where(real[..., None], beta, 0.0)
        else:
            state = jnp.zeros(u.shape[:1] + (H, dk, dv), jnp.float32)
            tail = jnp.zeros(u.shape[:1] + (K - 1, 2 * nk + nv), u.dtype)
            valid = jnp.full(u.shape[:1], u.shape[1], jnp.int32)
        B, T = u.shape[:2]

        u, tail = short_conv(u, conv_kernel, tail, valid)
        u = jax.nn.silu(u)
        q, k, v = jnp.split(u, [nk, 2 * nk], axis=-1)
        q = q.reshape(B, T, H, dk).astype(jnp.float32)
        k = k.reshape(B, T, H, dk).astype(jnp.float32)
        v = v.reshape(B, T, H, dv)
        unit = lambda t: t * jax.lax.rsqrt(
            jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-12)
        q, k = unit(q) * dk ** -0.5, unit(k)
        o, state = gated_delta_rule(q, k, v, g, beta, state)
        if ready:
            # an empty row's slot is out of range: its write is dropped
            ssm.value = ssm.value.at[slots].set(
                state, mode="drop", unique_indices=True)
            tails.value = tails.value.at[slots].set(
                tail, mode="drop", unique_indices=True)
            o = paged.to_tokens(o, lead)

        o = RMSNorm(dv, self.eps, name="o_norm")(o.astype(x.dtype))
        gate = jax.nn.silu(Linear(nv, name="g_proj")(x))
        return Linear(self.embed_dim, name="o_proj")(
            o.reshape(lead + (nv,)) * gate)


class PatternDecoderLayer(nn.Module):
    mixer: str
    embed_dim: int
    ffn_embed_dim: int
    num_heads: int
    linear_num_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x, positions=None, paged=None):
        if self.mixer == FULL:
            mixer = FullAttentionMixer(
                self.embed_dim, self.num_heads, self.eps, name="self_attn")
        elif self.mixer == LINEAR:
            mixer = LinearAttentionMixer(
                self.embed_dim, self.linear_num_heads,
                self.linear_key_head_dim, self.linear_value_head_dim,
                self.linear_conv_kernel_dim, self.linear_allow_neg_eigval,
                self.eps, name="linear_attn")
        else:
            raise ValueError(f"unknown mixer kind {self.mixer!r} (known: "
                             f"{FULL!r}, {LINEAR!r})")
        h = x + RMSNorm(self.embed_dim, self.eps,
                        name="post_attention_layernorm")(
            mixer(x, positions=positions, paged=paged))
        ffn = GatedFFN(self.embed_dim, self.ffn_embed_dim, name="mlp")(h)
        return h + RMSNorm(self.embed_dim, self.eps,
                           name="post_feedforward_layernorm")(ffn)


class PatternDecoder(nn.Module):
    """``len(layer_types)`` blocks in the order given, then the final
    RMSNorm.  Embedding and head are the caller's."""
    layer_types: Tuple[str, ...]
    embed_dim: int
    ffn_embed_dim: int
    num_heads: int
    linear_num_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x, positions: Optional[jnp.ndarray] = None,
                 paged=None):
        for i, kind in enumerate(self.layer_types):
            x = PatternDecoderLayer(
                kind, self.embed_dim, self.ffn_embed_dim, self.num_heads,
                self.linear_num_heads, self.linear_key_head_dim,
                self.linear_value_head_dim, self.linear_conv_kernel_dim,
                self.linear_allow_neg_eigval, self.eps,
                name=f"layers_{i}",
            )(x, positions=positions, paged=paged)
        return RMSNorm(self.embed_dim, self.eps, name="final_layer_norm")(x)
