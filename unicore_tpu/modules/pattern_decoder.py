"""A decoder assembled from a per-layer pattern.

``layer_types`` names each layer's MIXER (``"full_attention"``,
``"linear_attention"``, ``"conv"`` or ``"latent_attention"``) and
``ffn_types`` its feed-forward (``"dense"`` or ``"experts"``; empty: dense
everywhere); the block's residual form is the model's
(``norm_placement``):

    output (Olmo 2/3)   h = x + RMSNorm(Mixer(x))
                        y = h + RMSNorm(FFN(h))
    input  (pre-norm)   h = x + Mixer(RMSNorm(x))
                        y = h + FFN(RMSNorm(h))
    both   (sandwich)   h = x + RMSNorm(Mixer(RMSNorm(x)))
                        y = h + RMSNorm(FFN(RMSNorm(h)))

with no bias anywhere.  The dense FFN is SwiGLU; the expert FFN a router
over ``experts.num_experts`` SwiGLU experts (``ops/moe.py``), of which a
token of the serve step's list that nobody carries (position -1) reaches
none, beside ``experts.shared_experts`` that every token gets.  The
mixers hold their caches in the serve tier's ``"pagedkv"`` collection,
all addressed through :class:`~unicore_tpu.serve.attention.PagedMeta`:

- ``full_attention``: K/V pages, one slot per token (``k_pages`` /
  ``v_pages``, ``[num_slots, kv_heads * D]``), written at
  ``slot_mapping`` and read through the page table by the ragged paged
  attention op, which sorts the step's flat token list into its ``[rows,
  width]`` rectangle (``serve/attention.py`` ``write_and_attend``, which
  also folds grouped query heads).  QK-norm over the whole projection
  and no rotary (Olmo), or per head with rotary after it
  (``qk_norm_per_head``, ``rope_theta``).  A layer may bring an
  :class:`AttentionSpec` of its own (``PatternDecoder.attention``, one
  entry a layer): its query heads and an explicit ``head_dim`` (the
  projections are ``heads x head_dim`` wide whatever the hidden size), a
  SLIDING WINDOW (a query sees ``window`` keys up to its own; the
  layer's pages are then the serve tier's window kind, ``k_window_pages``
  / ``v_window_pages``, which the pool trims behind the window), its
  rotary (:class:`~unicore_tpu.modules.rotary.RotarySpec`: part of the
  head, YaRN), no QK-norm, and a per-head output gate ``o_h = sigmoid(x
  W_g)_h * o_h``.
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
- ``conv`` (the gated short convolution): a state that is ONLY a tail,
  the last ``K - 1`` gated inputs of the sequence (``conv_tail``
  ``[num_state_slots, K - 1, embed_dim]``), through the same rows, slots
  and rules as the linear-attention layer's.
- ``latent_attention`` (multi-head latent attention): ONE vector per
  token for all heads, ``latent_pages`` ``[num_slots, LATENT_LANES *
  k]``: the normed latent ``c_kv`` (``kv_lora_rank``), the rotated rope
  key shared by the heads (``qk_rope_head_dim``), zeros up to whole
  128-lane slabs (512 + 64 + 64 at the published widths).  A serve step
  attends in the ABSORBED form (``serve/attention.py``
  ``write_latent_and_attend``): the key up-projection folded into the
  query and the value up-projection applied to the output, so the entry
  itself is key and value and a row's context is never expanded to
  per-head keys and values.

An expert FFN keeps two counters beside the caches, in the same donated
collection: ``moe_load`` (tokens each expert got, summed over steps) and
``moe_touched`` (experts that got a token, summed over steps); the
engine reads them on demand (``ServeEngine.moe_stats``).  A layer that
holds a SHARE of its experts counts ``moe_load`` over all of them (the
router's histogram is the deployment's), ``moe_touched`` over its own,
and keeps a third, ``moe_held``: the choices that landed on its own.

Without ``paged`` a call is one full causal pass from zero state (init,
training-style forwards, tests): ``[B, T]`` is the rectangle.
"""

import dataclasses
import functools
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from unicore_tpu.ops import moe
from unicore_tpu.ops.gated_delta_rule import gated_delta_rule, short_conv

from .multihead_attention import bert_init
from .rotary import RotarySpec, apply_rotary_qk, apply_rotary_spec

FULL, LINEAR, CONV = "full_attention", "linear_attention", "conv"
LATENT = "latent_attention"
DENSE, EXPERTS = "dense", "experts"
LATENT_LANES = 128   # a latent page is whole lane slabs wide


@dataclasses.dataclass(frozen=True)
class ExpertSpec:
    """An expert FFN's sizes: ``num_experts`` routed, ``top_k`` a token,
    each a SwiGLU of ``ffn_dim``; ``use_bias``: a per-expert bias on the
    selection (False: the scores alone choose); ``scale``: the factor on
    the renormalised weights, ``eps`` what their sum is divided with
    (None: ``ops.moe.route``'s own 1e-6).  The layer holds experts
    ``first_expert .. first_expert + experts_held - 1`` (``experts_held``
    0: all of them) and computes their part, beside ``shared_experts``
    that every token gets (one SwiGLU of ``shared_experts x ffn_dim``)."""
    num_experts: int
    top_k: int
    ffn_dim: int
    use_bias: bool = True
    scale: float = 1.0
    first_expert: int = 0
    experts_held: int = 0
    eps: Optional[float] = None
    shared_experts: int = 0


@dataclasses.dataclass(frozen=True)
class LatentSpec:
    """A latent-attention mixer's sizes, under the published names."""
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int


@dataclasses.dataclass(frozen=True)
class AttentionSpec:
    """What one ``full_attention`` layer has of its own, where the layers
    of a model differ: ``num_heads`` query heads of ``head_dim`` over the
    decoder's K/V heads; ``window`` > 0: a sliding layer; ``rotary``: what
    it rotates (None: nothing); no QK-norm (a layer with a spec has
    none); ``gate``: a per-head sigmoid gate on the heads' outputs, from
    the layer's input; ``three_pass``: the serve kernel's float32 dots in
    three bfloat16 passes (``ops/pallas/paged_attention.py`` says who
    asks)."""
    num_heads: int
    head_dim: int
    window: int = 0
    rotary: Optional[RotarySpec] = None
    gate: bool = False
    three_pass: bool = False


def _float32_in_float32(a, b):
    """The type two operands multiply in and the precision for it: float32
    at ``HIGH`` (:class:`Linear` says why), anything else at the
    default."""
    dtype = jnp.result_type(a.dtype, b.dtype)
    return dtype, (jax.lax.Precision.HIGH if dtype == jnp.float32 else None)


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
        dtype, precision = _float32_in_float32(x, kernel)
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
    kv_heads: int = 0            # 0: as many as query heads
    qk_norm_per_head: bool = False
    rope_theta: float = 0.0      # 0: no rotary
    # the layer's own (module docstring); None: the fields above, heads
    # of ``embed_dim // num_heads``, QK-norm, no window, no gate
    spec: Optional[AttentionSpec] = None

    @nn.compact
    def __call__(self, x, positions=None, paged=None):
        B, T, D = x.shape
        sp = self.spec
        H = sp.num_heads if sp else self.num_heads
        hd = sp.head_dim if sp else self.embed_dim // H
        KV = self.kv_heads or H
        normed = sp is None
        window = sp.window if sp else 0
        # the norm over the whole projection (Olmo), or over each head
        whole = normed and not self.qk_norm_per_head
        q = Linear(H * hd, name="q_proj")(x)
        if whole:
            q = RMSNorm(H * hd, self.eps, name="q_norm")(q)
        k = Linear(KV * hd, name="k_proj")(x)
        if whole:
            k = RMSNorm(KV * hd, self.eps, name="k_norm")(k)
        v = Linear(KV * hd, name="v_proj")(x)
        q = q.reshape(B, T, H, hd)
        k, v = (t.reshape(B, T, KV, hd) for t in (k, v))
        if normed and not whole:
            q = RMSNorm(hd, self.eps, name="q_norm")(q)
            k = RMSNorm(hd, self.eps, name="k_norm")(k)
        if sp is not None and sp.rotary is not None:
            q, k = apply_rotary_spec(q, k, sp.rotary, positions=positions)
        elif sp is None and self.rope_theta:
            q, k = apply_rotary_qk(q, k, base=self.rope_theta,
                                   positions=positions)
        scale = hd ** -0.5
        # a sliding layer's pages are the window kind's, under their own
        # names: the engine tells the two kinds' bytes apart by them
        names = ("k_window_pages", "v_window_pages") if window else (
            "k_pages", "v_pages")
        ready = paged is not None and self.has_variable("pagedkv", names[0])
        if paged is not None:
            nslots = None if ready else int(
                paged.num_window_slots if window else paged.num_slots)
            k_pages = self.variable("pagedkv", names[0], jnp.zeros,
                                    (nslots, KV * hd), k.dtype)
            v_pages = self.variable("pagedkv", names[1], jnp.zeros,
                                    (nslots, KV * hd), v.dtype)
        if ready:
            from unicore_tpu.serve.attention import write_and_attend

            o = write_and_attend(
                q, k, v, k_pages, v_pages, paged, positions, scale,
                window=window, three_pass=bool(sp and sp.three_pass))
        else:
            from unicore_tpu.utils import causal_iota_mask

            if KV != H:
                k, v = (jnp.repeat(t, H // KV, axis=2) for t in (k, v))
            s = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k)
            s = s + causal_iota_mask(T, T)[None, None]
            if window:
                at = jnp.arange(T)
                s = s + jnp.where(at[None, :] <= at[:, None] - window,
                                  -1e30, 0.0)[None, None]
            p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
            o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        if sp is not None and sp.gate:
            with jax.named_scope("attn_gate"):
                gate = jax.nn.sigmoid(Linear(H, name="g_proj")(x))
                o = o * gate[..., None].astype(o.dtype)
        return Linear(D, name="o_proj")(o.reshape(B, T, H * hd))


def _einsum(spec, a, b):
    """Float32 operands multiply in float32, as :class:`Linear`'s do."""
    dtype, precision = _float32_in_float32(a, b)
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      precision=precision)


class LatentAttentionMixer(nn.Module):
    """Multi-head latent attention::

        c_q = N_q(W_qa x);  [q_nope_h, q_rope_h] = W_qb,h c_q
        [c, k_r] = split(W_kva x);  c_kv = N_kv(c);  k_r = RoPE(k_r)
        [k_nope_h, v_h] = W_kvb,h c_kv
        score_h(t, s) = (q_nope_h(t) . k_nope_h(s)
                         + RoPE(q_rope_h)(t) . k_r(s)) / sqrt(nope + rope)
        out = W_o concat_h(softmax_s(score_h) v_h)

    ``k_r`` is ONE rope key for all heads.  What a token leaves in the
    cache is ``c_kv`` after its norm and ``k_r`` after its rotation.
    Without pages this is the per-head form as written; a serve step
    takes the absorbed form, equal in exact arithmetic: ``q_lat_h =
    W_kvb,K,h^T q_nope_h``, scores against the entries themselves,
    ``o_h = W_kvb,V,h sum_s p_h c_kv(s)`` (module docstring)."""
    embed_dim: int
    num_heads: int
    spec: LatentSpec
    eps: float = 1e-6
    rope_theta: float = 10000.0

    @nn.compact
    def __call__(self, x, positions=None, paged=None):
        sp, H = self.spec, self.num_heads
        L, nope, rope, vd = (sp.kv_lora_rank, sp.qk_nope_head_dim,
                             sp.qk_rope_head_dim, sp.v_head_dim)
        lead = x.shape[:-1]
        scale = (nope + rope) ** -0.5
        with jax.named_scope("mla_project"):
            c_q = RMSNorm(sp.q_lora_rank, self.eps, name="q_a_layernorm")(
                Linear(sp.q_lora_rank, name="q_a_proj")(x))
            q = Linear(H * (nope + rope), name="q_b_proj")(c_q).reshape(
                lead + (H, nope + rope))
            c_kv, k_r = jnp.split(
                Linear(L + rope, name="kv_a_proj_with_mqa")(x), [L], axis=-1)
            c_kv = RMSNorm(L, self.eps, name="kv_a_layernorm")(c_kv)
            q_nope, q_r = q[..., :nope], q[..., nope:]
            q_r, k_r = apply_rotary_qk(q_r, k_r[..., None, :],
                                       base=self.rope_theta,
                                       positions=positions)
        kv_b = Linear(H * (nope + vd), name="kv_b_proj")
        out = Linear(self.embed_dim, name="o_proj")
        lanes = -(-(L + rope) // LATENT_LANES) * LATENT_LANES
        ready = paged is not None and self.has_variable("pagedkv",
                                                        "latent_pages")
        if paged is not None:
            nslots = None if ready else int(paged.num_slots)
            pages = self.variable("pagedkv", "latent_pages", jnp.zeros,
                                  (nslots, lanes), c_kv.dtype)
        if ready:
            from unicore_tpu.serve.attention import write_latent_and_attend

            w_kv = kv_b.variables["params"]["kernel"].reshape(
                L, H, nope + vd)
            pad = jnp.zeros(lead + (H, lanes - L - rope), q.dtype)
            with jax.named_scope("mla_project"):
                q_lat = _einsum("...hn,lhn->...hl", q_nope, w_kv[..., :nope])
            o_lat = write_latent_and_attend(
                jnp.concatenate([q_lat, q_r, pad], axis=-1),
                jnp.concatenate([c_kv, k_r[..., 0, :], pad[..., 0, :]],
                                axis=-1),
                pages, paged, positions, scale, value_lanes=L)
            with jax.named_scope("mla_project"):
                o = _einsum("...hl,lhv->...hv", o_lat, w_kv[..., nope:])
        else:
            from unicore_tpu.utils import causal_iota_mask

            T = x.shape[-2]
            kv = kv_b(c_kv).reshape(lead + (H, nope + vd))
            k_nope, v = kv[..., :nope], kv[..., nope:]
            s = (_einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
                 + _einsum("bqhd,bkd->bhqk", q_r, k_r[..., 0, :])) * scale
            s = s + causal_iota_mask(T, T)[None, None]
            p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
            o = _einsum("bhqk,bkhd->bqhd", p, v)
        return out(o.reshape(lead + (H * vd,)))


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


class ShortConvMixer(nn.Module):
    """The gated short convolution: ``[B, C, u] = split3(W_in x)``, ``z =
    B * u``, a depthwise causal convolution of ``kernel_dim`` taps over
    ``z`` (no bias, no activation), ``W_out (C * conv)``.  The state of a
    sequence is its last ``kernel_dim - 1`` values of ``z`` (module
    docstring)."""
    embed_dim: int
    kernel_dim: int = 3

    @nn.compact
    def __call__(self, x, positions=None, paged=None):
        lead = x.shape[:-1]
        D, K = self.embed_dim, self.kernel_dim
        gate_in, gate_out, u = jnp.split(
            Linear(3 * D, name="in_proj")(x), 3, axis=-1)
        z = gate_in * u
        conv_kernel = self.param("conv_kernel", bert_init, (K, D),
                                 jnp.float32)
        ready = paged is not None and self.has_variable("pagedkv",
                                                        "conv_tail")
        if paged is not None:
            nstate = None if ready else int(paged.num_state_slots)
            tails = self.variable("pagedkv", "conv_tail", jnp.zeros,
                                  (nstate, K - 1, D), z.dtype)
        if ready:
            # as LinearAttentionMixer: the chain gets rows, a row at
            # position 0 starts from zeros, position -1 carries nothing
            z = paged.to_rows(z)
            positions = paged.row_positions(positions)
            slots = paged.state_slots
            fresh = positions[:, 0] == 0
            tail = jnp.where(
                fresh[:, None, None], 0.0,
                jnp.take(tails.value, slots, axis=0, mode="clip"))
            valid = jnp.sum(positions >= 0, axis=1, dtype=jnp.int32)
        else:
            tail = jnp.zeros(z.shape[:1] + (K - 1, D), z.dtype)
            valid = jnp.full(z.shape[:1], z.shape[1], jnp.int32)
        c, tail = short_conv(z, conv_kernel, tail, valid)
        if ready:
            tails.value = tails.value.at[slots].set(
                tail, mode="drop", unique_indices=True)
            c = paged.to_tokens(c, lead)
        return Linear(D, name="out_proj")(gate_out * c)


class ExpertFFN(nn.Module):
    """A router over ``spec.num_experts`` SwiGLU experts (``ops/moe.py``
    has the equations).  The scores are computed in float32 at
    ``highest``: they decide which expert runs."""
    embed_dim: int
    spec: ExpertSpec

    @nn.compact
    def __call__(self, x, positions=None, paged=None):
        sp, D = self.spec, self.embed_dim
        held = sp.experts_held or sp.num_experts
        router = self.param("router", bert_init, (D, sp.num_experts),
                            jnp.float32)
        bias = self.param("expert_bias", nn.initializers.zeros,
                          (sp.num_experts,), jnp.float32) \
            if sp.use_bias else None
        w1 = self.param("w1", bert_init, (held, D, sp.ffn_dim), jnp.float32)
        w3 = self.param("w3", bert_init, (held, D, sp.ffn_dim), jnp.float32)
        w2 = self.param("w2", bert_init, (held, sp.ffn_dim, D), jnp.float32)
        tokens = x.reshape(-1, D)
        scores = jax.nn.sigmoid(jnp.dot(
            tokens.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        sel, w = moe.route(scores, bias, sp.top_k, sp.scale,
                           **({} if sp.eps is None else {"eps": sp.eps}))
        # a token of the step's list that nobody carries: position -1
        valid = None if paged is None else positions.reshape(-1) >= 0
        y, load = moe.expert_ffn(tokens, valid, w1, w3, w2, sel, w,
                                 first_expert=sp.first_expert,
                                 num_experts=sp.num_experts)
        share = held < sp.num_experts
        ready = paged is not None and self.has_variable("pagedkv",
                                                        "moe_load")
        if paged is not None:
            total = self.variable("pagedkv", "moe_load", jnp.zeros,
                                  (sp.num_experts,), jnp.int32)
            touched = self.variable("pagedkv", "moe_touched", jnp.zeros,
                                    (), jnp.int32)
            if share:
                landed = self.variable("pagedkv", "moe_held", jnp.zeros,
                                       (), jnp.int32)
        if ready:
            held_load = load
            if share:
                # the choices that landed on the experts held here, and
                # the router's histogram over ALL experts
                landed.value = landed.value + jnp.sum(held_load)
                chose = sel[..., None] == jnp.arange(sp.num_experts,
                                                     dtype=sel.dtype)
                load = jnp.sum(chose & valid[:, None, None], axis=(0, 1),
                               dtype=jnp.int32)
            total.value = total.value + load
            touched.value = touched.value + jnp.sum(held_load > 0,
                                                    dtype=jnp.int32)
        y = y.reshape(x.shape)
        if sp.shared_experts:
            with jax.named_scope("moe_shared_expert"):
                y = y + GatedFFN(D, sp.shared_experts * sp.ffn_dim,
                                 name="shared_experts")(x)
        return y


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
    kv_heads: int = 0
    qk_norm_per_head: bool = False
    rope_theta: float = 0.0
    short_conv_kernel_dim: int = 3
    norm_placement: str = "output"
    experts: Optional[ExpertSpec] = None    # None: the dense FFN
    latent: Optional[LatentSpec] = None     # a latent_attention layer's
    attention: Optional[AttentionSpec] = None  # a full_attention layer's own

    @nn.compact
    def __call__(self, x, positions=None, paged=None):
        if self.mixer == LATENT:
            mixer = LatentAttentionMixer(
                self.embed_dim, self.num_heads, self.latent, self.eps,
                self.rope_theta, name="self_attn")
        elif self.mixer == FULL:
            mixer = FullAttentionMixer(
                self.embed_dim, self.num_heads, self.eps, self.kv_heads,
                self.qk_norm_per_head, self.rope_theta, self.attention,
                name="self_attn")
        elif self.mixer == LINEAR:
            mixer = LinearAttentionMixer(
                self.embed_dim, self.linear_num_heads,
                self.linear_key_head_dim, self.linear_value_head_dim,
                self.linear_conv_kernel_dim, self.linear_allow_neg_eigval,
                self.eps, name="linear_attn")
        elif self.mixer == CONV:
            mixer = ShortConvMixer(self.embed_dim,
                                   self.short_conv_kernel_dim, name="conv")
        else:
            raise ValueError(f"unknown mixer kind {self.mixer!r} (known: "
                             f"{FULL!r}, {LINEAR!r}, {CONV!r}, {LATENT!r})")
        norm = lambda name: RMSNorm(self.embed_dim, self.eps, name=name)
        if self.experts is None:
            ffn = GatedFFN(self.embed_dim, self.ffn_embed_dim,
                           name="mlp" if self.norm_placement == "output"
                           else "feed_forward")
        else:
            ffn = functools.partial(
                ExpertFFN(self.embed_dim, self.experts, name="feed_forward"),
                positions=positions, paged=paged)
        if self.norm_placement == "output":
            h = x + norm("post_attention_layernorm")(
                mixer(x, positions=positions, paged=paged))
            return h + norm("post_feedforward_layernorm")(ffn(h))
        if self.norm_placement == "both":
            h = x + norm("post_attention_layernorm")(mixer(
                norm("input_layernorm")(x), positions=positions, paged=paged))
            return h + norm("post_mlp_layernorm")(
                ffn(norm("pre_mlp_layernorm")(h)))
        if self.norm_placement != "input":
            raise ValueError(f"unknown norm placement "
                             f"{self.norm_placement!r} (output, input, both)")
        h = x + mixer(norm("operator_norm")(x), positions=positions,
                      paged=paged)
        return h + ffn(norm("ffn_norm")(h))


class PatternDecoder(nn.Module):
    """``len(layer_types)`` blocks in the order given, then the final
    RMSNorm.  Embedding and head are the caller's."""
    layer_types: Tuple[str, ...]
    embed_dim: int
    ffn_embed_dim: int
    num_heads: int
    linear_num_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    eps: float = 1e-6
    kv_heads: int = 0
    qk_norm_per_head: bool = False
    rope_theta: float = 0.0
    short_conv_kernel_dim: int = 3
    norm_placement: str = "output"
    ffn_types: Tuple[str, ...] = ()         # empty: dense everywhere
    experts: Optional[ExpertSpec] = None
    latent: Optional[LatentSpec] = None
    # one entry a layer where the full_attention layers differ (None for
    # a layer of another kind); empty: the fields above for all of them
    attention: Tuple[Optional[AttentionSpec], ...] = ()

    @nn.compact
    def __call__(self, x, positions: Optional[jnp.ndarray] = None,
                 paged=None):
        for i, kind in enumerate(self.layer_types):
            sparse = bool(self.ffn_types) and self.ffn_types[i] == EXPERTS
            x = PatternDecoderLayer(
                kind, self.embed_dim, self.ffn_embed_dim, self.num_heads,
                self.linear_num_heads, self.linear_key_head_dim,
                self.linear_value_head_dim, self.linear_conv_kernel_dim,
                self.linear_allow_neg_eigval, self.eps, self.kv_heads,
                self.qk_norm_per_head, self.rope_theta,
                self.short_conv_kernel_dim, self.norm_placement,
                self.experts if sparse else None, self.latent,
                self.attention[i] if self.attention else None,
                name=f"layers_{i}",
            )(x, positions=positions, paged=paged)
        return RMSNorm(self.embed_dim, self.eps, name="final_layer_norm")(x)
