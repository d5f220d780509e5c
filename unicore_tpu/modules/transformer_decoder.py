"""Transformer decoder with causal masking and cross-attention.

Parity target: ``unicore/modules/transformer_decoder.py`` and
``transformer_decoder_layer.py`` (self-attn -> optional cross-attn -> FFN).
Causal-semantics difference by design: the reference merges a
materialized future mask into the additive attention mask
(``transformer_decoder.py:19-22,106-121``); here ``auto_regressive``
flows to the attention core as a flag so the flash kernel masks
in-block and the materialized path builds the mask from fused iota
compares — no [T, T] tensor in HBM (the sequence-parallel path takes
``causal=`` natively too).

This is the LayerNorm / one-activation / multi-head block with a K/V
page per token in every layer; a decoder whose layers differ in kind
(linear-attention layers beside full attention, RMSNorm, a gated FFN)
is ``pattern_decoder.py``.
"""

from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from unicore_tpu.ops import dropout as ops_dropout

from .layer_norm import LayerNorm
from .multihead_attention import _BATCH_AXES, CrossMultiheadAttention, SelfMultiheadAttention, bert_init
from .transformer_encoder import RelativePositionBias
from unicore_tpu.parallel import tp_constraint
from unicore_tpu.utils import get_activation_fn


class TransformerDecoderLayer(nn.Module):
    embed_dim: int = 768
    ffn_embed_dim: int = 3072
    attention_heads: int = 8
    dropout: float = 0.1
    attention_dropout: float = 0.1
    activation_dropout: float = 0.0
    activation_fn: str = "gelu"
    post_ln: bool = False
    rotary: bool = False

    @nn.compact
    def __call__(
        self,
        x,
        encoder_out: Optional[jnp.ndarray] = None,
        attn_bias: Optional[jnp.ndarray] = None,
        padding_mask: Optional[jnp.ndarray] = None,
        encoder_attn_bias: Optional[jnp.ndarray] = None,
        encoder_padding_mask: Optional[jnp.ndarray] = None,
        deterministic: bool = True,
        causal: bool = False,
        decode: bool = False,
        positions: Optional[jnp.ndarray] = None,
        paged=None,
        segment_ids: Optional[jnp.ndarray] = None,
    ):
        act = get_activation_fn(self.activation_fn)

        def drop(h, rate):
            if deterministic or rate == 0.0:
                return h
            # uint8-draw dropout (ops/dropout.py): 1.6x the bernoulli path
            return ops_dropout(h, rate, self.make_rng("dropout"))

        residual = x
        if not self.post_ln:
            x = LayerNorm(self.embed_dim, name="self_attn_layer_norm")(x)
        x = SelfMultiheadAttention(
            self.embed_dim,
            self.attention_heads,
            dropout=self.attention_dropout,
            rotary=self.rotary,
            name="self_attn",
        )(x, key_padding_mask=None if decode else padding_mask,
          attn_bias=attn_bias,
          deterministic=deterministic, causal=causal, decode=decode,
          positions=positions, paged=paged, segment_ids=segment_ids)
        x = drop(x, self.dropout)
        x = residual + x
        if self.post_ln:
            x = LayerNorm(self.embed_dim, name="self_attn_layer_norm")(x)

        if encoder_out is not None:
            residual = x
            if not self.post_ln:
                x = LayerNorm(self.embed_dim, name="encoder_attn_layer_norm")(x)
            x = CrossMultiheadAttention(
                self.embed_dim,
                self.attention_heads,
                dropout=self.attention_dropout,
                name="encoder_attn",
            )(x, encoder_out, encoder_out,
              key_padding_mask=encoder_padding_mask,
              attn_bias=encoder_attn_bias,
              deterministic=deterministic)
            x = drop(x, self.dropout)
            x = residual + x
            if self.post_ln:
                x = LayerNorm(self.embed_dim, name="encoder_attn_layer_norm")(x)

        residual = x
        if not self.post_ln:
            x = LayerNorm(self.embed_dim, name="final_layer_norm")(x)
        x = nn.Dense(self.ffn_embed_dim, kernel_init=bert_init, name="fc1")(x)
        # column-parallel fc1 -> row-parallel fc2 (see encoder layer)
        x = tp_constraint(x, _BATCH_AXES, None, "tensor")
        x = act(x)
        x = drop(x, self.activation_dropout)
        x = nn.Dense(self.embed_dim, kernel_init=bert_init, name="fc2")(x)
        x = tp_constraint(x, _BATCH_AXES, None, None)
        x = drop(x, self.dropout)
        x = residual + x
        if self.post_ln:
            x = LayerNorm(self.embed_dim, name="final_layer_norm")(x)
        return x


class TransformerDecoder(nn.Module):
    decoder_layers: int = 6
    embed_dim: int = 768
    ffn_embed_dim: int = 3072
    attention_heads: int = 8
    emb_dropout: float = 0.1
    dropout: float = 0.1
    attention_dropout: float = 0.1
    activation_dropout: float = 0.0
    max_seq_len: int = 256
    activation_fn: str = "gelu"
    rel_pos: bool = True
    rel_pos_bins: int = 32
    max_rel_pos: int = 128
    post_ln: bool = False
    auto_regressive: bool = True
    rotary: bool = False
    checkpoint_activations: bool = False

    @nn.compact
    def __call__(
        self,
        emb,
        encoder_out: Optional[jnp.ndarray] = None,
        padding_mask: Optional[jnp.ndarray] = None,
        encoder_padding_mask: Optional[jnp.ndarray] = None,
        attn_mask: Optional[jnp.ndarray] = None,
        encoder_attn_mask: Optional[jnp.ndarray] = None,
        deterministic: bool = True,
        decode: bool = False,
        positions: Optional[jnp.ndarray] = None,
        paged=None,
        segment_ids: Optional[jnp.ndarray] = None,
    ):
        if segment_ids is not None and self.rel_pos:
            # the shared [T, T] relative-position bias is indexed by
            # GLOBAL row offsets — across a segment boundary it would
            # claim tokens of different samples are "close"; packing
            # needs position schemes that reset per segment (rotary or
            # absolute positions driven by the packed `positions` array)
            raise NotImplementedError(
                "sequence packing (segment_ids) with rel_pos=True: the "
                "relative-position bias is global-offset-indexed and "
                "cannot reset per segment — build the decoder with "
                "rel_pos=False (rotary or absolute positions)"
            )
        if decode and self.rel_pos:
            raise NotImplementedError(
                "incremental decoding needs a position scheme that does "
                "not materialize a [T, T] bias at a traced offset — build "
                "the decoder with rel_pos=False (use rotary or absolute "
                "positions)"
            )
        bsz, seq_len, _ = emb.shape
        x = LayerNorm(self.embed_dim, name="emb_layer_norm")(emb)
        if not deterministic and self.emb_dropout > 0.0:
            x = ops_dropout(x, self.emb_dropout, self.make_rng("dropout"))

        if padding_mask is not None:
            x = x * (1 - padding_mask[..., None].astype(x.dtype))

        if attn_mask is not None and attn_mask.ndim == 3:
            attn_mask = attn_mask.reshape(bsz, -1, seq_len, seq_len)
        if self.rel_pos:
            rel_pos_bias = RelativePositionBias(
                self.rel_pos_bins, self.attention_heads, self.max_seq_len,
                self.max_rel_pos, name="relative_attention_bias",
            )(seq_len)
            attn_mask = rel_pos_bias if attn_mask is None else attn_mask + rel_pos_bias
        if attn_mask is not None:
            # compute-dtype bias (see the encoder note): every layer
            # re-reads this tensor; the scores it adds into are x-dtype
            attn_mask = attn_mask.astype(x.dtype)
        # causal masking is NOT merged into attn_mask: it flows to the
        # attention core as a flag.  On the flash and sequence-parallel
        # paths it is applied in-kernel, so no [T, T] future-mask tensor
        # (256 MB fp32 at T=8192) ever exists; the materialized fallback
        # still folds an iota-built mask into its bias operand (same HBM
        # as before, short-T regime only).

        # padding mask intentionally NOT merged into attn_mask (see encoder)

        layer_cls = TransformerDecoderLayer
        if self.checkpoint_activations:
            # remat each layer (trade FLOPs for activation memory, same
            # scheme as the encoder): args passed positionally below;
            # deterministic (7), causal (8), and decode (9) are Python
            # bools driving trace-time control flow, so they must be static
            layer_cls = nn.remat(layer_cls, static_argnums=(7, 8, 9))
        for i in range(self.decoder_layers):
            x = layer_cls(
                embed_dim=self.embed_dim,
                ffn_embed_dim=self.ffn_embed_dim,
                attention_heads=self.attention_heads,
                dropout=self.dropout,
                attention_dropout=self.attention_dropout,
                activation_dropout=self.activation_dropout,
                activation_fn=self.activation_fn,
                post_ln=self.post_ln,
                rotary=self.rotary,
                name=f"layers_{i}",
            )(x, encoder_out, attn_mask, padding_mask, encoder_attn_mask,
              encoder_padding_mask, deterministic, self.auto_regressive,
              decode, positions, paged=paged, segment_ids=segment_ids)

        if not self.post_ln:
            x = LayerNorm(self.embed_dim, name="final_layer_norm")(x)
        return x
