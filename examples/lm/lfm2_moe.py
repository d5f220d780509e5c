"""Decoder LM of gated short-convolution and grouped-query attention
layers with sparse experts (``lfm2_moe_lm``; LiquidAI's ``lfm2_moe``).

Token embedding, :class:`~unicore_tpu.modules.PatternDecoder` with
pre-norm residuals (``conv`` and ``full_attention`` mixers in the order
``layer_types`` gives; per-head QK-norm then rotary in the attention
layers, fewer K/V heads than query heads; a dense SwiGLU in the first
``num_dense_layers`` layers, a router over ``num_experts`` SwiGLU experts
in the others), the final RMSNorm, and a head TIED to the embedding.  It
meets the serve engine's contract as ``hybrid_lm`` does and tells it, by
``has_recurrent_state``, that a sequence holds a state slot (the
convolution tails) beside its pages.
"""

from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from unicore_tpu.models import (
    BaseUnicoreModel,
    register_model,
    register_model_architecture,
)
from unicore_tpu.modules import ExpertSpec, PatternDecoder, bert_init
from unicore_tpu.modules.pattern_decoder import CONV, DENSE, EXPERTS, FULL

from .hybrid import parse_layer_types


@register_model("lfm2_moe_lm")
class Lfm2MoeLMModel(BaseUnicoreModel):
    vocab_size: int = 30522
    padding_idx: int = 0
    layer_types: Tuple[str, ...] = (CONV, FULL, CONV, CONV, CONV)
    num_dense_layers: int = 1
    decoder_embed_dim: int = 256
    decoder_ffn_embed_dim: int = 704
    decoder_attention_heads: int = 8
    decoder_kv_heads: int = 2
    conv_kernel_dim: int = 3
    num_experts: int = 8
    num_experts_per_tok: int = 2
    moe_ffn_embed_dim: int = 96
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    first_expert: int = 0       # the share of the experts held here
    experts_held: int = 0       # 0: all
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 512

    @property
    def has_recurrent_state(self):
        return CONV in self.layer_types

    @staticmethod
    def add_args(parser):
        parser.add_argument("--layer-types", metavar="PATTERN",
                            help="mixer of each layer: 'cfccc' (c = gated "
                                 "short convolution, f = full attention) or "
                                 "the kinds spelled out, comma separated")
        parser.add_argument("--num-dense-layers", type=int, metavar="N",
                            help="leading layers with a dense FFN; the "
                                 "others route over the experts")
        parser.add_argument("--decoder-embed-dim", type=int, metavar="H")
        parser.add_argument("--decoder-ffn-embed-dim", type=int, metavar="F")
        parser.add_argument("--decoder-attention-heads", type=int, metavar="A")
        parser.add_argument("--decoder-kv-heads", type=int, metavar="A")
        parser.add_argument("--num-experts", type=int)
        parser.add_argument("--num-experts-per-tok", type=int)
        parser.add_argument("--moe-ffn-embed-dim", type=int)
        parser.add_argument("--max-seq-len", type=int)

    @classmethod
    def build_model(cls, args, task):
        return cls(
            vocab_size=len(task.dictionary),
            padding_idx=task.dictionary.pad(),
            layer_types=parse_layer_types(args.layer_types),
            num_dense_layers=args.num_dense_layers,
            decoder_embed_dim=args.decoder_embed_dim,
            decoder_ffn_embed_dim=args.decoder_ffn_embed_dim,
            decoder_attention_heads=args.decoder_attention_heads,
            decoder_kv_heads=args.decoder_kv_heads,
            num_experts=args.num_experts,
            num_experts_per_tok=args.num_experts_per_tok,
            moe_ffn_embed_dim=args.moe_ffn_embed_dim,
            max_seq_len=args.max_seq_len,
        )

    @nn.compact
    def __call__(self, src_tokens, deterministic=True, decode=False,
                 positions=None, paged=None, **kwargs):
        del deterministic, decode, kwargs  # no dropout, one forward form
        embed = nn.Embed(self.vocab_size, self.decoder_embed_dim,
                         embedding_init=bert_init, name="embed_tokens")
        layers = len(self.layer_types)
        x = PatternDecoder(
            layer_types=tuple(self.layer_types),
            embed_dim=self.decoder_embed_dim,
            ffn_embed_dim=self.decoder_ffn_embed_dim,
            num_heads=self.decoder_attention_heads,
            eps=self.rms_norm_eps,
            kv_heads=self.decoder_kv_heads,
            qk_norm_per_head=True,
            rope_theta=self.rope_theta,
            short_conv_kernel_dim=self.conv_kernel_dim,
            norm_placement="input",
            ffn_types=tuple(DENSE if i < self.num_dense_layers else EXPERTS
                            for i in range(layers)),
            experts=ExpertSpec(
                self.num_experts, self.num_experts_per_tok,
                self.moe_ffn_embed_dim, self.use_expert_bias,
                self.routed_scaling_factor, self.first_expert,
                self.experts_held),
            name="decoder",
        )(embed(src_tokens), positions=positions, paged=paged)
        if paged is not None and paged.last_token is not None:
            # a serve step's tokens are a flat list (serve/engine.py):
            # the head runs on each row's last token
            x = jnp.take(x, paged.last_token, axis=1)
        # the head is the embedding, transposed; float32 multiplies in
        # float32, as the decoder's own projections do
        table = embed.embedding
        dtype = jnp.result_type(x.dtype, table.dtype)
        precision = jax.lax.Precision.HIGH if dtype == jnp.float32 else None
        return jnp.einsum("...d,vd->...v", x.astype(dtype),
                          table.astype(dtype), precision=precision)


@register_model_architecture("lfm2_moe_lm", "lfm2_moe_lm")
def lfm2_moe_lm_architecture(args):
    args.layer_types = getattr(args, "layer_types", "cfccc")
    args.num_dense_layers = getattr(args, "num_dense_layers", 1)
    args.decoder_embed_dim = getattr(args, "decoder_embed_dim", 256)
    args.decoder_ffn_embed_dim = getattr(args, "decoder_ffn_embed_dim", 704)
    args.decoder_attention_heads = getattr(args, "decoder_attention_heads", 8)
    args.decoder_kv_heads = getattr(args, "decoder_kv_heads", 2)
    args.num_experts = getattr(args, "num_experts", 8)
    args.num_experts_per_tok = getattr(args, "num_experts_per_tok", 2)
    args.moe_ffn_embed_dim = getattr(args, "moe_ffn_embed_dim", 96)
    args.max_seq_len = getattr(args, "max_seq_len", 512)
