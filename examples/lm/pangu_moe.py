"""Decoder LM of latent-attention layers with a shared expert beside
routed experts (``pangu_moe_lm``; openPangu's ``pangu_ultra_moe``).

Token embedding, :class:`~unicore_tpu.modules.PatternDecoder` with
sandwich norms (an RMSNorm on the input AND on the output of each
sub-layer), every mixer multi-head latent attention, a dense SwiGLU in the
first ``first_k_dense`` layers and in the others a sigmoid router (the
scores alone choose, no bias) over ``num_experts`` SwiGLU experts beside
``shared_experts`` that every token gets; the final RMSNorm and an UNTIED
head.  It meets the serve engine's contract as ``hybrid_lm`` does; it
holds no recurrent state, so its sequences take prefix hits, on latent
pages.

An instance may hold a SHARE of the model, as one chip of a deployment
does: ``experts_held`` of each layer's routed experts from
``first_expert`` on (the router still scores all ``num_experts``; what the
absent experts would add is added by nobody here), and ``vocab_size`` rows
of the vocabulary (embedding and head; ids are the slice's own).
"""

import flax.linen as nn
import jax.numpy as jnp

from unicore_tpu.models import (
    BaseUnicoreModel,
    register_model,
    register_model_architecture,
)
from unicore_tpu.modules import (
    ExpertSpec,
    LatentSpec,
    PatternDecoder,
    bert_init,
)
from unicore_tpu.modules.pattern_decoder import (
    DENSE,
    EXPERTS,
    LATENT,
    Linear,
)


@register_model("pangu_moe_lm")
class PanguMoeLMModel(BaseUnicoreModel):
    vocab_size: int = 30522
    padding_idx: int = 0
    decoder_layers: int = 3
    first_k_dense: int = 1
    decoder_embed_dim: int = 256
    decoder_ffn_embed_dim: int = 704
    decoder_attention_heads: int = 8
    q_lora_rank: int = 96
    kv_lora_rank: int = 64
    qk_nope_head_dim: int = 32
    qk_rope_head_dim: int = 16
    v_head_dim: int = 32
    num_experts: int = 16
    num_experts_per_tok: int = 4
    moe_ffn_embed_dim: int = 96
    shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    first_expert: int = 0       # the share of the experts held here
    experts_held: int = 0       # 0: all
    rope_theta: float = 25600000.0
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 512

    @staticmethod
    def add_args(parser):
        parser.add_argument("--decoder-layers", type=int, metavar="L")
        parser.add_argument("--first-k-dense", type=int, metavar="N",
                            help="leading layers with a dense FFN; the "
                                 "others route over the experts")
        parser.add_argument("--decoder-embed-dim", type=int, metavar="H")
        parser.add_argument("--decoder-ffn-embed-dim", type=int, metavar="F")
        parser.add_argument("--decoder-attention-heads", type=int, metavar="A")
        parser.add_argument("--q-lora-rank", type=int)
        parser.add_argument("--kv-lora-rank", type=int)
        parser.add_argument("--num-experts", type=int)
        parser.add_argument("--num-experts-per-tok", type=int)
        parser.add_argument("--moe-ffn-embed-dim", type=int)
        parser.add_argument("--max-seq-len", type=int)

    @classmethod
    def build_model(cls, args, task):
        return cls(
            vocab_size=len(task.dictionary),
            padding_idx=task.dictionary.pad(),
            decoder_layers=args.decoder_layers,
            first_k_dense=args.first_k_dense,
            decoder_embed_dim=args.decoder_embed_dim,
            decoder_ffn_embed_dim=args.decoder_ffn_embed_dim,
            decoder_attention_heads=args.decoder_attention_heads,
            q_lora_rank=args.q_lora_rank,
            kv_lora_rank=args.kv_lora_rank,
            num_experts=args.num_experts,
            num_experts_per_tok=args.num_experts_per_tok,
            moe_ffn_embed_dim=args.moe_ffn_embed_dim,
            max_seq_len=args.max_seq_len,
        )

    @nn.compact
    def __call__(self, src_tokens, deterministic=True, decode=False,
                 positions=None, paged=None, **kwargs):
        del deterministic, decode, kwargs  # no dropout, one forward form
        x = nn.Embed(self.vocab_size, self.decoder_embed_dim,
                     embedding_init=bert_init, name="embed_tokens")(src_tokens)
        x = PatternDecoder(
            layer_types=(LATENT,) * self.decoder_layers,
            embed_dim=self.decoder_embed_dim,
            ffn_embed_dim=self.decoder_ffn_embed_dim,
            num_heads=self.decoder_attention_heads,
            eps=self.rms_norm_eps,
            rope_theta=self.rope_theta,
            norm_placement="both",
            ffn_types=tuple(DENSE if i < self.first_k_dense else EXPERTS
                            for i in range(self.decoder_layers)),
            experts=ExpertSpec(
                self.num_experts, self.num_experts_per_tok,
                self.moe_ffn_embed_dim, use_bias=False,
                scale=self.routed_scaling_factor,
                first_expert=self.first_expert,
                experts_held=self.experts_held, eps=1e-20,
                shared_experts=self.shared_experts),
            latent=LatentSpec(self.q_lora_rank, self.kv_lora_rank,
                              self.qk_nope_head_dim, self.qk_rope_head_dim,
                              self.v_head_dim),
            name="decoder",
        )(x, positions=positions, paged=paged)
        if paged is not None and paged.last_token is not None:
            # a serve step's tokens are a flat list (serve/engine.py):
            # the head runs on each row's last token
            x = jnp.take(x, paged.last_token, axis=1)
        return Linear(self.vocab_size, name="lm_head")(x)


@register_model_architecture("pangu_moe_lm", "pangu_moe_lm")
def pangu_moe_lm_architecture(args):
    args.decoder_layers = getattr(args, "decoder_layers", 3)
    args.first_k_dense = getattr(args, "first_k_dense", 1)
    args.decoder_embed_dim = getattr(args, "decoder_embed_dim", 256)
    args.decoder_ffn_embed_dim = getattr(args, "decoder_ffn_embed_dim", 704)
    args.decoder_attention_heads = getattr(args, "decoder_attention_heads", 8)
    args.q_lora_rank = getattr(args, "q_lora_rank", 96)
    args.kv_lora_rank = getattr(args, "kv_lora_rank", 64)
    args.num_experts = getattr(args, "num_experts", 16)
    args.num_experts_per_tok = getattr(args, "num_experts_per_tok", 4)
    args.moe_ffn_embed_dim = getattr(args, "moe_ffn_embed_dim", 96)
    args.max_seq_len = getattr(args, "max_seq_len", 512)
