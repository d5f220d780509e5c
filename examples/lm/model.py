"""Decoder-only transformer LM.

Structure mirrors the BERT example (``examples/bert/model.py``) but on
``TransformerDecoder`` (causal mask via ``auto_regressive``, no
cross-attention): token + learned position embeddings, pre-LN decoder with
bucketed rel-pos bias, tied-weight output projection.
"""

import flax.linen as nn
import jax.numpy as jnp

from unicore_tpu.models import (
    BaseUnicoreModel,
    register_model,
    register_model_architecture,
)
from unicore_tpu.modules import LayerNorm, TransformerDecoder, bert_init
from unicore_tpu.utils import arg_bool, eval_bool, get_activation_fn


def _embed_init_with_zero_pad(padding_idx):
    base = nn.initializers.normal(stddev=0.02)

    def init(key, shape, dtype=jnp.float32):
        return base(key, shape, dtype).at[padding_idx].set(0.0)

    return init


@register_model("transformer_lm")
class TransformerLMModel(BaseUnicoreModel):
    # losses may request the fused-head output form (features + tied
    # kernel + bias) via ``fused_head=True`` instead of materialized
    # [B, T, V] logits (ops/fused_cross_entropy.py)
    supports_fused_head = True

    vocab_size: int = 30522
    padding_idx: int = 0
    decoder_layers: int = 6
    decoder_embed_dim: int = 512
    decoder_ffn_embed_dim: int = 2048
    decoder_attention_heads: int = 8
    emb_dropout: float = 0.1
    dropout: float = 0.1
    attention_dropout: float = 0.1
    activation_dropout: float = 0.0
    max_seq_len: int = 512
    activation_fn: str = "gelu"
    post_ln: bool = False
    rel_pos: bool = True
    rotary: bool = False
    abs_pos: bool = True
    checkpoint_activations: bool = False

    @staticmethod
    def add_args(parser):
        parser.add_argument("--decoder-layers", type=int, metavar="L")
        parser.add_argument("--decoder-embed-dim", type=int, metavar="H")
        parser.add_argument("--decoder-ffn-embed-dim", type=int, metavar="F")
        parser.add_argument("--decoder-attention-heads", type=int, metavar="A")
        parser.add_argument("--activation-fn")
        parser.add_argument("--emb-dropout", type=float, metavar="D")
        parser.add_argument("--dropout", type=float, metavar="D")
        parser.add_argument("--attention-dropout", type=float, metavar="D")
        parser.add_argument("--activation-dropout", type=float, metavar="D")
        parser.add_argument("--max-seq-len", type=int)
        # NOT type=bool: bool("False") is True — eval_bool parses the text
        parser.add_argument("--post-ln", type=eval_bool)
        parser.add_argument("--rel-pos", type=eval_bool,
                            help="bucketed T5 rel-pos bias; pass False for "
                                 "long sequences — the [1,H,T,T] bias tensor "
                                 "grows quadratically, while the bias-free "
                                 "flash path is memory-O(T)")
        parser.add_argument("--rotary", type=eval_bool,
                            help="rotary position embeddings (RoPE): O(T*D) "
                                 "relative positions with no bias tensor — "
                                 "the long-context choice (typically with "
                                 "--rel-pos False --abs-pos False)")
        parser.add_argument("--abs-pos", type=eval_bool,
                            help="learned absolute position embeddings "
                                 "(bounded by --max-seq-len); False to rely "
                                 "on rotary/rel-pos alone")
        parser.add_argument("--checkpoint-activations", type=arg_bool,
                            nargs="?", const=True, default=False,
                            help="rematerialize decoder-layer activations "
                                 "in backward (memory for FLOPs); bare flag "
                                 "or explicit True/False")

    @classmethod
    def build_model(cls, args, task):
        return cls(
            vocab_size=len(task.dictionary),
            padding_idx=task.dictionary.pad(),
            decoder_layers=args.decoder_layers,
            decoder_embed_dim=args.decoder_embed_dim,
            decoder_ffn_embed_dim=args.decoder_ffn_embed_dim,
            decoder_attention_heads=args.decoder_attention_heads,
            emb_dropout=args.emb_dropout,
            dropout=args.dropout,
            attention_dropout=args.attention_dropout,
            activation_dropout=args.activation_dropout,
            max_seq_len=args.max_seq_len,
            activation_fn=args.activation_fn,
            post_ln=args.post_ln,
            rel_pos=cls._rel_pos_default(args),
            rotary=bool(getattr(args, "rotary", None)),
            abs_pos=cls._abs_pos_default(args),
            checkpoint_activations=bool(
                getattr(args, "checkpoint_activations", False)
            ),
        )

    @staticmethod
    def _off_when_rotary(args, flag):
        """Default a position-scheme flag to False under ``--rotary``:
        RoPE is the position scheme, and silently stacking rel-pos (the
        quadratic [1,H,T,T] bias) or learned absolute embeddings (bounded
        by --max-seq-len) on top defeats the long-context intent.
        NOTE for resumers: runs launched before r4 defaulted --abs-pos
        True under --rotary; resuming them needs an explicit
        ``--abs-pos True`` or restore fails on the missing embed table."""
        import logging

        val = getattr(args, flag.replace("-", "_"), None)
        rotary = bool(getattr(args, "rotary", None))
        if val is None:
            if rotary:
                logging.getLogger(__name__).info(
                    "--rotary: defaulting --%s False (pass --%s True "
                    "explicitly to combine both position schemes; resumes "
                    "of runs trained with both need the explicit flag)",
                    flag, flag,
                )
            return not rotary
        if val and rotary and flag == "rel-pos":
            logging.getLogger(__name__).warning(
                "--rotary with --rel-pos True: the quadratic [1,H,T,T] "
                "rel-pos bias is still built — long-context memory is "
                "bounded by it, not by RoPE"
            )
        return bool(val)

    @classmethod
    def _abs_pos_default(cls, args):
        return cls._off_when_rotary(args, "abs-pos")

    @classmethod
    def _rel_pos_default(cls, args):
        return cls._off_when_rotary(args, "rel-pos")

    @nn.compact
    def __call__(self, src_tokens, deterministic=True, decode=False,
                 positions=None, paged=None, fused_head=False,
                 segment_ids=None, **kwargs):
        # decoding assumes unpadded OR right-padded prompts (generate()
        # enforces; a 2-D positions array carries the per-sequence
        # offsets); the decoder drops the key-padding mask on the decode
        # path itself.
        # ``paged`` (serve/attention.py PagedMeta): the serve engine's
        # step.  Its tokens are a flat list, [1, N] with their positions;
        # only attention sorts them into batch rows.
        # ``segment_ids`` [B, T] routes packed rows (data/packing.py)
        # through segment-causal attention; ``positions`` then carries
        # the per-segment reset offsets (-1 at pad slots)
        padding_mask = (src_tokens == self.padding_idx).astype(jnp.float32)
        embed = nn.Embed(
            self.vocab_size,
            self.decoder_embed_dim,
            embedding_init=_embed_init_with_zero_pad(self.padding_idx),
            name="embed_tokens",
        )
        x = embed(src_tokens)
        if self.abs_pos:
            pos = self.param(
                "embed_positions", bert_init,
                (self.max_seq_len, self.decoder_embed_dim), jnp.float32,
            )
            if positions is None:
                x = x + pos[: src_tokens.shape[1], :].astype(x.dtype)
            else:
                # -1 marks inactive (padded) rows; clamp keeps the gather
                # in-bounds — those rows are masked out of attention
                x = x + jnp.take(
                    pos, jnp.maximum(positions, 0), axis=0
                ).astype(x.dtype)

        x = TransformerDecoder(
            decoder_layers=self.decoder_layers,
            embed_dim=self.decoder_embed_dim,
            ffn_embed_dim=self.decoder_ffn_embed_dim,
            attention_heads=self.decoder_attention_heads,
            emb_dropout=self.emb_dropout,
            dropout=self.dropout,
            attention_dropout=self.attention_dropout,
            activation_dropout=self.activation_dropout,
            max_seq_len=self.max_seq_len,
            activation_fn=self.activation_fn,
            rel_pos=self.rel_pos,
            rotary=self.rotary,
            post_ln=self.post_ln,
            checkpoint_activations=self.checkpoint_activations,
            auto_regressive=True,
            name="decoder",
        )(x, padding_mask=padding_mask, deterministic=deterministic,
          decode=decode, positions=positions, paged=paged,
          segment_ids=segment_ids)

        if paged is not None and paged.last_token is not None:
            # a serve step reads one token's logits per batch row: the
            # head runs on those [1, max_batch] tokens, not on every
            # token the step carries
            x = jnp.take(x, paged.last_token, axis=1)
        # tied projection + final LN'd features -> logits
        x = LayerNorm(self.decoder_embed_dim, name="out_layer_norm")(x)
        x = get_activation_fn(self.activation_fn)(x)
        bias = self.param("out_bias", nn.initializers.zeros, (self.vocab_size,))
        if fused_head:
            # pre-projection features + tied kernel: the loss runs the
            # vocab matmul chunk-by-chunk so [B, T, V] never materializes
            return {"features": x, "kernel": embed.embedding, "bias": bias,
                    "tied": True}
        return embed.attend(x) + bias


@register_model_architecture("transformer_lm", "transformer_lm")
def base_lm_architecture(args):
    args.decoder_layers = getattr(args, "decoder_layers", 6)
    args.decoder_embed_dim = getattr(args, "decoder_embed_dim", 512)
    args.decoder_ffn_embed_dim = getattr(args, "decoder_ffn_embed_dim", 2048)
    args.decoder_attention_heads = getattr(args, "decoder_attention_heads", 8)
    args.dropout = getattr(args, "dropout", 0.1)
    args.emb_dropout = getattr(args, "emb_dropout", 0.1)
    args.attention_dropout = getattr(args, "attention_dropout", 0.1)
    args.activation_dropout = getattr(args, "activation_dropout", 0.0)
    args.max_seq_len = getattr(args, "max_seq_len", 512)
    args.activation_fn = getattr(args, "activation_fn", "gelu")
    args.post_ln = getattr(args, "post_ln", False)


@register_model_architecture("transformer_lm", "transformer_lm_base")
def lm_base_architecture(args):
    args.decoder_layers = getattr(args, "decoder_layers", 12)
    args.decoder_embed_dim = getattr(args, "decoder_embed_dim", 768)
    args.decoder_ffn_embed_dim = getattr(args, "decoder_ffn_embed_dim", 3072)
    args.decoder_attention_heads = getattr(args, "decoder_attention_heads", 12)
    base_lm_architecture(args)
