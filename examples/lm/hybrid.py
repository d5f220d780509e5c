"""Decoder LM over a per-layer pattern of mixers (``hybrid_lm``).

Token embedding, :class:`~unicore_tpu.modules.PatternDecoder` (full
attention and gated-delta-rule linear attention layers in the order
``layer_types`` gives, output-normed residuals, SwiGLU, RMSNorm), an
UNTIED head, no position table: the recurrent layers carry order.  It
meets the serve engine's contract (``apply(..., decode=True, positions=,
paged=)``, ``max_seq_len``, ``padding_idx``) and tells the engine, by
``has_recurrent_state``, that its sequences hold a state slot beside
their pages.
"""

from typing import Tuple

import flax.linen as nn
import jax.numpy as jnp

from unicore_tpu.models import (
    BaseUnicoreModel,
    register_model,
    register_model_architecture,
)
from unicore_tpu.modules import PatternDecoder, bert_init
from unicore_tpu.modules.pattern_decoder import CONV, FULL, LINEAR, Linear


def parse_layer_types(text):
    """``"lllf"`` or ``"linear_attention,full_attention"`` -> a tuple of
    mixer kinds (``c``: the gated short convolution of ``lfm2_moe_lm``);
    a short pattern repeats to ``--decoder-layers``."""
    short = {"l": LINEAR, "f": FULL, "c": CONV}
    parts = ([p.strip() for p in text.split(",")] if "," in text or "_" in text
             else list(text.strip()))
    return tuple(short.get(p, p) for p in parts if p)


@register_model("hybrid_lm")
class HybridLMModel(BaseUnicoreModel):
    vocab_size: int = 30522
    padding_idx: int = 0
    layer_types: Tuple[str, ...] = (LINEAR, LINEAR, LINEAR, FULL)
    decoder_embed_dim: int = 512
    decoder_ffn_embed_dim: int = 1408
    decoder_attention_heads: int = 8
    linear_num_heads: int = 8
    linear_key_head_dim: int = 48
    linear_value_head_dim: int = 96
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 512

    @property
    def has_recurrent_state(self):
        return LINEAR in self.layer_types

    @staticmethod
    def add_args(parser):
        parser.add_argument("--decoder-layers", type=int, metavar="L")
        parser.add_argument("--layer-types", metavar="PATTERN",
                            help="mixer of each layer: 'lllf' (l = linear "
                                 "attention, f = full attention) or the "
                                 "kinds spelled out, comma separated; a "
                                 "pattern shorter than --decoder-layers "
                                 "repeats")
        parser.add_argument("--decoder-embed-dim", type=int, metavar="H")
        parser.add_argument("--decoder-ffn-embed-dim", type=int, metavar="F")
        parser.add_argument("--decoder-attention-heads", type=int, metavar="A")
        parser.add_argument("--linear-num-heads", type=int)
        parser.add_argument("--linear-key-head-dim", type=int)
        parser.add_argument("--linear-value-head-dim", type=int)
        parser.add_argument("--max-seq-len", type=int)

    @classmethod
    def build_model(cls, args, task):
        pattern = parse_layer_types(args.layer_types)
        layers = args.decoder_layers
        if layers % len(pattern):
            raise ValueError(
                f"--decoder-layers {layers} is not a whole number of "
                f"periods of --layer-types ({len(pattern)} layers)")
        return cls(
            vocab_size=len(task.dictionary),
            padding_idx=task.dictionary.pad(),
            layer_types=pattern * (layers // len(pattern)),
            decoder_embed_dim=args.decoder_embed_dim,
            decoder_ffn_embed_dim=args.decoder_ffn_embed_dim,
            decoder_attention_heads=args.decoder_attention_heads,
            linear_num_heads=args.linear_num_heads,
            linear_key_head_dim=args.linear_key_head_dim,
            linear_value_head_dim=args.linear_value_head_dim,
            max_seq_len=args.max_seq_len,
        )

    @nn.compact
    def __call__(self, src_tokens, deterministic=True, decode=False,
                 positions=None, paged=None, **kwargs):
        del deterministic, decode, kwargs  # no dropout, one forward form
        x = nn.Embed(self.vocab_size, self.decoder_embed_dim,
                     embedding_init=bert_init, name="embed_tokens")(src_tokens)
        x = PatternDecoder(
            layer_types=tuple(self.layer_types),
            embed_dim=self.decoder_embed_dim,
            ffn_embed_dim=self.decoder_ffn_embed_dim,
            num_heads=self.decoder_attention_heads,
            linear_num_heads=self.linear_num_heads,
            linear_key_head_dim=self.linear_key_head_dim,
            linear_value_head_dim=self.linear_value_head_dim,
            linear_conv_kernel_dim=self.linear_conv_kernel_dim,
            linear_allow_neg_eigval=self.linear_allow_neg_eigval,
            eps=self.rms_norm_eps,
            name="decoder",
        )(x, positions=positions, paged=paged)
        if paged is not None and paged.last_token is not None:
            # a serve step's tokens are a flat list (serve/engine.py):
            # the head runs on each row's last token
            x = jnp.take(x, paged.last_token, axis=1)
        return Linear(self.vocab_size, name="lm_head")(x)


@register_model_architecture("hybrid_lm", "hybrid_lm")
def hybrid_lm_architecture(args):
    args.decoder_layers = getattr(args, "decoder_layers", 8)
    args.layer_types = getattr(args, "layer_types", "lllf")
    args.decoder_embed_dim = getattr(args, "decoder_embed_dim", 512)
    args.decoder_ffn_embed_dim = getattr(args, "decoder_ffn_embed_dim", 1408)
    args.decoder_attention_heads = getattr(args, "decoder_attention_heads", 8)
    args.linear_num_heads = getattr(
        args, "linear_num_heads", args.decoder_attention_heads)
    args.linear_key_head_dim = getattr(args, "linear_key_head_dim", 48)
    args.linear_value_head_dim = getattr(args, "linear_value_head_dim", 96)
    args.max_seq_len = getattr(args, "max_seq_len", 512)
