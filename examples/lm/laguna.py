"""Decoder LM of sliding-window and global attention layers with a shared
expert beside routed experts (``laguna_lm``; poolside's ``laguna``).

Token embedding, :class:`~unicore_tpu.modules.PatternDecoder` with
pre-norm residuals, every mixer grouped-query attention over
``decoder_kv_heads`` K/V heads of an explicit ``head_dim``, and what
differs from layer to layer given layer by layer: ``layer_types``
(``full_attention``: every key up to the query's; ``sliding_attention``:
the last ``sliding_window`` of them), ``heads_per_layer`` (the query
heads), the rotary (global layers: YaRN on the first
``global_rotary_lanes`` of each head, the other lanes unrotated; sliding
layers: plain rotary on the whole head), and a per-head sigmoid gate on
every layer's attention output.  A dense SwiGLU where ``mlp_layer_types``
says ``dense``, elsewhere a sigmoid router (the scores alone choose) over
``num_experts`` SwiGLU experts beside one shared expert; the final
RMSNorm and an UNTIED head.  It meets the serve engine's contract as
``pangu_moe_lm`` does, and tells it by ``attention_window`` that its
sliding layers keep a window: the engine then holds two kinds of page
(``serve/kv_pool.py``) and refuses prefix hits.

An instance may hold a SHARE of the model, as one chip of a deployment
does: ``experts_held`` of each layer's routed experts from
``first_expert`` on (the router still scores all ``num_experts``), and
``vocab_size`` rows of the vocabulary (ids are the slice's own).
"""

from typing import Tuple

import flax.linen as nn
import jax.numpy as jnp

from unicore_tpu.models import (
    BaseUnicoreModel,
    register_model,
    register_model_architecture,
)
from unicore_tpu.modules import (
    AttentionSpec,
    ExpertSpec,
    PatternDecoder,
    RotarySpec,
    bert_init,
)
from unicore_tpu.modules.pattern_decoder import DENSE, EXPERTS, FULL, Linear

SLIDING = "sliding_attention"


def parse_layer_types(pattern):
    """``"gsssgsss"`` (g = global, s = sliding) or the kinds spelled out,
    comma separated."""
    if isinstance(pattern, (tuple, list)):
        return tuple(pattern)
    if "," in pattern or "_" in pattern:
        return tuple(s.strip() for s in pattern.split(","))
    return tuple({"g": FULL, "s": SLIDING}[c] for c in pattern)


@register_model("laguna_lm")
class LagunaLMModel(BaseUnicoreModel):
    vocab_size: int = 30522
    padding_idx: int = 0
    layer_types: Tuple[str, ...] = (FULL, SLIDING, SLIDING, SLIDING, FULL)
    mlp_layer_types: Tuple[str, ...] = ()   # empty: dense first, then sparse
    heads_per_layer: Tuple[int, ...] = ()   # empty: the two counts below
    global_heads: int = 6
    sliding_heads: int = 8
    decoder_kv_heads: int = 2
    head_dim: int = 32
    decoder_embed_dim: int = 128
    decoder_ffn_embed_dim: int = 352
    sliding_window: int = 64
    num_experts: int = 16
    num_experts_per_tok: int = 2
    moe_ffn_embed_dim: int = 48
    shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    first_expert: int = 0       # the share of the experts held here
    experts_held: int = 0       # 0: all
    gating: bool = True
    global_rope_theta: float = 500000.0
    global_rotary_lanes: int = 16       # of head_dim; 0: the whole head
    yarn_factor: float = 64.0           # 0: plain rotary in global layers
    yarn_original_positions: int = 4096
    yarn_beta_fast: float = 64.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.4158883083359672
    sliding_rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 512

    @property
    def attention_window(self):
        """The sliding layers' window in tokens (0: none has one): what
        tells the serve engine to keep a second kind of page."""
        return self.sliding_window if SLIDING in self.layer_types else 0

    @staticmethod
    def add_args(parser):
        parser.add_argument("--layer-types", metavar="PATTERN",
                            help="attention of each layer: 'gsssgsss' (g = "
                                 "global, s = sliding window) or the kinds "
                                 "spelled out, comma separated")
        parser.add_argument("--decoder-embed-dim", type=int, metavar="H")
        parser.add_argument("--decoder-ffn-embed-dim", type=int, metavar="F")
        parser.add_argument("--global-heads", type=int, metavar="A")
        parser.add_argument("--sliding-heads", type=int, metavar="A")
        parser.add_argument("--decoder-kv-heads", type=int, metavar="A")
        parser.add_argument("--head-dim", type=int)
        parser.add_argument("--sliding-window", type=int)
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
            decoder_embed_dim=args.decoder_embed_dim,
            decoder_ffn_embed_dim=args.decoder_ffn_embed_dim,
            global_heads=args.global_heads,
            sliding_heads=args.sliding_heads,
            decoder_kv_heads=args.decoder_kv_heads,
            head_dim=args.head_dim,
            sliding_window=args.sliding_window,
            num_experts=args.num_experts,
            num_experts_per_tok=args.num_experts_per_tok,
            moe_ffn_embed_dim=args.moe_ffn_embed_dim,
            max_seq_len=args.max_seq_len,
        )

    def attention_specs(self):
        """One :class:`AttentionSpec` a layer."""
        global_rotary = RotarySpec(
            theta=self.global_rope_theta, lanes=self.global_rotary_lanes,
            yarn_factor=self.yarn_factor,
            yarn_original_positions=self.yarn_original_positions,
            yarn_beta_fast=self.yarn_beta_fast,
            yarn_beta_slow=self.yarn_beta_slow,
            attention_factor=(self.yarn_attention_factor
                              if self.yarn_factor else 1.0))
        sliding_rotary = RotarySpec(theta=self.sliding_rope_theta)
        specs = []
        for i, kind in enumerate(self.layer_types):
            sliding = kind == SLIDING
            if kind not in (FULL, SLIDING):
                raise ValueError(f"unknown attention kind {kind!r} "
                                 f"({FULL!r}, {SLIDING!r})")
            heads = (self.heads_per_layer[i] if self.heads_per_layer
                     else self.sliding_heads if sliding
                     else self.global_heads)
            specs.append(AttentionSpec(
                num_heads=heads, head_dim=self.head_dim,
                window=self.sliding_window if sliding else 0,
                rotary=sliding_rotary if sliding else global_rotary,
                gate=self.gating,
                # the serve kernel's float32 dots in three bfloat16 passes:
                # with ONE the attention's rounding of 2^-8 reaches the
                # router and a token now and then gets another expert
                # (PERF.md section 6, PR 43; PR 35 found the same)
                three_pass=True))
        return tuple(specs)

    @nn.compact
    def __call__(self, src_tokens, deterministic=True, decode=False,
                 positions=None, paged=None, **kwargs):
        del deterministic, decode, kwargs  # no dropout, one forward form
        layers = len(self.layer_types)
        mlp = self.mlp_layer_types or ("dense",) + ("sparse",) * (layers - 1)
        x = nn.Embed(self.vocab_size, self.decoder_embed_dim,
                     embedding_init=bert_init, name="embed_tokens")(src_tokens)
        x = PatternDecoder(
            layer_types=(FULL,) * layers,
            embed_dim=self.decoder_embed_dim,
            ffn_embed_dim=self.decoder_ffn_embed_dim,
            num_heads=self.global_heads,
            eps=self.rms_norm_eps,
            kv_heads=self.decoder_kv_heads,
            norm_placement="input",
            ffn_types=tuple(DENSE if kind == "dense" else EXPERTS
                            for kind in mlp),
            experts=ExpertSpec(
                self.num_experts, self.num_experts_per_tok,
                self.moe_ffn_embed_dim, use_bias=False,
                scale=self.routed_scaling_factor,
                first_expert=self.first_expert,
                experts_held=self.experts_held,
                shared_experts=self.shared_experts),
            attention=self.attention_specs(),
            name="decoder",
        )(x, positions=positions, paged=paged)
        if paged is not None and paged.last_token is not None:
            # a serve step's tokens are a flat list (serve/engine.py):
            # the head runs on each row's last token
            x = jnp.take(x, paged.last_token, axis=1)
        return Linear(self.vocab_size, name="lm_head")(x)


@register_model_architecture("laguna_lm", "laguna_lm")
def laguna_lm_architecture(args):
    args.layer_types = getattr(args, "layer_types", "gsssg")
    args.decoder_embed_dim = getattr(args, "decoder_embed_dim", 128)
    args.decoder_ffn_embed_dim = getattr(args, "decoder_ffn_embed_dim", 352)
    args.global_heads = getattr(args, "global_heads", 6)
    args.sliding_heads = getattr(args, "sliding_heads", 8)
    args.decoder_kv_heads = getattr(args, "decoder_kv_heads", 2)
    args.head_dim = getattr(args, "head_dim", 32)
    args.sliding_window = getattr(args, "sliding_window", 64)
    args.num_experts = getattr(args, "num_experts", 16)
    args.num_experts_per_tok = getattr(args, "num_experts_per_tok", 2)
    args.moe_ffn_embed_dim = getattr(args, "moe_ffn_embed_dim", 48)
    args.max_seq_len = getattr(args, "max_seq_len", 512)
