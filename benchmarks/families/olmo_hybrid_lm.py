"""The family ``olmo_hybrid_lm``: a decoder of full-attention and
gated-delta-rule linear-attention layers served through ``ServeEngine``.
What the harness needs to know of this family and of no other."""

from benchmarks.reference import olmo_hybrid_lm as _reference

FULL, LINEAR = "full_attention", "linear_attention"


def dims(cfg):
    """``layers`` counts the layers that hold K/V pages: the ragged
    paged attention kernel runs in those and in no other, so the reader
    of its roofline counts right."""
    heads = cfg["num_attention_heads"]
    return {"layers": sum(1 for kind in cfg["layer_types"] if kind == FULL),
            "linear_layers": sum(1 for kind in cfg["layer_types"]
                                 if kind == LINEAR),
            "heads": heads, "head_dim": cfg["hidden_size"] // heads,
            "linear_heads": cfg["linear_num_value_heads"],
            "linear_key_dim": cfg["linear_key_head_dim"],
            "linear_value_dim": cfg["linear_value_head_dim"],
            "conv_kernel": cfg["linear_conv_kernel_dim"]}


def build_model(cfg):
    from examples.lm.hybrid import HybridLMModel

    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"], cfg
    assert cfg["linear_num_key_heads"] == cfg["linear_num_value_heads"], cfg
    return HybridLMModel(
        vocab_size=cfg["vocab_size"], padding_idx=cfg["pad_token_id"],
        layer_types=tuple(cfg["layer_types"]),
        decoder_embed_dim=cfg["hidden_size"],
        decoder_ffn_embed_dim=cfg["intermediate_size"],
        decoder_attention_heads=cfg["num_attention_heads"],
        linear_num_heads=cfg["linear_num_value_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=cfg["linear_allow_neg_eigval"],
        rms_norm_eps=cfg["rms_norm_eps"],
        max_seq_len=cfg["max_position_embeddings"],
    )


def reference_logits(params, tokens, cfg, precision):
    """``[T, V]`` logits of one sequence; traceable."""
    return _reference.forward(
        params, tokens, heads=cfg["num_attention_heads"],
        linear_heads=cfg["linear_num_value_heads"],
        linear_key_dim=cfg["linear_key_head_dim"],
        linear_value_dim=cfg["linear_value_head_dim"],
        eps=cfg["rms_norm_eps"], precision=precision)
