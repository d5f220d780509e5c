"""The family ``lfm2_moe_lm``: a decoder of gated short-convolution and
grouped-query attention layers with sparse experts, served through
``ServeEngine``.  What the harness needs to know of this family and of no
other.  The program's model is imported as this file is: a checkout
without it cannot run the family's cells, and says so at once."""

from benchmarks.reference import lfm2_moe_lm as _reference
from examples.lm.lfm2_moe import Lfm2MoeLMModel

FULL, CONV = "full_attention", "conv"


def dims(cfg):
    """``layers`` counts the layers that hold K/V pages (the ragged paged
    attention kernel runs in those and in no other); ``heads`` the QUERY
    heads, ``kv_heads`` what the pages hold."""
    heads = cfg["num_attention_heads"]
    return {"layers": sum(1 for kind in cfg["layer_types"] if kind == FULL),
            "conv_layers": sum(1 for kind in cfg["layer_types"]
                               if kind == CONV),
            "expert_layers": cfg["num_hidden_layers"]
            - cfg["num_dense_layers"],
            "heads": heads, "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["hidden_size"] // heads,
            "hidden": cfg["hidden_size"],
            "experts": cfg["num_experts"],
            "experts_per_token": cfg["num_experts_per_tok"],
            "expert_width": cfg["moe_intermediate_size"],
            "conv_kernel": cfg["conv_L_cache"]}


def build_model(cfg):
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"], cfg
    assert not cfg["conv_bias"] and cfg["norm_topk_prob"], cfg
    return Lfm2MoeLMModel(
        vocab_size=cfg["vocab_size"], padding_idx=cfg["pad_token_id"],
        layer_types=tuple(cfg["layer_types"]),
        num_dense_layers=cfg["num_dense_layers"],
        decoder_embed_dim=cfg["hidden_size"],
        decoder_ffn_embed_dim=cfg["intermediate_size"],
        decoder_attention_heads=cfg["num_attention_heads"],
        decoder_kv_heads=cfg["num_key_value_heads"],
        conv_kernel_dim=cfg["conv_L_cache"],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_ffn_embed_dim=cfg["moe_intermediate_size"],
        use_expert_bias=cfg["use_expert_bias"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        rms_norm_eps=cfg["norm_eps"],
        max_seq_len=cfg["max_position_embeddings"],
    )


def reference_logits(params, tokens, cfg, precision):
    """``[T, V]`` logits of one sequence; traceable."""
    return _reference.forward(
        params, tokens, heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"],
        top_k=cfg["num_experts_per_tok"],
        theta=float(cfg["rope_parameters"]["rope_theta"]),
        eps=cfg["norm_eps"], scale=float(cfg["routed_scaling_factor"]),
        precision=precision)
