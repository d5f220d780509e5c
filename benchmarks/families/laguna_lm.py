"""The family ``laguna_lm``: a decoder of sliding-window and global
attention layers (grouped queries, a per-head output gate, YaRN on part of
each global head) with a shared expert beside routed experts, of which the
chip holds a share, served through ``ServeEngine`` over two kinds of page.
What the harness needs to know of this family and of no other.  The
program's model is imported as this file is: a checkout without it cannot
run the family's cells, and says so at once."""

from benchmarks.reference import laguna_lm as _reference
from examples.lm.laguna import LagunaLMModel

FULL, SLIDING = "full_attention", "sliding_attention"


def dims(cfg):
    """``layers`` counts the layers that hold K/V pages (all of them: the
    ragged kernel runs in each); ``global_layers`` / ``sliding_layers``
    the two kinds, ``heads`` / ``sliding_heads`` their QUERY heads,
    ``kv_heads`` what the pages hold, ``window`` the keys a sliding
    layer's query sees; ``experts`` the router's outputs,
    ``experts_held`` the routed experts whose weights are here."""
    kinds = cfg["layer_types"]
    heads = dict(zip(kinds, cfg["num_attention_heads_per_layer"]))
    assert all(heads[k] == h for k, h in zip(
        kinds, cfg["num_attention_heads_per_layer"])), cfg
    return {"layers": len(kinds),
            "global_layers": sum(1 for k in kinds if k == FULL),
            "sliding_layers": sum(1 for k in kinds if k == SLIDING),
            "heads": heads[FULL], "sliding_heads": heads.get(SLIDING, 0),
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "window": cfg["sliding_window"],
            "hidden": cfg["hidden_size"],
            "expert_layers": sum(1 for k in cfg["mlp_layer_types"]
                                 if k == "sparse"),
            "experts": cfg["router_outputs"],
            "experts_held": cfg["num_experts"],
            "experts_per_token": cfg["num_experts_per_tok"],
            "expert_width": cfg["moe_intermediate_size"]}


def build_model(cfg):
    layers = cfg["num_hidden_layers"]
    assert len(cfg["layer_types"]) == len(cfg["mlp_layer_types"]) == len(
        cfg["num_attention_heads_per_layer"]) == layers, cfg
    assert not cfg["attention_bias"] and not cfg["tie_word_embeddings"], cfg
    assert cfg["gating"] and not cfg["moe_apply_router_weight_on_input"], cfg
    assert cfg["shared_expert_intermediate_size"] \
        == cfg["moe_intermediate_size"], cfg
    rope = cfg["rope_parameters"]
    full, sliding = rope[FULL], rope[SLIDING]
    assert full["rope_type"] == "yarn" and sliding["rope_type"] == "default"
    assert sliding["partial_rotary_factor"] == 1, cfg
    return LagunaLMModel(
        vocab_size=cfg["vocab_size"], padding_idx=cfg["pad_token_id"],
        layer_types=tuple(cfg["layer_types"]),
        mlp_layer_types=tuple(cfg["mlp_layer_types"]),
        heads_per_layer=tuple(cfg["num_attention_heads_per_layer"]),
        decoder_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        decoder_embed_dim=cfg["hidden_size"],
        decoder_ffn_embed_dim=cfg["intermediate_size"],
        sliding_window=cfg["sliding_window"],
        num_experts=cfg["router_outputs"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_ffn_embed_dim=cfg["moe_intermediate_size"],
        shared_experts=1,
        routed_scaling_factor=float(cfg["moe_routed_scaling_factor"]),
        first_expert=cfg["first_expert"],
        experts_held=cfg["num_experts"],
        gating=cfg["gating"],
        global_rope_theta=float(full["rope_theta"]),
        global_rotary_lanes=int(round(
            full["partial_rotary_factor"] * cfg["head_dim"])),
        yarn_factor=float(full["factor"]),
        yarn_original_positions=full["original_max_position_embeddings"],
        yarn_beta_fast=float(full["beta_fast"]),
        yarn_beta_slow=float(full["beta_slow"]),
        yarn_attention_factor=float(full["attention_factor"]),
        sliding_rope_theta=float(sliding["rope_theta"]),
        rms_norm_eps=cfg["rms_norm_eps"],
        max_seq_len=cfg["max_position_embeddings"],
    )


def reference_logits(params, tokens, cfg, precision):
    """``[T, V]`` logits of one sequence; traceable."""
    return _reference.forward(
        params, tokens, layer_types=tuple(cfg["layer_types"]),
        heads_per_layer=tuple(cfg["num_attention_heads_per_layer"]),
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        window=cfg["sliding_window"], rope=cfg["rope_parameters"],
        top_k=cfg["num_experts_per_tok"], eps=cfg["rms_norm_eps"],
        scale=float(cfg["moe_routed_scaling_factor"]),
        first_expert=cfg["first_expert"], precision=precision)
