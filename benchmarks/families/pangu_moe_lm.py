"""The family ``pangu_moe_lm``: a decoder of latent-attention layers with
a shared expert beside routed experts, of which the chip holds a share,
served through ``ServeEngine``.  What the harness needs to know of this
family and of no other.  The program's model is imported as this file is:
a checkout without it cannot run the family's cells, and says so at
once."""

from benchmarks.reference import pangu_moe_lm as _reference
from examples.lm.pangu_moe import PanguMoeLMModel


def dims(cfg):
    """``layers`` counts the layers that hold latent pages (all of them);
    ``heads`` the query heads; ``latent`` / ``rope`` what a token leaves in
    a layer's cache, ``lanes`` what a page is wide (whole 128-lane slabs);
    ``experts`` the router's outputs, ``experts_held`` the routed experts
    whose weights are here."""
    layers = cfg["num_hidden_layers"]
    entry = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return {"layers": layers, "heads": cfg["num_attention_heads"],
            "lanes": -(-entry // 128) * 128,
            "head_dim": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            "latent": cfg["kv_lora_rank"], "rope": cfg["qk_rope_head_dim"],
            "nope": cfg["qk_nope_head_dim"], "v": cfg["v_head_dim"],
            "hidden": cfg["hidden_size"],
            "expert_layers": layers - cfg["first_k_dense_replace"],
            "experts": cfg["router_outputs"],
            "experts_held": cfg["n_routed_experts"],
            "experts_per_token": cfg["num_experts_per_tok"],
            "expert_width": cfg["moe_intermediate_size"]}


def build_model(cfg):
    assert cfg["sandwich_norm"] and cfg["norm_topk_prob"], cfg
    assert not cfg["attention_bias"] and not cfg["tie_word_embeddings"], cfg
    assert cfg["num_nextn_predict_layers"] == 0, cfg
    return PanguMoeLMModel(
        vocab_size=cfg["vocab_size"], padding_idx=cfg["pad_token_id"],
        decoder_layers=cfg["num_hidden_layers"],
        first_k_dense=cfg["first_k_dense_replace"],
        decoder_embed_dim=cfg["hidden_size"],
        decoder_ffn_embed_dim=cfg["intermediate_size"],
        decoder_attention_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        num_experts=cfg["router_outputs"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_ffn_embed_dim=cfg["moe_intermediate_size"],
        shared_experts=cfg["n_shared_experts"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        first_expert=cfg["first_expert"],
        experts_held=cfg["n_routed_experts"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=cfg["rms_norm_eps"],
        max_seq_len=cfg["max_position_embeddings"],
    )


def reference_logits(params, tokens, cfg, precision):
    """``[T, V]`` logits of one sequence; traceable."""
    return _reference.forward(
        params, tokens, heads=cfg["num_attention_heads"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], top_k=cfg["num_experts_per_tok"],
        theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        scale=float(cfg["routed_scaling_factor"]),
        first_expert=cfg["first_expert"], precision=precision)
