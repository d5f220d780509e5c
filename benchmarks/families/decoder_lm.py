"""The family ``decoder_lm``: a decoder served through ``ServeEngine``.
What the harness needs to know of this family and of no other: how the
program's model is built at the configuration's sizes, the plain
reference that gives its logits, and its sizes under the names the
readers use.  Another family is another file."""

from benchmarks.reference import decoder_lm as _reference


def dims(cfg):
    heads = cfg["num_attention_heads"]
    return {"layers": cfg["num_hidden_layers"], "heads": heads,
            "head_dim": cfg["hidden_size"] // heads}


def build_model(cfg):
    """The repo's only decoder arch at the configuration's sizes."""
    from examples.lm.model import TransformerLMModel

    m = cfg["model"]
    return TransformerLMModel(
        vocab_size=cfg["vocab_size"], padding_idx=cfg["pad_token_id"],
        decoder_layers=cfg["num_hidden_layers"],
        decoder_embed_dim=cfg["hidden_size"],
        decoder_ffn_embed_dim=cfg["ffn_dim"],
        decoder_attention_heads=cfg["num_attention_heads"],
        emb_dropout=0.0, dropout=0.0, attention_dropout=0.0,
        activation_dropout=0.0,
        max_seq_len=cfg["max_position_embeddings"],
        activation_fn=cfg["activation_function"],
        post_ln=not cfg["do_layer_norm_before"],
        rel_pos=m["rel_pos"], rotary=m["rotary"], abs_pos=m["abs_pos"],
    )


def reference_logits(params, tokens, cfg, precision):
    """``[T, V]`` logits of one sequence; traceable."""
    return _reference.forward(params, tokens,
                              heads=cfg["num_attention_heads"],
                              precision=precision)
