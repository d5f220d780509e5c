"""The family ``bert_mlm``: an encoder trained on masked tokens through
``unicore-train``.  A configuration names its family (``"family"``); what
the harness needs to know of one family and of no other is here: the
entry point's flags for the architecture, the plain reference that
follows its updates, its sizes under the names the readers use, and the
operations one token requires.  Another family is another file."""

from benchmarks.reference import bert_mlm as _reference


def dims(cfg):
    heads = cfg["num_attention_heads"]
    return {"layers": cfg["num_hidden_layers"], "heads": heads,
            "head_dim": cfg["hidden_size"] // heads}


def train_argv(cfg):
    """The architecture's part of the ``unicore-train`` command line."""
    return [
        "--encoder-layers", str(cfg["num_hidden_layers"]),
        "--encoder-embed-dim", str(cfg["hidden_size"]),
        "--encoder-ffn-embed-dim", str(cfg["intermediate_size"]),
        "--encoder-attention-heads", str(cfg["num_attention_heads"]),
        "--activation-fn", cfg["hidden_act"],
        "--dropout", repr(cfg["hidden_dropout_prob"]),
        "--emb-dropout", repr(cfg["hidden_dropout_prob"]),
        "--attention-dropout", repr(cfg["attention_probs_dropout_prob"]),
        "--activation-dropout", "0.0",
        "--mask-prob", repr(cfg["run"]["mask_prob"]),
    ]


def reference_train_steps(params, batches, pad, cfg, precision, devices):
    return _reference.train_steps(params, batches, pad, cfg["optim"],
                                  precision=precision, devices=devices)


def train_flops_per_token(cfg, mean_len):
    """Operations the forward and backward passes require per real
    (non-pad) token.  Matmuls only, 2 FLOPs a multiply-add, backward
    twice the forward, nothing recomputed counted.  Attention is counted
    over the mean real length of a row, the vocabulary projection over
    the masked share of tokens (``mask_prob``)."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    L, V = cfg["num_hidden_layers"], cfg["vocab_size"]
    macs = L * (4 * D * D + 2 * D * F)          # projections, feed-forward
    macs += L * 2 * mean_len * D                # QK^T and PV
    macs += cfg["run"]["mask_prob"] * (D * D + D * V)  # LM head
    return 3.0 * 2.0 * macs
