"""Digests of the serve step programs of the tiny engines of the four
served families, for ``tests/test_serve_window.py``'s guard: run as a
script in ANY checkout (it imports nothing newer than PR 35) it prints
the fixture, ``{"eager": {...}, "kernel": {...}}``."""

import hashlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp


def normalised_digest(jaxpr):
    """A digest of a jaxpr's text that names cannot move: source lines
    and object addresses out."""
    text = re.sub(r" at 0x[0-9a-f]+", "", str(jaxpr))
    text = re.sub(r"/[\w/\-.]+\.py:\d+", "", text)
    text = re.sub(r":\d+( \(|\))", r"\1", text)
    return hashlib.sha256(text.encode()).hexdigest(), text.count("\n")


def guard_engines():
    """``{name: engine}``: the tiny engines of the four served families
    the suite already builds, over abstract weights."""
    from examples.lm.hybrid import HybridLMModel
    from examples.lm.lfm2_moe import Lfm2MoeLMModel
    from examples.lm.model import TransformerLMModel
    from examples.lm.pangu_moe import PanguMoeLMModel

    models = {
        "opt": TransformerLMModel(
            vocab_size=128, padding_idx=1, decoder_layers=2,
            decoder_embed_dim=64, decoder_ffn_embed_dim=96,
            decoder_attention_heads=4, max_seq_len=256, rel_pos=False,
            abs_pos=True, rotary=False),
        "hybrid": HybridLMModel(
            vocab_size=128, padding_idx=1,
            layer_types=("linear_attention",) * 3 + ("full_attention",),
            decoder_embed_dim=64, decoder_ffn_embed_dim=96,
            decoder_attention_heads=4, max_seq_len=256),
        "lfm2": Lfm2MoeLMModel(
            vocab_size=128, decoder_embed_dim=64, decoder_ffn_embed_dim=96,
            decoder_attention_heads=4, decoder_kv_heads=2,
            moe_ffn_embed_dim=32, max_seq_len=256),
        "pangu": PanguMoeLMModel(
            vocab_size=128, padding_idx=1, decoder_embed_dim=64,
            decoder_ffn_embed_dim=96, decoder_attention_heads=4,
            q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, num_experts=8,
            num_experts_per_tok=2, moe_ffn_embed_dim=32, max_seq_len=256),
    }
    from unicore_tpu.serve import ServeEngine

    engines = {}
    for name, model in models.items():
        abstract = jax.eval_shape(
            model.init, jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32)
        )["params"]
        engines[name] = ServeEngine(model, abstract, num_pages=40,
                                    page_size=8, max_batch=4,
                                    prefill_chunk=16)
    return engines


def step_digests(kernel):
    """``{"<engine>-w<width>": [digest, lines]}`` of both step programs of
    every guard engine: the eager path, or (``kernel``) with the ragged
    kernel traced in interpret mode."""
    from unicore_tpu.ops import backend

    out = {}
    with backend.kernel_backend("pallas" if kernel else "reference"):
        for name, engine in guard_engines().items():
            for art, traced in engine.trace_step_fns().items():
                out[f"{name}-{art}"] = list(normalised_digest(
                    traced["jaxpr"]))
    return out


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    print(json.dumps({"eager": step_digests(False),
                      "kernel": step_digests(True)}, indent=1))
