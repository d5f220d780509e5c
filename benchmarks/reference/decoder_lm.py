"""Plain reference of the decoder-only LM the serve cells run.

The architecture is OPT's (facebook/opt-1.3b ``config.json``: pre-LN
blocks, learned absolute positions, ReLU feed-forward, tied output
head), with the departures this repo's ``transformer_lm`` block makes
from it, each of which the configuration file lists under ``assumed``:

- no position offset of 2 (position ``i`` reads row ``i`` of the table);
- a LayerNorm over the summed embeddings (``emb_layer_norm``);
- after the decoder's final LayerNorm a second LayerNorm
  (``out_layer_norm``), then the activation, then the tied projection
  plus a vocabulary bias (``out_bias``).

One full causal forward over a whole sequence: ``jax.numpy``, no kernel,
no cache, no batching.  It reads a parameter tree under the names of the
repo's checkpoint layout and imports nothing of the program.
"""

import jax
import jax.numpy as jnp

from . import numerics as nx


def _dense(x, p, precision):
    return nx.einsum("td,df->tf", x, p["kernel"], precision) + \
        p["bias"].astype(x.dtype)


def forward(params, tokens, *, heads, precision="fp32"):
    """Logits ``[T, V]`` (float32) of one sequence ``tokens`` ``[T]``."""
    dt = nx.act_dtype(precision)
    emb = params["embed_tokens"]["embedding"]
    T = tokens.shape[0]
    x = emb[tokens].astype(dt) + params["embed_positions"][:T].astype(dt)
    dec = params["decoder"]
    x = nx.layer_norm(x, dec["emb_layer_norm"]["weight"],
                      dec["emb_layer_norm"]["bias"])
    causal = jnp.tril(jnp.ones((T, T), bool))
    n_layers = sum(1 for k in dec if k.startswith("layers_"))
    for i in range(n_layers):
        p = dec[f"layers_{i}"]
        h = nx.layer_norm(x, p["self_attn_layer_norm"]["weight"],
                          p["self_attn_layer_norm"]["bias"])
        attn = p["self_attn"]
        # fused projection: kernel [D, 3, H, Dh], bias [3, H, Dh]
        qkv = nx.einsum("td,dchk->tchk", h, attn["in_proj"]["kernel"],
                        precision) + attn["in_proj"]["bias"].astype(dt)
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
        head_dim = q.shape[-1]
        scores = nx.einsum("qhd,khd->hqk", q * (head_dim ** -0.5), k,
                           precision)
        scores = jnp.where(causal[None], scores.astype(jnp.float32), -1e30)
        probs = nx.softmax(scores).astype(dt)
        o = nx.einsum("hqk,khd->qhd", probs, v, precision)
        o = o.reshape(T, heads * head_dim)
        x = x + _dense(o, attn["out_proj"], precision)
        h = nx.layer_norm(x, p["final_layer_norm"]["weight"],
                          p["final_layer_norm"]["bias"])
        h = jax.nn.relu(_dense(h, p["fc1"], precision))
        x = x + _dense(h, p["fc2"], precision)
    x = nx.layer_norm(x, dec["final_layer_norm"]["weight"],
                      dec["final_layer_norm"]["bias"])
    x = nx.layer_norm(x, params["out_layer_norm"]["weight"],
                      params["out_layer_norm"]["bias"])
    x = jax.nn.relu(x)
    logits = nx.einsum("td,vd->tv", x, emb, precision)
    return logits.astype(jnp.float32) + params["out_bias"].astype(jnp.float32)
