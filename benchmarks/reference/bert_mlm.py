"""Plain reference of BERT-base masked-LM training as the train cells
run it.

The architecture is BERT's (google-research/bert
``uncased_L-12_H-768_A-12/bert_config.json``: post-LN blocks, learned
absolute positions, exact GELU, tied output head behind a dense + GELU +
LayerNorm transform), with this repo's two additions, both listed in the
configuration file: a trainable T5-style bucketed relative-position bias
``[1, H, T, T]`` (32 buckets, maximum distance 128) added to every
layer's scores, and no token-type embeddings.

``loss_and_grads`` is one forward and backward over a batch, in blocks
of rows so that it fits beside nothing else; ``train_steps`` follows the
optimizer (gradient of the summed loss over the number of masked tokens,
clipped to a global norm, AdamW) for a few updates.  ``jax.numpy`` only,
nothing of the program imported.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import numerics as nx

REL_BUCKETS, REL_MAX_DISTANCE = 32, 128


def relative_buckets(seq_len):
    """``[T, T]`` bucket of (key position - query position): the signed
    T5 rule, half the buckets to each side, exact up to a quarter of
    them and logarithmic beyond, shifted to start at 0."""
    ctx = np.arange(seq_len)[:, None]
    mem = np.arange(seq_len)[None, :]
    rel = mem - ctx
    half = REL_BUCKETS // 2
    n = np.abs(rel)
    exact = half // 2
    large = exact + np.ceil(
        np.log(np.maximum(n, 1) / exact)
        / math.log((REL_MAX_DISTANCE - 1) / exact) * (half - 1 - exact)
    ).astype(np.int64)
    large = np.minimum(large, half - 1)
    bucket = np.where(n < exact, n, large) * np.sign(rel)
    return (bucket - bucket.min()).astype(np.int32)


def _dense(x, p, precision):
    return nx.einsum("...d,df->...f", x, p["kernel"], precision) + \
        p["bias"].astype(x.dtype)


def encode(params, tokens, pad, *, precision="fp32"):
    """Final hidden states ``[B, T, D]``."""
    dt = nx.act_dtype(precision)
    B, T = tokens.shape
    is_pad = tokens == pad
    x = params["embed_tokens"]["embedding"][tokens].astype(dt)
    x = x + params["embed_positions"][:T].astype(dt)
    enc = params["sentence_encoder"]
    x = nx.layer_norm(x, enc["emb_layer_norm"]["weight"],
                      enc["emb_layer_norm"]["bias"])
    x = x * (~is_pad)[..., None].astype(dt)
    rel = enc["relative_attention_bias"]["weight"][relative_buckets(T)]
    rel = jnp.transpose(rel, (2, 0, 1)).astype(jnp.float32)  # [H, T, T]
    key_mask = jnp.where(is_pad, -jnp.inf, 0.0)[:, None, None, :]
    n_layers = sum(1 for k in enc if k.startswith("layers_"))
    for i in range(n_layers):
        p = enc[f"layers_{i}"]
        attn = p["self_attn"]
        qkv = nx.einsum("btd,dchk->btchk", x, attn["in_proj"]["kernel"],
                        precision) + attn["in_proj"]["bias"].astype(dt)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        head_dim = q.shape[-1]
        scores = nx.einsum("bqhd,bkhd->bhqk", q * (head_dim ** -0.5), k,
                           precision)
        scores = scores.astype(jnp.float32) + rel[None] + key_mask
        probs = nx.softmax(scores).astype(dt)
        o = nx.einsum("bhqk,bkhd->bqhd", probs, v, precision)
        o = o.reshape(B, T, -1)
        x = x + _dense(o, attn["out_proj"], precision)
        x = nx.layer_norm(x, p["self_attn_layer_norm"]["weight"],
                          p["self_attn_layer_norm"]["bias"])
        h = jax.nn.gelu(_dense(x, p["fc1"], precision), approximate=False)
        x = x + _dense(h, p["fc2"], precision)
        x = nx.layer_norm(x, p["final_layer_norm"]["weight"],
                          p["final_layer_norm"]["bias"])
    return x


def masked_nll_sum(params, tokens, target, pad, *, precision="fp32"):
    """Sum over masked positions (``target != pad``) of the negative log
    likelihood of the target, in nats, and the number of them."""
    x = encode(params, tokens, pad, precision=precision)
    head = params["lm_head"]
    h = jax.nn.gelu(_dense(x, head["dense"], precision), approximate=False)
    h = nx.layer_norm(h, head["layer_norm"]["weight"],
                      head["layer_norm"]["bias"])
    logits = nx.einsum("btd,vd->btv", h, params["embed_tokens"]["embedding"],
                       precision)
    logits = logits.astype(jnp.float32) + head["bias"].astype(jnp.float32)
    masked = target != pad
    tgt = jnp.where(masked, target, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    nll = (lse - picked) * masked.astype(jnp.float32)
    return jnp.sum(nll), jnp.sum(masked.astype(jnp.float32))


def loss_and_grads(params, tokens, target, pad, *, precision="fp32",
                   block_rows=8, devices=None):
    """Summed loss, masked count and the gradient of the summed loss,
    accumulated over blocks of ``block_rows`` rows.  With several
    ``devices`` the blocks go to them in turn (each holds a copy of the
    parameters and its own partial sums), so a four-chip cell's larger
    batch takes no longer than a one-chip cell's."""
    grad_fn = jax.jit(
        jax.value_and_grad(
            lambda p, t, y: masked_nll_sum(p, t, y, pad, precision=precision),
            has_aux=True),
    )
    devices = list(devices or [jax.devices()[0]])
    copies = [jax.device_put(params, d) for d in devices]
    sums = [None] * len(devices)
    for i, r in enumerate(range(0, tokens.shape[0], block_rows)):
        k = i % len(devices)
        (loss, n), g = grad_fn(
            copies[k], jax.device_put(tokens[r:r + block_rows], devices[k]),
            jax.device_put(target[r:r + block_rows], devices[k]))
        part = (loss, n, g)
        sums[k] = part if sums[k] is None else jax.tree_util.tree_map(
            jnp.add, sums[k], part)
    home = devices[0]
    total = None
    for part in sums:
        if part is None:
            continue
        part = jax.device_put(part, home)
        total = part if total is None else jax.tree_util.tree_map(
            jnp.add, total, part)
    return total


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree_util.tree_leaves(tree)))


@jax.jit
def _adamw(params, grads, m, v, step, lr, b1, b2, eps, wd):
    """One AdamW update (Kingma & Ba with bias correction; decoupled
    weight decay)."""
    def one(p, g, m_, v_):
        m_ = b1 * m_ + (1 - b1) * g
        v_ = b2 * v_ + (1 - b2) * g * g
        m_hat = m_ / (1 - b1 ** step)
        v_hat = v_ / (1 - b2 ** step)
        p = p - lr * m_hat / (jnp.sqrt(v_hat) + eps) - lr * wd * p
        return p, m_, v_
    out = jax.tree_util.tree_map(one, params, grads, m, v)
    pick = lambda i: jax.tree_util.tree_map(
        lambda _, o: o[i], params, out)
    return pick(0), pick(1), pick(2)


def train_steps(params, batches, pad, optim, *, precision="fp32",
                block_rows=8, devices=None):
    """Follow ``len(batches)`` updates from ``params``.  Returns, for the
    comparison: each step's loss in bits per masked token, the first
    clipped gradient (as the optimizer gets it) and the parameters after
    the last update."""
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    m, v = zeros, zeros
    losses, first_grads = [], None
    for step, (tokens, target) in enumerate(batches, start=1):
        total, count, grads = loss_and_grads(
            params, jnp.asarray(tokens), jnp.asarray(target), pad,
            precision=precision, block_rows=block_rows, devices=devices)
        losses.append(float(total / count / math.log(2)))
        grads = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32) / jnp.maximum(count, 1.0), grads)
        norm = global_norm(grads)
        if optim["clip_norm"] > 0:
            coef = jnp.minimum(1.0, optim["clip_norm"] / (norm + 1e-6))
            grads = jax.tree_util.tree_map(lambda g: g * coef, grads)
        if first_grads is None:
            first_grads = grads
        params, m, v = _adamw(
            params, grads, m, v, jnp.float32(step), jnp.float32(optim["lr"]),
            optim["beta1"], optim["beta2"], optim["eps"],
            optim["weight_decay"])
    return losses, first_grads, params
