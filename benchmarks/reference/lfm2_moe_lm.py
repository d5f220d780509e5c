"""Plain reference of the decoder LM the ``lfm2_24b_a2b`` cell serves:
LiquidAI/LFM2-24B-A2B ``config.json`` (``model_type lfm2_moe``), layers of
two kinds in the order ``layer_types`` gives, a dense feed-forward in the
leading layers and sparse experts in the others.

With ``RMSNorm`` (eps ``norm_eps``, float32 statistics) and no bias
anywhere:

Block (pre-norm):  ``h = x + Mixer(RMSNorm_op(x))``;
``y = h + FFN(RMSNorm_ffn(h))``.  After the last layer ``RMSNorm_emb``,
then the head = the embedding matrix transposed (tied).

``conv`` mixer (gated short convolution): ``[B, C, u] = split3(W_in x)``
(hidden size each); ``z = B * u``; ``c_t = sum_{j=0..K-1} w_j *
z_{t-K+1+j}`` (depthwise, causal, ``conv_L_cache`` = K taps a channel, tap
K-1 on the current token, zeros before the sequence starts, no bias, NO
activation); ``out = W_out (C * c)``.

``full_attention`` mixer: ``q = W_q x`` as ``num_attention_heads`` heads,
``k = W_k x``, ``v = W_v x`` as ``num_key_value_heads`` heads; RMSNorm
over each head (one weight of ``head_dim`` for q, one for k); rotary
(rotate-half, ``rope_theta``, the whole head) on q and k; causal
``softmax(q k^T / sqrt(head_dim))`` with query head ``h`` reading K/V head
``h // (heads / kv heads)``; ``W_o``.  Computed in blocks of queries.

Dense FFN (the leading layers): ``W_2(silu(W_1 x) * W_3 x)``.

Expert FFN: ``s = sigmoid(W_g x)`` (``num_experts`` scores, float32);
``sel = top_k(s + b)`` with ``b`` the expert bias (selection only);
``w = s[sel] / (sum s[sel] + 1e-6)``, times ``routed_scaling_factor``;
``y = sum_{e in sel} w_e W_2^e(silu(W_1^e x) * W_3^e x)``.  No shared
expert.  Computed here as a loop over the experts, each applied to EVERY
token and weighted by a dense ``[T, E]`` matrix that is 0 where a token
did not choose it: no sort, no gather by expert, no grouping.  A tree that
holds only a share of the experts (``first_expert``, as many as its
stacked weights have) gives that share's part of ``y``.

``jax.numpy`` only; reads a parameter tree under the names of the repo's
checkpoint layout and imports nothing of the program.  ``precision``:
``fp32`` (float32, ``highest`` matmuls) or ``bf16`` (weights, activations
and the router's input rounded to bfloat16; float32 statistics in softmax
and RMSNorm, float32 accumulation in a matmul, float32 rotation).
"""

import jax
import jax.numpy as jnp

from . import numerics as nx

QUERY_BLOCK = 512


def rms_norm(x, weight, eps):
    """Over the last axis, statistics in float32 whatever the type."""
    xf = x.astype(jnp.float32)
    inv = 1.0 / jnp.sqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                         + eps)
    return (xf * inv).astype(x.dtype) * weight.astype(x.dtype)


def _proj(x, p, precision):
    return nx.einsum("td,df->tf", x, p["kernel"], precision)


def rotary(x, theta):
    """``x`` [T, H, D] rotated by its position, rotate-half, in float32."""
    T, _, D = x.shape
    half = D // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def full_attention(x, p, *, heads, kv_heads, theta, eps, precision):
    T, D = x.shape
    dt = x.dtype
    hd = D // heads
    q = _proj(x, p["q_proj"], precision).reshape(T, heads, hd)
    k = _proj(x, p["k_proj"], precision).reshape(T, kv_heads, hd)
    v = _proj(x, p["v_proj"], precision).reshape(T, kv_heads, hd)
    q = rotary(rms_norm(q, p["q_norm"]["weight"], eps), theta)
    k = rotary(rms_norm(k, p["k_norm"]["weight"], eps), theta)
    group = heads // kv_heads
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    block = min(QUERY_BLOCK, T)
    assert T % block == 0, (T, block)
    cols = jnp.arange(T)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        s = nx.einsum("qhd,khd->hqk", qb * (hd ** -0.5), k, precision)
        rows = start + jnp.arange(block)
        s = jnp.where(cols[None, None, :] <= rows[None, :, None],
                      s.astype(jnp.float32), -1e30)
        return nx.einsum("hqk,khd->qhd", nx.softmax(s).astype(dt), v,
                         precision)

    o = jax.lax.map(one_block, jnp.arange(0, T, block))
    return _proj(o.reshape(T, D), p["o_proj"], precision)


def short_conv(x, p, *, precision):
    T, D = x.shape
    gate_in, gate_out, u = jnp.split(_proj(x, p["in_proj"], precision), 3,
                                     axis=-1)
    z = gate_in * u
    w = p["conv_kernel"].astype(z.dtype)                  # [K, D]
    K = w.shape[0]
    ext = jnp.concatenate([jnp.zeros((K - 1, D), z.dtype), z], axis=0)
    c = sum(ext[i:i + T] * w[i] for i in range(K))
    return _proj(gate_out * c, p["out_proj"], precision)


def swiglu(x, w1, w3, w2, precision):
    hidden = jax.nn.silu(nx.einsum("td,df->tf", x, w1, precision)) \
        * nx.einsum("td,df->tf", x, w3, precision)
    return nx.einsum("tf,fd->td", hidden, w2, precision)


def expert_ffn(x, p, *, top_k, scale, first_expert, precision):
    T = x.shape[0]
    scores = jax.nn.sigmoid(nx.einsum("td,de->te", x, p["router"],
                                      precision).astype(jnp.float32))
    E = scores.shape[1]
    chosen_by = scores + p["expert_bias"].astype(jnp.float32) \
        if "expert_bias" in p else scores
    _, sel = jax.lax.top_k(chosen_by, top_k)
    w = jnp.take_along_axis(scores, sel, axis=-1)
    w = scale * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    dense = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], sel].set(w)
    held = p["w1"].shape[0]
    mine = jax.lax.dynamic_slice_in_dim(dense, first_expert, held, axis=1)

    def one_expert(xs):
        w1, w3, w2, weight = xs
        return swiglu(x, w1, w3, w2, precision) * weight[:, None].astype(
            x.dtype)

    parts = jax.lax.map(one_expert, (p["w1"], p["w3"], p["w2"], mine.T))
    return jnp.sum(parts.astype(jnp.float32), axis=0).astype(x.dtype)


def forward(params, tokens, *, heads, kv_heads, top_k, theta, eps=1e-5,
            scale=1.0, first_expert=0, precision="fp32"):
    """Logits ``[T, V]`` (float32) of one sequence ``tokens`` ``[T]``."""
    dt = nx.act_dtype(precision)
    table = params["embed_tokens"]["embedding"]
    x = table[tokens].astype(dt)
    dec = params["decoder"]
    n_layers = sum(1 for name in dec if name.startswith("layers_"))
    for i in range(n_layers):
        p = dec[f"layers_{i}"]
        normed = rms_norm(x, p["operator_norm"]["weight"], eps)
        if "conv" in p:
            mixed = short_conv(normed, p["conv"], precision=precision)
        else:
            mixed = full_attention(
                normed, p["self_attn"], heads=heads, kv_heads=kv_heads,
                theta=theta, eps=eps, precision=precision)
        h = x + mixed
        normed = rms_norm(h, p["ffn_norm"]["weight"], eps)
        ff = p["feed_forward"]
        if "router" in ff:
            ffn = expert_ffn(normed, ff, top_k=top_k, scale=scale,
                             first_expert=first_expert, precision=precision)
        else:
            ffn = swiglu(normed, ff["gate_proj"]["kernel"],
                         ff["up_proj"]["kernel"], ff["down_proj"]["kernel"],
                         precision)
        x = h + ffn
    x = rms_norm(x, dec["final_layer_norm"]["weight"], eps)
    return nx.einsum("td,vd->tv", x, table, precision).astype(jnp.float32)
