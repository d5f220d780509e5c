"""Plain reference of the hybrid decoder LM the ``olmo_hybrid_7b`` cells
serve: allenai/Olmo-Hybrid-7B ``config.json`` (``model_type
olmo_hybrid``), layers of two kinds in the order ``layer_types`` gives.

Block, both kinds (Olmo 2/3: the norm sits on the sub-layer's output):

    h = x + RMSNorm(Mixer(x))
    y = h + RMSNorm(W_down(silu(W_gate h) * W_up h))        eps 1e-6, no bias

Embedding, the blocks, a final RMSNorm, an untied head; no position table.

``full_attention``: ``q = RMSNorm(W_q x)``, ``k = RMSNorm(W_k x)`` over
the whole projection, then split into heads; ``v = W_v x``; causal
softmax at ``head_dim ** -0.5``; ``W_o``.  No rotary.  Computed in blocks
of queries, so that 4,096 positions fit beside the weights.

``linear_attention`` (gated delta rule, arXiv:2412.06464), per token t:
``u = [W_q; W_k; W_v] x_t``; on every channel a causal convolution over
time of width K (tap K-1 on the current token), no bias, then SiLU; split
into ``q_t, k_t`` (H x dk) and ``v_t`` (H x dv); ``q_t <- q_t / |q_t| *
dk ** -0.5``, ``k_t <- k_t / |k_t|``; ``beta_t = 2 sigmoid(W_b x_t)``;
``alpha_t = exp(-exp(A_log) softplus(W_a x_t + dt_bias))``.  Per head a
state ``S`` (dk x dv), zero before the first token:

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

computed here TOKEN BY TOKEN (a scan over time: no chunks, no cache, no
kernel).  Output ``W_o [RMSNorm_dv(o_t) * silu(W_g x_t)]``, the norm per
head with one weight of ``dv``.

``jax.numpy`` only; reads a parameter tree under the names of the repo's
checkpoint layout and imports nothing of the program.  ``precision``:
``fp32`` (float32, ``highest`` matmuls) or ``bf16`` (weights,
activations, cache AND the recurrent state rounded to bfloat16; float32
statistics in softmax and RMSNorm, float32 accumulation in a matmul).
"""

import jax
import jax.numpy as jnp

from . import numerics as nx

QUERY_BLOCK = 512
_HI = jax.lax.Precision.HIGHEST


def rms_norm(x, weight, eps):
    """Over the last axis, statistics in float32 whatever the type."""
    xf = x.astype(jnp.float32)
    inv = 1.0 / jnp.sqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                         + eps)
    return (xf * inv).astype(x.dtype) * weight.astype(x.dtype)


def _proj(x, p, precision):
    return nx.einsum("td,df->tf", x, p["kernel"], precision)


def full_attention(x, p, *, heads, eps, precision):
    T, D = x.shape
    dt = x.dtype
    hd = D // heads
    q = rms_norm(_proj(x, p["q_proj"], precision), p["q_norm"]["weight"], eps)
    k = rms_norm(_proj(x, p["k_proj"], precision), p["k_norm"]["weight"], eps)
    v = _proj(x, p["v_proj"], precision)
    q, k, v = (t.reshape(T, heads, hd) for t in (q, k, v))
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


def linear_attention(x, p, *, heads, key_dim, value_dim, eps, precision):
    T, _ = x.shape
    dt = x.dtype
    nk = heads * key_dim
    u = jnp.concatenate([_proj(x, p[n], precision)
                         for n in ("q_proj", "k_proj", "v_proj")], axis=-1)
    w = p["conv_kernel"].astype(dt)                       # [K, channels]
    K = w.shape[0]
    ext = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), dt), u], axis=0)
    u = jax.nn.silu(sum(ext[i:i + T] * w[i] for i in range(K)))
    q = u[:, :nk].reshape(T, heads, key_dim)
    k = u[:, nk:2 * nk].reshape(T, heads, key_dim)
    v = u[:, 2 * nk:].reshape(T, heads, value_dim)
    norm = lambda t: jnp.sqrt(jnp.sum(jnp.square(t.astype(jnp.float32)),
                                      axis=-1, keepdims=True))
    q = (q.astype(jnp.float32) / norm(q) * key_dim ** -0.5).astype(dt)
    k = (k.astype(jnp.float32) / norm(k)).astype(dt)
    beta = 2.0 * jax.nn.sigmoid(_proj(x, p["b_proj"], precision))
    dt_in = _proj(x, p["a_proj"], precision) + p["dt_bias"].astype(dt)
    alpha = jnp.exp(-jnp.exp(p["A_log"].astype(jnp.float32))
                    * jax.nn.softplus(dt_in.astype(jnp.float32))).astype(dt)

    def one_token(S, xs):
        q_t, k_t, v_t, a_t, b_t = xs                 # [H, dk], ..., [H]
        S = S * a_t[:, None, None]
        read = jnp.einsum("hkv,hk->hv", S, k_t, precision=_HI,
                          preferred_element_type=jnp.float32).astype(dt)
        upd = b_t[:, None] * (v_t - read)
        S = (S + k_t[:, :, None] * upd[:, None, :]).astype(dt)
        o_t = jnp.einsum("hkv,hk->hv", S, q_t, precision=_HI,
                         preferred_element_type=jnp.float32).astype(dt)
        return S, o_t

    S0 = jnp.zeros((heads, key_dim, value_dim), dt)
    _, o = jax.lax.scan(one_token, S0, (q, k, v, alpha, beta.astype(dt)))
    o = rms_norm(o, p["o_norm"]["weight"], eps)
    gate = jax.nn.silu(_proj(x, p["g_proj"], precision))
    o = o * gate.reshape(T, heads, value_dim)
    return _proj(o.reshape(T, heads * value_dim), p["o_proj"], precision)


def forward(params, tokens, *, heads, linear_heads, linear_key_dim,
            linear_value_dim, eps=1e-6, precision="fp32"):
    """Logits ``[T, V]`` (float32) of one sequence ``tokens`` ``[T]``."""
    dt = nx.act_dtype(precision)
    x = params["embed_tokens"]["embedding"][tokens].astype(dt)
    dec = params["decoder"]
    n_layers = sum(1 for name in dec if name.startswith("layers_"))
    for i in range(n_layers):
        p = dec[f"layers_{i}"]
        if "linear_attn" in p:
            mixed = linear_attention(
                x, p["linear_attn"], heads=linear_heads,
                key_dim=linear_key_dim, value_dim=linear_value_dim, eps=eps,
                precision=precision)
        else:
            mixed = full_attention(x, p["self_attn"], heads=heads, eps=eps,
                                   precision=precision)
        h = x + rms_norm(mixed, p["post_attention_layernorm"]["weight"], eps)
        mlp = p["mlp"]
        ffn = _proj(jax.nn.silu(_proj(h, mlp["gate_proj"], precision))
                    * _proj(h, mlp["up_proj"], precision),
                    mlp["down_proj"], precision)
        x = h + rms_norm(ffn, p["post_feedforward_layernorm"]["weight"], eps)
    x = rms_norm(x, dec["final_layer_norm"]["weight"], eps)
    return _proj(x, params["lm_head"], precision).astype(jnp.float32)
