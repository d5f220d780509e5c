"""Plain reference of the decoder LM the ``openpangu_ultra_moe_718b`` cell
serves: FreedomIntelligence/openPangu-Ultra-MoE-718B ``config.json``
(``model_type pangu_ultra_moe``): multi-head latent attention in every
layer, a dense feed-forward in the leading layers and, in the others, a
shared expert beside routed experts; sandwich norms.

With ``RMSNorm`` (eps ``rms_norm_eps``, float32 statistics) and no bias
anywhere (``attention_bias`` false):

Block (sandwich norm): ``h = x + N_post_attn(Attn(N_in(x)))``;
``y = h + N_post_mlp(FFN(N_pre_mlp(h)))``: four RMSNorms a layer.  After
the last layer a final RMSNorm, then an UNTIED head.  (The config gives
``sandwich_norm: true`` and no formula; this is the released modelling
file's.)

Latent attention: ``c_q = N_q(W_qa x)`` (``q_lora_rank``); ``q = W_qb
c_q`` as ``num_attention_heads`` heads of ``[q_nope (qk_nope_head_dim),
q_rope (qk_rope_head_dim)]``.  ``[c, k_r] = split(W_kva x)``
(``kv_lora_rank`` + ``qk_rope_head_dim``); ``c_kv = N_kv(c)``; ``k_r =
RoPE(k_r)``, ONE rope key for all heads.  ``[k_nope_h, v_h] = W_kvb,h
c_kv`` (``qk_nope_head_dim`` + ``v_head_dim`` a head).  ``score_h(t, s) =
(q_nope_h(t) . k_nope_h(s) + RoPE(q_rope_h)(t) . k_r(s)) / sqrt(nope +
rope)``; causal softmax in float32; ``o_h = sum_s p_h v_h(s)``; ``out =
W_o concat_h(o_h)``.  RoPE: rotate-half over the rope lanes, ``rope_theta``,
no scaling.  (The released file de-interleaves the rope lanes first, a
fixed permutation of weight columns applied to q and k alike, which leaves
``q . k`` unchanged under seeded weights: not done here.)  Computed in
the PER-HEAD form only, as written: no absorption of ``W_kvb`` into query
or output, no cache, no page; in blocks of heads and of queries, so that
8,448 positions x 128 heads fit beside the weights.

Dense FFN (the leading layers): ``W_2(silu(W_1 x) * W_3 x)``, in blocks
of rows.

Expert FFN: ``s = sigmoid(W_g x)`` (``n_routed_experts`` scores, float32);
``sel = top_k(s)`` over all of them, no groups, no selection bias; ``w =
s[sel] / (sum s[sel] + 1e-20)``, times ``routed_scaling_factor``; ``y =
Shared(x) + sum_{e in sel} w_e E_e(x)``, each a SwiGLU of
``moe_intermediate_size`` (the shared one ``n_shared_experts`` times
that).  (The config has no ``scoring_func`` / ``topk_method`` / ``n_group``:
sigmoid and a plain top-k are the released modelling file's.)  Computed
as a loop over the HELD experts, each applied to EVERY token and weighted
by a dense ``[T, held]`` matrix cut from the ``[T, E]`` one that is 0
where a token did not choose it: no sort, no gather by expert, no
grouping.  A tree that holds only a share of the experts (``first_expert``,
as many as its stacked weights have) gives that share's part of the
routed sum, beside the whole shared expert: what the absent experts would
add is left out.

Multi-token prediction (``num_nextn_predict_layers``) is no part of the
main model's logits and is left out.

``jax.numpy`` only; reads a parameter tree under the names of the repo's
checkpoint layout and imports nothing of the program.  ``precision``:
``fp32`` (float32, ``highest`` matmuls) or ``bf16`` (weights, activations
and the router's input rounded to bfloat16; float32 statistics in softmax
and RMSNorm, float32 accumulation in a matmul, float32 rotation).
"""

import jax
import jax.numpy as jnp

from . import numerics as nx

HEAD_BLOCK = 8
QUERY_BLOCK = 704
ROW_BLOCK = 2112
ROUTER_EPS = 1e-20


def _block(n, most):
    """The largest divisor of ``n`` that is at most ``most``."""
    b = min(n, most)
    while n % b:
        b -= 1
    return b


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


def latent_attention(x, p, *, heads, nope, rope, v_dim, theta, eps,
                     precision):
    T, D = x.shape
    dt = x.dtype
    c_q = rms_norm(_proj(x, p["q_a_proj"], precision),
                   p["q_a_layernorm"]["weight"], eps)
    kv_a = _proj(x, p["kv_a_proj_with_mqa"], precision)
    latent = kv_a.shape[1] - rope
    c_kv = rms_norm(kv_a[:, :latent], p["kv_a_layernorm"]["weight"], eps)
    k_r = rotary(kv_a[:, None, latent:], theta)[:, 0]          # [T, rope]
    scale = (nope + rope) ** -0.5
    hb = _block(heads, HEAD_BLOCK)
    groups = heads // hb
    by_group = lambda w, per_head: w.reshape(
        w.shape[0], groups, hb * per_head).swapaxes(0, 1)
    w_qb = by_group(p["q_b_proj"]["kernel"], nope + rope)
    w_kvb = by_group(p["kv_b_proj"]["kernel"], nope + v_dim)
    w_o = p["o_proj"]["kernel"].reshape(groups, hb * v_dim, D)
    block = _block(T, QUERY_BLOCK)
    cols = jnp.arange(T)

    def one_group(out, ws):
        wq, wkv, wo = ws
        q = nx.einsum("tc,cf->tf", c_q, wq, precision).reshape(
            T, hb, nope + rope)
        q_nope, q_r = q[..., :nope], rotary(q[..., nope:], theta)
        kv = nx.einsum("tl,lf->tf", c_kv, wkv, precision).reshape(
            T, hb, nope + v_dim)
        k_nope, v = kv[..., :nope], kv[..., nope:]

        def one_block(start):
            cut = lambda t: jax.lax.dynamic_slice_in_dim(t, start, block,
                                                         axis=0)
            s = (nx.einsum("qhd,khd->hqk", cut(q_nope), k_nope,
                           precision).astype(jnp.float32)
                 + nx.einsum("qhd,kd->hqk", cut(q_r), k_r,
                             precision).astype(jnp.float32)) * scale
            rows = start + jnp.arange(block)
            s = jnp.where(cols[None, None, :] <= rows[None, :, None], s,
                          -1e30)
            return nx.einsum("hqk,khd->qhd", nx.softmax(s).astype(dt), v,
                             precision)

        o = jax.lax.map(one_block, jnp.arange(0, T, block))
        return out + nx.einsum("tf,fd->td", o.reshape(T, hb * v_dim), wo,
                               precision).astype(jnp.float32), None

    out, _ = jax.lax.scan(one_group, jnp.zeros((T, D), jnp.float32),
                          (w_qb, w_kvb, w_o))
    return out.astype(dt)


def swiglu(x, w1, w3, w2, precision):
    hidden = jax.nn.silu(nx.einsum("td,df->tf", x, w1, precision)) \
        * nx.einsum("td,df->tf", x, w3, precision)
    return nx.einsum("tf,fd->td", hidden, w2, precision)


def swiglu_by_rows(x, p, precision):
    """``swiglu`` of a ``gate_proj / up_proj / down_proj`` tree, in blocks
    of rows."""
    T, D = x.shape
    block = _block(T, ROW_BLOCK)
    return jax.lax.map(
        lambda xb: swiglu(xb, p["gate_proj"]["kernel"],
                          p["up_proj"]["kernel"], p["down_proj"]["kernel"],
                          precision),
        x.reshape(T // block, block, D)).reshape(T, D)


def expert_ffn(x, p, *, top_k, scale, first_expert, precision):
    T = x.shape[0]
    scores = jax.nn.sigmoid(nx.einsum("td,de->te", x, p["router"],
                                      precision).astype(jnp.float32))
    _, sel = jax.lax.top_k(scores, top_k)
    w = jnp.take_along_axis(scores, sel, axis=-1)
    w = scale * w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTER_EPS)
    dense = jnp.zeros(scores.shape, jnp.float32).at[
        jnp.arange(T)[:, None], sel].set(w)
    held = p["w1"].shape[0]
    mine = jax.lax.dynamic_slice_in_dim(dense, first_expert, held, axis=1)

    def one_expert(y, xs):
        w1, w3, w2, weight = xs
        part = swiglu(x, w1, w3, w2, precision) * weight[:, None].astype(
            x.dtype)
        return y + part.astype(jnp.float32), None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros(x.shape, jnp.float32),
                             (p["w1"], p["w3"], p["w2"], mine.T))
    return swiglu_by_rows(x, p["shared_experts"], precision) \
        + routed.astype(x.dtype)


def forward(params, tokens, *, heads, nope, rope, v_dim, top_k, theta,
            eps=1e-5, scale=1.0, first_expert=0, precision="fp32"):
    """Logits ``[T, V]`` (float32) of one sequence ``tokens`` ``[T]``."""
    dt = nx.act_dtype(precision)
    x = params["embed_tokens"]["embedding"][tokens].astype(dt)
    dec = params["decoder"]
    n_layers = sum(1 for name in dec if name.startswith("layers_"))
    for i in range(n_layers):
        p = dec[f"layers_{i}"]
        norm = lambda t, name: rms_norm(t, p[name]["weight"], eps)
        h = x + norm(latent_attention(
            norm(x, "input_layernorm"), p["self_attn"], heads=heads,
            nope=nope, rope=rope, v_dim=v_dim, theta=theta, eps=eps,
            precision=precision), "post_attention_layernorm")
        ff = p["feed_forward"]
        normed = norm(h, "pre_mlp_layernorm")
        if "router" in ff:
            ffn = expert_ffn(normed, ff, top_k=top_k, scale=scale,
                             first_expert=first_expert, precision=precision)
        else:
            ffn = swiglu_by_rows(normed, ff, precision)
        x = h + norm(ffn, "post_mlp_layernorm")
    x = rms_norm(x, dec["final_layer_norm"]["weight"], eps)
    return nx.einsum("td,dv->tv", x, params["lm_head"]["kernel"],
                     precision).astype(jnp.float32)
