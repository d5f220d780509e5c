"""Plain reference of the decoder LM the ``laguna_xs2`` cell serves:
poolside/Laguna-XS.2 ``config.json`` (``model_type laguna``), attention
layers of two kinds in the order ``layer_types`` gives, a dense
feed-forward in the layers ``mlp_layer_types`` calls ``dense`` and a
shared expert beside routed experts in the others.

With ``RMSNorm`` (eps ``rms_norm_eps``, float32 statistics) and no bias
anywhere:

Block (pre-norm):  ``h = x + Attn(RMSNorm_op(x))``;
``y = h + FFN(RMSNorm_ffn(h))``.  After the last layer a final RMSNorm,
then an untied head.

``Attn(u)`` of layer ``l``: ``q = u W_q`` as ``H_l`` heads of
``head_dim`` (``num_attention_heads_per_layer[l]``), ``k = u W_k``, ``v =
u W_v`` as ``num_key_value_heads`` heads; rotary on q and k (below); query
``i`` of head ``h`` attends the keys ``j <= i`` of K/V head ``h // (H_l /
kv heads)``, and in a ``sliding_attention`` layer only those with ``j > i
- sliding_window``; ``softmax(q k^T / sqrt(head_dim))`` in float32; ``o_h
= sigmoid(u W_g)_h * sum_j p_ij v_j`` with ``W_g`` ``[hidden, H_l]`` (one
gate a head); ``W_o``.  Computed in blocks of queries and, inside a
block, one K/V head's group of query heads at a time; a sliding layer
reads only the band of keys a block of queries can see.

Rotary: rotate-half over the first ``lanes`` of each head, the other
lanes unrotated, angles ``position * inv_freq_i``.  ``sliding_attention``
layers: all ``head_dim`` lanes, ``inv_freq_i = theta^(-2i/lanes)``.
``full_attention`` layers: ``lanes = partial_rotary_factor * head_dim``
and YaRN: with ``f_i = theta^(-2i/lanes)`` and ``c(r) = lanes *
ln(original_max_position_embeddings / (2 pi r)) / (2 ln theta)``, ``low =
floor(c(beta_fast))``, ``high = ceil(c(beta_slow))`` held to ``[0, lanes -
1]``, ``ramp_i = clip((i - low) / (high - low), 0, 1)``, ``inv_freq_i =
f_i (1 - ramp_i) + (f_i / factor) ramp_i``; cos and sin are multiplied by
``attention_factor``.

Dense FFN: ``W_2(silu(W_1 x) * W_3 x)``.

Expert FFN: ``s = sigmoid(W_r x)`` (``router_outputs`` scores, float32);
``sel = top_k(s)``; ``w = s[sel] / (sum s[sel] + 1e-6)``, times
``moe_routed_scaling_factor``, applied to the experts' OUTPUTS; ``y =
Shared(x) + sum_{e in sel} w_e E_e(x)``, all SwiGLU.  Computed as a loop
over the experts, each applied to EVERY token of a block of rows and
weighted by a dense ``[rows, E]`` matrix that is 0 where a token did not
choose it: no sort, no gather by expert, no grouping.

DEPARTURES from the published description, each also in the
configuration file's ``assumed`` / ``reduced``:

- a tree that holds only a share of the routed experts (``first_expert``,
  as many as its stacked weights have) gives that share's part of ``y``:
  what the absent experts would add is added by nobody (the deployment's
  cut); the vocabulary is whatever slice the tree's embedding and head
  hold;
- ``gating: true`` has no formula in the config: taken as the head-wise
  sigmoid gate above, on the layer's normed input;
- no QK-norm, no selection bias, no expert groups: the config names none.

``jax.numpy`` only; reads a parameter tree under the names of the repo's
checkpoint layout and imports nothing of the program.  ``precision``:
``fp32`` (float32, ``highest`` matmuls) or ``bf16`` (weights, activations
and the router's input rounded to bfloat16; float32 statistics in softmax
and RMSNorm, float32 accumulation in a matmul, float32 rotation).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import numerics as nx

QUERY_BLOCK = 512   # queries of one attention block
ROW_BLOCK = 4224    # rows of one feed-forward block


def _block(total, cap):
    """The largest divisor of ``total`` that is at most ``cap``."""
    size = min(total, cap)
    while total % size:
        size -= 1
    return size


def rms_norm(x, weight, eps):
    """Over the last axis, statistics in float32 whatever the type."""
    xf = x.astype(jnp.float32)
    inv = 1.0 / jnp.sqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                         + eps)
    return (xf * inv).astype(x.dtype) * weight.astype(x.dtype)


def _proj(x, p, precision):
    return nx.einsum("td,df->tf", x, p["kernel"], precision)


def inverse_frequencies(lanes, rope):
    """``lanes // 2`` inverse frequencies of one layer kind's
    ``rope_parameters`` entry (float64): plain, or YaRN's."""
    theta = float(rope["rope_theta"])
    i = np.arange(lanes // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / lanes)
    if rope.get("rope_type", "default") != "yarn":
        return f
    original = rope["original_max_position_embeddings"]

    def c(r):
        return lanes * math.log(original / (2 * math.pi * r)) / (
            2 * math.log(theta))

    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), lanes - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f * (1.0 - ramp) + f / rope["factor"] * ramp


def rotary(x, rope):
    """``x`` [T, H, D] rotated by its position as ``rope`` says, in
    float32; the lanes past ``partial_rotary_factor * D`` pass through."""
    T, _, D = x.shape
    lanes = int(round(D * rope.get("partial_rotary_factor", 1)))
    half = lanes // 2
    inv_freq = jnp.asarray(inverse_frequencies(lanes, rope), jnp.float32)
    angles = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None]
    factor = float(rope.get("attention_factor", 1.0)) \
        if rope.get("rope_type", "default") == "yarn" else 1.0
    cos = (jnp.cos(angles) * factor)[:, None, :]
    sin = (jnp.sin(angles) * factor)[:, None, :]
    xf = x.astype(jnp.float32)
    x1, x2, rest = xf[..., :half], xf[..., half:lanes], xf[..., lanes:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
        axis=-1).astype(x.dtype)


def attention(x, p, *, heads, kv_heads, head_dim, window, rope, precision):
    """One attention layer over ``x`` [T, hidden]; ``window`` 0: every
    key up to the query's."""
    T = x.shape[0]
    dt = x.dtype
    group = heads // kv_heads
    q = rotary(_proj(x, p["q_proj"], precision).reshape(T, heads, head_dim),
               rope)
    k = rotary(_proj(x, p["k_proj"], precision).reshape(
        T, kv_heads, head_dim), rope)
    v = _proj(x, p["v_proj"], precision).reshape(T, kv_heads, head_dim)
    gate = jax.nn.sigmoid(
        _proj(x, p["g_proj"], precision).astype(jnp.float32))   # [T, H]
    q = q.reshape(T, kv_heads, group, head_dim)
    block = _block(T, QUERY_BLOCK)
    # the keys one block of queries can see: all of them, or the band
    # from ``window - 1`` before its first query to its last
    front = window - 1 if window else 0
    span = block + front if window else T
    if window:  # zero keys before position 0, so every band is whole
        k = jnp.concatenate([jnp.zeros((front,) + k.shape[1:], k.dtype), k])
        v = jnp.concatenate([jnp.zeros((front,) + v.shape[1:], v.dtype), v])

    def one_block(start):
        rows = start + jnp.arange(block)
        if window:
            kb = jax.lax.dynamic_slice_in_dim(k, start, span, axis=0)
            vb = jax.lax.dynamic_slice_in_dim(v, start, span, axis=0)
            cols = start - front + jnp.arange(span)
        else:
            kb, vb, cols = k, v, jnp.arange(T)
        seen = (cols[None, :] <= rows[:, None]) & (cols[None, :] >= 0)
        if window:
            seen = seen & (cols[None, :] > rows[:, None] - window)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)

        def one_kv_head(xs):
            qh, kh, vh = xs        # [block, group, D], [span, D], [span, D]
            s = nx.einsum("qgd,kd->gqk", qh * (head_dim ** -0.5), kh,
                          precision)
            s = jnp.where(seen[None], s.astype(jnp.float32), -1e30)
            return nx.einsum("gqk,kd->gqd", nx.softmax(s).astype(dt), vh,
                             precision).swapaxes(0, 1)

        o = jax.lax.map(one_kv_head, (qb.swapaxes(0, 1), kb.swapaxes(0, 1),
                                      vb.swapaxes(0, 1)))
        return o.swapaxes(0, 1)    # [block, kv, group, D]

    o = jax.lax.map(one_block, jnp.arange(0, T, block))
    o = o.reshape(T, heads, head_dim) * gate[..., None].astype(dt)
    return _proj(o.reshape(T, heads * head_dim), p["o_proj"], precision)


def swiglu(x, w1, w3, w2, precision):
    hidden = jax.nn.silu(nx.einsum("td,df->tf", x, w1, precision)) \
        * nx.einsum("td,df->tf", x, w3, precision)
    return nx.einsum("tf,fd->td", hidden, w2, precision)


def _by_rows(fn, x):
    """``fn`` over blocks of rows of ``x`` [T, D]."""
    T, D = x.shape
    block = _block(T, ROW_BLOCK)
    return jax.lax.map(fn, x.reshape(T // block, block, D)).reshape(T, -1)


def dense_ffn(x, p, precision):
    return _by_rows(lambda xb: swiglu(
        xb, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
        p["down_proj"]["kernel"], precision), x)


def expert_ffn(x, p, *, top_k, scale, first_expert, precision):
    def rows(xb):
        n = xb.shape[0]
        scores = jax.nn.sigmoid(nx.einsum(
            "td,de->te", xb, p["router"], precision).astype(jnp.float32))
        _, sel = jax.lax.top_k(scores, top_k)
        w = jnp.take_along_axis(scores, sel, axis=-1)
        w = scale * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
        dense = jnp.zeros(scores.shape, jnp.float32).at[
            jnp.arange(n)[:, None], sel].set(w)
        held = p["w1"].shape[0]
        mine = jax.lax.dynamic_slice_in_dim(dense, first_expert, held, axis=1)

        def one_expert(acc, xs):
            w1, w3, w2, weight = xs
            part = swiglu(xb, w1, w3, w2, precision).astype(jnp.float32)
            return acc + part * weight[:, None], None

        routed, _ = jax.lax.scan(
            one_expert, jnp.zeros(xb.shape, jnp.float32),
            (p["w1"], p["w3"], p["w2"], mine.T))
        sh = p["shared_experts"]
        shared = swiglu(xb, sh["gate_proj"]["kernel"], sh["up_proj"]["kernel"],
                        sh["down_proj"]["kernel"], precision)
        return shared + routed.astype(xb.dtype)

    return _by_rows(rows, x)


def forward(params, tokens, *, layer_types, heads_per_layer, kv_heads,
            head_dim, window, rope, top_k, eps=1e-6, scale=2.5,
            first_expert=0, precision="fp32"):
    """Logits ``[T, V]`` (float32) of one sequence ``tokens`` ``[T]``.
    ``rope``: the config's ``rope_parameters`` (one entry a layer
    kind)."""
    dt = nx.act_dtype(precision)
    x = params["embed_tokens"]["embedding"][tokens].astype(dt)
    dec = params["decoder"]
    for i, kind in enumerate(layer_types):
        p = dec[f"layers_{i}"]
        sliding = kind == "sliding_attention"
        x = x + attention(
            rms_norm(x, p["operator_norm"]["weight"], eps), p["self_attn"],
            heads=heads_per_layer[i], kv_heads=kv_heads, head_dim=head_dim,
            window=window if sliding else 0, rope=rope[kind],
            precision=precision)
        normed = rms_norm(x, p["ffn_norm"]["weight"], eps)
        ff = p["feed_forward"]
        if "router" in ff:
            x = x + expert_ffn(normed, ff, top_k=top_k, scale=scale,
                               first_expert=first_expert, precision=precision)
        else:
            x = x + dense_ffn(normed, ff, precision)
    x = rms_norm(x, dec["final_layer_norm"]["weight"], eps)
    return _by_rows(
        lambda xb: nx.einsum("td,dv->tv", xb, params["lm_head"]["kernel"],
                             precision).astype(jnp.float32), x)
