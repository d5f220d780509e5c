"""Per-kernel tuning specs: shape buckets, candidate configs, runners.

Each tunable op registers an :class:`OpSpec` naming

- ``bucket(workload)`` — the cache-key tuple.  Sequence/row dims are
  pow2-rounded and lead/batch dims dropped so one tuned entry covers a
  family of shapes; head-dim stays exact (it picks the MXU layout) and
  the bias/mask broadcast patterns stay exact (they pick the BlockSpecs).
- ``candidates(workload)`` — the bounded config set.  ``"eager"`` is
  ALWAYS a candidate: when the plain-XLA composition beats every kernel
  config for a bucket, the cache records it and dispatch skips the
  kernel (the r5-era evoformer case, 0.985x, becomes an automatic
  win instead of a silent regression).
- ``build_runner(workload, config)`` — an AOT-compiled zero-arg step of
  the op (fwd+bwd, the training cost) under that config.

Workloads are plain dicts of shapes/dtypes/flags — never arrays — so
dispatch sites can hand them over from inside a jit trace.
"""

import functools

BLOCKING_BUDGET_BYTES = 12 << 20  # explored superset; compile probe is the
                                  # hard filter (fail-open skips a config
                                  # Mosaic rejects)
MAX_KERNEL_CANDIDATES = 8


def pow2_bucket(n):
    """Smallest power of two >= n (the shape-bucket rounding rule)."""
    n = int(n)
    if n <= 1:
        return 1
    return 1 << (n - 1).bit_length()


def describe_config(config):
    if config == "eager":
        return "eager"
    return ",".join(f"{k}={v}" for k, v in sorted(config.items()))


def _pat(op):
    """Broadcast-pattern key for a mask/bias operand: dtype + which dims
    are 1 (exactly what picks its BlockSpec)."""
    if op is None:
        return None
    shape, dtype = op
    return dtype + ":" + "".join("1" if s == 1 else "x" for s in shape)


def _zeros(shape, dtype):
    import jax.numpy as jnp

    return jnp.zeros(shape, jnp.dtype(dtype))


def _aot(fn, *args):
    """Trace+lower+compile now (so timing windows never include compile)
    and return a zero-arg compiled step."""
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    return lambda: compiled(*args)


# ---------------------------------------------------------------------------
# softmax_dropout
# ---------------------------------------------------------------------------


def sd_workload(x_shape, dtype, mask=None, bias=None, dropout_on=True):
    """mask/bias: (shape, dtype-name) or None."""
    return {
        "op": "softmax_dropout",
        "x_shape": tuple(int(s) for s in x_shape),
        "dtype": str(dtype),
        "mask": None if mask is None else (tuple(mask[0]), str(mask[1])),
        "bias": None if bias is None else (tuple(bias[0]), str(bias[1])),
        "dropout_on": bool(dropout_on),
    }


def _sd_bucket(wl):
    q, k = wl["x_shape"][-2], wl["x_shape"][-1]
    return (
        "softmax_dropout", wl["dtype"], len(wl["x_shape"]),
        pow2_bucket(q), pow2_bucket(k),
        _pat(wl["mask"]), _pat(wl["bias"]), int(wl["dropout_on"]),
    )


def _sd_candidates(wl):
    import jax.numpy as jnp

    q, k = wl["x_shape"][-2], wl["x_shape"][-1]
    itemsize = jnp.dtype(wl["dtype"]).itemsize
    n_streams = 3 + (wl["mask"] is not None) + (wl["bias"] is not None)
    cands = ["eager"]
    for blk in (256, 128, 64, 32, 16, 8):
        if blk > q or q % blk:
            continue
        if 2 * n_streams * blk * k * max(itemsize, 4) > BLOCKING_BUDGET_BYTES:
            continue
        cands.append({"q_blk": blk})
    return cands[: 1 + MAX_KERNEL_CANDIDATES]


def _sd_runner(wl, config):
    import jax
    import jax.numpy as jnp

    from unicore_tpu.ops.pallas import softmax_dropout as pl_sd
    from unicore_tpu.ops.softmax_dropout import softmax_dropout_reference

    x = _zeros(wl["x_shape"], wl["dtype"])
    mask = None if wl["mask"] is None else _zeros(*wl["mask"])
    bias = None if wl["bias"] is None else _zeros(*wl["bias"])
    dropout_on = wl["dropout_on"]
    rng = jax.random.PRNGKey(0) if dropout_on else None
    dp = 0.1 if dropout_on else 0.0
    if config == "eager":
        impl = softmax_dropout_reference
    else:
        impl = functools.partial(pl_sd.softmax_dropout,
                                 q_blk=int(config["q_blk"]))

    def loss(x_):
        return jnp.sum(
            impl(x_, dp, rng=rng, is_training=dropout_on,
                 mask=mask, bias=bias).astype(jnp.float32)
        )

    return _aot(jax.grad(loss), x)


def _sd_shrink(wl):
    """Dry-run variant: non-1 lead/batch dims shrink to 2, not 1 —
    collapsing them to 1 would flip the mask/bias broadcast patterns
    (the '1-vs-x' BlockSpec variants AND the bucket key), so the dry run
    would lower different specs than production and record entries under
    different keys.  At 2 the patterns, specs, and bucket are identical;
    only the grid shrinks."""
    xs = wl["x_shape"]
    small = tuple(min(s, 2) for s in xs[:-2]) + xs[-2:]

    def op(o):
        if o is None:
            return None
        shape, dt = o
        off = len(small) - len(shape)
        return (tuple(
            1 if s == 1 else small[i + off] for i, s in enumerate(shape)
        ), dt)

    return dict(wl, x_shape=small, mask=op(wl["mask"]), bias=op(wl["bias"]))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


def flash_workload(q_shape, kv_len, dtype, bias=None, has_pad=False,
                   causal=False, dropout_on=False):
    """q_shape: module layout [B, T, H, D]; bias: (shape4, dtype) or None."""
    return {
        "op": "flash_attention",
        "q_shape": tuple(int(s) for s in q_shape),
        "kv_len": int(kv_len),
        "dtype": str(dtype),
        "bias": None if bias is None else (tuple(bias[0]), str(bias[1])),
        "has_pad": bool(has_pad),
        "causal": bool(causal),
        "dropout_on": bool(dropout_on),
    }


def _flash_bias_class(wl):
    # dtype + q-broadcastness only: both drive the block-size budget (a
    # bQ==1 bias streams ~KBs; a full bias doubles the score-block
    # stream).  Head-broadcastness is deliberately NOT bucketed — block
    # choice is independent of it.
    if wl["bias"] is None:
        return None
    shape, dt = wl["bias"]
    return "%s:%s" % (dt, "q1" if shape[2] == 1 else "qT")


def _flash_bucket(wl):
    _, tq, _, d = wl["q_shape"]
    return (
        "flash", wl["dtype"], pow2_bucket(tq), pow2_bucket(wl["kv_len"]), d,
        _flash_bias_class(wl), int(wl["has_pad"]), int(wl["causal"]),
        int(wl["dropout_on"]),
    )


def _flash_candidates(wl):
    import jax.numpy as jnp

    from unicore_tpu.ops.pallas.flash_attention import _pick_blocks

    _, tq, _, d = wl["q_shape"]
    tk = wl["kv_len"]
    bias_itemsize = 0
    if wl["bias"] is not None and wl["bias"][0][2] != 1:
        bias_itemsize = jnp.dtype(wl["bias"][1]).itemsize
    pairs = [_pick_blocks(tq, tk, bias_itemsize)]  # the heuristic is always
                                                   # in the running
    for bq in (1024, 512, 384, 256, 128):
        if bq > tq or tq % bq:
            continue
        for bk in (tk, 2048, 1536, 1024, 512, 256, 128):
            if bk > tk or tk % bk:
                continue
            # fp32 score block + bias stream against scoped VMEM (soft
            # bound at 2x the heuristic's; compile probe is the hard one)
            if bq * bk * (4 + 2 * bias_itemsize) > BLOCKING_BUDGET_BYTES:
                continue
            if (bq, bk) not in pairs:
                pairs.append((bq, bk))
    pairs = pairs[:MAX_KERNEL_CANDIDATES]
    return ["eager"] + [{"block_q": bq, "block_k": bk} for bq, bk in pairs]


def _flash_eager_loss(q, k, v, bias, pad, causal, dp, rng, scale):
    """The materialized einsum + reference-softmax composition — exactly
    the module fallback path (multihead_attention._attend)."""
    import jax.numpy as jnp

    from unicore_tpu.ops.softmax_dropout import softmax_dropout_reference
    from unicore_tpu.utils import causal_iota_mask

    def loss(q_):
        s = jnp.einsum("bqhd,bkhd->bhqk", q_ * scale, k)
        if pad is not None:
            s = s + jnp.where(pad.astype(bool)[:, None, None, :],
                              jnp.float32(-1e30), 0.0).astype(s.dtype)
        b = bias
        if causal:
            cb = causal_iota_mask(q_.shape[1], k.shape[1])[None, None]
            b = cb if b is None else b + cb
        p = softmax_dropout_reference(
            s, dp, rng=rng, is_training=dp > 0.0, bias=b
        )
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        return jnp.sum(o.astype(jnp.float32))

    return loss


def _flash_runner(wl, config):
    import jax
    import jax.numpy as jnp

    from unicore_tpu.ops.pallas.flash_attention import flash_attention

    bsz, tq, heads, d = wl["q_shape"]
    tk = wl["kv_len"]
    q = _zeros(wl["q_shape"], wl["dtype"])
    kv = _zeros((bsz, tk, heads, d), wl["dtype"])
    bias = None if wl["bias"] is None else _zeros(*wl["bias"])
    pad = _zeros((bsz, tk), "int32") if wl["has_pad"] else None
    dropout_on = wl["dropout_on"]
    rng = jax.random.PRNGKey(0) if dropout_on else None
    dp = 0.1 if dropout_on else 0.0
    scale = d ** -0.5

    if config == "eager":
        loss = _flash_eager_loss(q, kv, kv, bias, pad, wl["causal"], dp,
                                 rng, scale)
        return _aot(jax.grad(loss), q)

    def loss(q_):
        o = flash_attention(
            q_, kv, kv, bias=bias, key_padding_mask=pad,
            causal=wl["causal"], dropout_prob=dp, rng=rng,
            is_training=dropout_on, scale=scale,
        )
        return jnp.sum(o.astype(jnp.float32))

    # the forced config must be live while the jit TRACES (picked_blocks
    # runs at trace time); tuner.py wraps build_runner in forced_config
    return _aot(jax.grad(loss), q)


def _flash_shrink(wl):
    bsz, tq, heads, d = wl["q_shape"]
    bias = wl["bias"]
    if bias is not None:
        shape, dt = bias
        bias = ((1,) + shape[1:], dt)
    return dict(wl, q_shape=(1, tq, heads, d), bias=bias)


# ---------------------------------------------------------------------------
# ragged paged attention (serve-tier unified prefill+decode step)
# ---------------------------------------------------------------------------


def ragged_workload(q_shape, table_pages, page_size, dtype):
    """q_shape: module layout [B, T, H, D] — T is the serve engine's
    prefill-chunk width (1 = the pure-decode dispatch)."""
    return {
        "op": "ragged_paged_attention",
        "q_shape": tuple(int(s) for s in q_shape),
        "table_pages": int(table_pages),
        "page_size": int(page_size),
        "dtype": str(dtype),
    }


def _ragged_bucket(wl):
    bsz, t, heads, d = wl["q_shape"]
    # batch/chunk are bucketed (the serve engine's fixed max_batch and
    # chunk width make them near-static anyway); heads/head-dim/
    # page-size exact — they pick the scratch layout and DMA shape
    return ("ragged_paged_attention", wl["dtype"], pow2_bucket(bsz),
            pow2_bucket(t), heads, d, wl["page_size"],
            pow2_bucket(wl["table_pages"]))


def _ragged_candidates(wl):
    from unicore_tpu.ops.pallas.paged_attention import pick_pages_per_block

    _, t, heads, d = wl["q_shape"]
    import jax.numpy as jnp

    itemsize = jnp.dtype(wl["dtype"]).itemsize
    heuristic = pick_pages_per_block(
        wl["table_pages"], wl["page_size"], d, num_heads=heads,
        itemsize=itemsize,
    )
    pps = [heuristic]
    for pp in (1, 2, 4, 8):
        if pp <= wl["table_pages"] and pp not in pps:
            pps.append(pp)
    cands = ["eager"] + [{"pages_per_block": pp} for pp in pps]
    # prefill-chunk candidates: the same prompt slice admitted in
    # halved-width chunks (more dispatches of a narrower program) —
    # what the engine's --prefill-chunk auto pick consults via
    # tuned_prefill_chunk
    c = t // 2
    while c >= 8 and len(cands) < 1 + MAX_KERNEL_CANDIDATES:
        cands.append({"pages_per_block": heuristic, "prefill_chunk": c})
        c //= 2
    return cands[: 1 + MAX_KERNEL_CANDIDATES]


def _ragged_args(wl, width):
    import jax.numpy as jnp

    bsz, _, heads, d = wl["q_shape"]
    pages, ps = wl["table_pages"], wl["page_size"]
    num_pages = bsz * pages + 1  # page 0 reserved (trash)
    q = _zeros((bsz, width, heads, d), wl["dtype"])
    pool = _zeros((num_pages * ps, heads * d), wl["dtype"])
    table = (1 + jnp.arange(bsz * pages, dtype=jnp.int32).reshape(
        bsz, pages))
    lengths = jnp.full((bsz,), pages * ps, jnp.int32)
    # the chunk's queries sit at the row's last `width` positions
    positions = (lengths[:, None] - width
                 + jnp.arange(width, dtype=jnp.int32)[None])
    return q, pool, table, positions, lengths


def _ragged_runner(wl, config):
    import jax.numpy as jnp

    ps = wl["page_size"]
    d = wl["q_shape"][3]
    t = wl["q_shape"][1]
    scale = d ** -0.5
    chunk = t
    if config != "eager" and "prefill_chunk" in config:
        chunk = max(1, min(int(config["prefill_chunk"]), t))
    q, pool, table, positions, lengths = _ragged_args(wl, chunk)
    n_calls = max(1, -(-t // chunk))  # chunked admission of the slice

    if config == "eager":
        from unicore_tpu.serve.attention import paged_attention_reference

        def run(q_):
            return paged_attention_reference(
                q_, pool, pool, table, positions, lengths, ps, scale
            ).astype(jnp.float32)
    else:
        from unicore_tpu.ops.pallas.paged_attention import (
            ragged_paged_attention,
        )

        pp = int(config["pages_per_block"])

        def run(q_):
            return ragged_paged_attention(
                q_, pool, pool, table, positions, lengths, page_size=ps,
                scale=scale, pages_per_block=pp,
            ).astype(jnp.float32)

    if n_calls == 1:
        return _aot(run, q)

    def chunked(q_):
        # serialize n dependent calls (feeding the previous output back
        # into the next query defeats CSE): the timed cost is the whole
        # chunked admission of the slice, not one narrow dispatch
        out = run(q_)
        for _ in range(n_calls - 1):
            q_ = q_ + (0.0 * out.sum()).astype(q_.dtype)
            out = run(q_)
        return out

    return _aot(chunked, q)


def _ragged_shrink(wl):
    bsz = min(wl["q_shape"][0], 2)
    return dict(
        wl,
        q_shape=(bsz,) + wl["q_shape"][1:],
        table_pages=min(wl["table_pages"], 4),
    )


# ---------------------------------------------------------------------------
# fused chunked linear + cross-entropy head
# ---------------------------------------------------------------------------


def ce_workload(rows, hidden, vocab, dtype, tied=True, has_bias=True):
    return {
        "op": "fused_cross_entropy",
        "rows": int(rows), "hidden": int(hidden), "vocab": int(vocab),
        "dtype": str(dtype), "tied": bool(tied), "has_bias": bool(has_bias),
    }


def _ce_bucket(wl):
    # rows/vocab pow2-bucketed (one entry covers a batch-size family);
    # hidden exact — it picks the MXU layout of every chunk matmul
    return ("fused_ce", wl["dtype"], pow2_bucket(wl["rows"]), wl["hidden"],
            pow2_bucket(wl["vocab"]), int(wl["tied"]), int(wl["has_bias"]))


def _ce_candidates(wl):
    from unicore_tpu.ops.fused_cross_entropy import pick_chunk

    chunks = [pick_chunk(wl["rows"], wl["vocab"])]
    for c in (2048, 1024, 512, 256, 128, 64):
        if c > wl["rows"] or c in chunks:
            continue
        # per-chunk fp32 logits are an HBM temporary, not VMEM — the
        # bound only excludes configs that defeat the op's purpose
        if c * wl["vocab"] * 4 > (128 << 20):
            continue
        chunks.append(c)
    return ["eager"] + [
        {"chunk": c} for c in chunks[:MAX_KERNEL_CANDIDATES]
    ]


def _ce_runner(wl, config):
    import jax
    import jax.numpy as jnp

    from unicore_tpu.ops.fused_cross_entropy import (
        fused_linear_cross_entropy, linear_nll_reference,
    )

    rows, hidden, vocab = wl["rows"], wl["hidden"], wl["vocab"]
    tied = wl["tied"]
    f = _zeros((rows, hidden), wl["dtype"])
    k = _zeros((vocab, hidden) if tied else (hidden, vocab), wl["dtype"])
    bias = _zeros((vocab,), "float32") if wl["has_bias"] else None
    t = jnp.zeros((rows,), jnp.int32)

    if config == "eager":
        def loss(f_, k_):
            return jnp.sum(linear_nll_reference(f_, k_, t, bias=bias,
                                                tied=tied))
    else:
        chunk = int(config["chunk"])

        def loss(f_, k_):
            return jnp.sum(fused_linear_cross_entropy(
                f_, k_, t, bias=bias, tied=tied, chunk_size=chunk,
            ))

    # fwd+bwd wrt features AND weight — the training cost of the head
    return _aot(jax.grad(loss, argnums=(0, 1)), f, k)


def _ce_shrink(wl):
    return dict(wl, rows=min(wl["rows"], 256), hidden=min(wl["hidden"], 64),
                vocab=min(wl["vocab"], 512))


# ---------------------------------------------------------------------------
# optim_sr_cast — stochastic-rounding fp32 -> bf16 (optimizer moments)
# ---------------------------------------------------------------------------


def sr_cast_workload(n, dtype="float32"):
    """``n``: flat element count of the cast leaf (the moment sizes the
    bf16-moment optimizer store re-quantizes every update)."""
    return {"op": "optim_sr_cast", "n": int(n), "dtype": str(dtype)}


def _sr_cast_bucket(wl):
    # one entry covers a pow2 family of leaf sizes; the kernel's row
    # block is a pure function of n (pick_layout), so the config space
    # is impl choice only
    return ("optim_sr_cast", wl["dtype"], pow2_bucket(wl["n"]))


def _sr_cast_candidates(wl):
    # eager (threefry jnp reference) vs the Pallas VMEM-tiled kernel:
    # both are ONE bit-twiddling pass, so the only question the timing
    # answers is whether the kernel's fixed costs amortize at this size
    return ["eager", {"impl": "pallas"}]


def _sr_cast_runner(wl, config):
    import jax

    from unicore_tpu.ops.rounding import fp32_to_bf16_sr_reference

    x = _zeros((wl["n"],), wl["dtype"])
    rng = jax.random.PRNGKey(0)
    if config == "eager":
        return _aot(fp32_to_bf16_sr_reference, x, rng)
    from unicore_tpu.ops.pallas import rounding as pl_impl

    return _aot(pl_impl.fp32_to_bf16_sr, x, rng)


def _sr_cast_shrink(wl):
    return dict(wl, n=min(wl["n"], 4096))


# ---------------------------------------------------------------------------
# layer_norm
# ---------------------------------------------------------------------------


def ln_workload(rows, hidden, dtype):
    return {"op": "layer_norm", "rows": int(rows), "hidden": int(hidden),
            "dtype": str(dtype)}


def _ln_candidates(wl):
    # the Pallas LayerNorm kernel was deleted in r5 after honest
    # re-measurement (0.671x vs XLA's own fusion, docs/performance.md);
    # the op declares its own candidate set (eager only) and tuning
    # simply RECORDS its cost so the cache documents the verdict per
    # device kind
    from unicore_tpu.ops.layer_norm import TUNING_CANDIDATES

    return [c if c == "eager" else dict(c) for c in TUNING_CANDIDATES]


def _ln_runner(wl, config):
    import jax
    import jax.numpy as jnp

    from unicore_tpu.ops.layer_norm import layer_norm

    x = _zeros((wl["rows"], wl["hidden"]), wl["dtype"])
    w = jnp.ones((wl["hidden"],), jnp.float32)
    b = jnp.zeros((wl["hidden"],), jnp.float32)

    def loss(x_):
        return jnp.sum(layer_norm(x_, w, b).astype(jnp.float32))

    return _aot(jax.grad(loss), x)


def _ln_shrink(wl):
    return dict(wl, rows=min(wl["rows"], 64))


class OpSpec:
    def __init__(self, name, bucket, candidates, build_runner, shrink):
        self.name = name
        self.bucket = bucket
        self.candidates = candidates
        self.build_runner = build_runner
        self.shrink = shrink


OPS = {
    "softmax_dropout": OpSpec(
        "softmax_dropout", _sd_bucket, _sd_candidates, _sd_runner, _sd_shrink
    ),
    "flash_attention": OpSpec(
        "flash_attention", _flash_bucket, _flash_candidates, _flash_runner,
        _flash_shrink,
    ),
    "layer_norm": OpSpec(
        "layer_norm",
        lambda wl: ("layer_norm", wl["dtype"], pow2_bucket(wl["rows"]),
                    wl["hidden"]),
        _ln_candidates, _ln_runner, _ln_shrink,
    ),
    "ragged_paged_attention": OpSpec(
        "ragged_paged_attention", _ragged_bucket, _ragged_candidates,
        _ragged_runner, _ragged_shrink,
    ),
    "fused_cross_entropy": OpSpec(
        "fused_cross_entropy", _ce_bucket, _ce_candidates, _ce_runner,
        _ce_shrink,
    ),
    "optim_sr_cast": OpSpec(
        "optim_sr_cast", _sr_cast_bucket, _sr_cast_candidates,
        _sr_cast_runner, _sr_cast_shrink,
    ),
}


# Preset workloads for the CLI: the shapes the bench and the flagship
# configs actually run (the r5-era micro set).
PRESETS = {
    "sd_bert": sd_workload(
        (32, 12, 512, 512), "bfloat16",
        bias=((1, 12, 512, 512), "bfloat16"), dropout_on=True,
    ),
    "sd_evoformer": sd_workload(
        (1, 128, 4, 128, 128), "bfloat16",
        mask=((1, 128, 1, 1, 128), "bfloat16"),
        bias=((1, 1, 4, 128, 128), "bfloat16"), dropout_on=True,
    ),
    "sd_k2048": sd_workload(
        (4, 8, 1024, 2048), "bfloat16",
        bias=((1, 8, 1024, 2048), "bfloat16"), dropout_on=True,
    ),
    "flash_bert": flash_workload(
        (8, 512, 12, 64), 512, "bfloat16",
        bias=((1, 12, 512, 512), "bfloat16"), has_pad=True, dropout_on=True,
    ),
    "flash_t2048": flash_workload(
        (4, 2048, 12, 64), 2048, "bfloat16", causal=False, dropout_on=False,
    ),
    "layer_norm_bert": ln_workload(16384, 768, "bfloat16"),
    # unified serve step: batch 8, chunk 32, 8 heads x 64, 16-token
    # pages, 2k context (the decode-only paged_decode_b8 preset retired
    # with the per-bucket prefill jits — the width-1 dispatch is the
    # same program family)
    "ragged_serve_b8": ragged_workload((8, 32, 8, 64), 128, 16,
                                       "bfloat16"),
    # MLM head at the batch-64 bench shape: 8192 static slots
    # (32768 tokens x 0.25 capacity), tied-embedding projection
    "fused_ce_bert": ce_workload(8192, 768, 30528, "bfloat16"),
    # bf16-moment SR re-quantization at the BERT-base attention-kernel
    # leaf size (768x768) — the shape --optim-bf16-moments casts ~48
    # times per update
    "optim_sr_cast_moments": sr_cast_workload(768 * 768),
}
