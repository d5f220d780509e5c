"""Self/cross multi-head attention flax modules.

Parity target: ``unicore/modules/multihead_attention.py`` —
``SelfMultiheadAttention`` (fused QKV projection, ``scaling_factor`` knob,
key-padding -inf fill, additive attn bias through the fused softmax) and
``CrossMultiheadAttention`` (separate q/k/v projections).

TPU-first redesign: the reference flattens to ``[B*H, T, D]`` and uses
``torch.bmm``; here heads stay a named axis — ``[B, T, H, D]`` einsums — so
XLA maps the contractions straight onto the MXU and shardings can target the
head axis (tensor parallelism) without reshapes.  ``attn_bias`` accepts
anything broadcastable to ``[B, H, q, k]``; the reference's ``[B*H, q, k]``
convention is detected and reshaped.
"""

from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from unicore_tpu import ops
from unicore_tpu.parallel import tp_constraint

bert_init = nn.initializers.normal(stddev=0.02)

# batch rides (data, fsdp) — the same pair data_sharding() uses
_BATCH_AXES = ("data", "fsdp")


def _canon_bias(bias, bsz, num_heads):
    """Accept [B*H, q, k] (reference convention) or anything broadcastable to
    [B, H, q, k]."""
    if bias is None:
        return None
    if bias.ndim == 3 and bias.shape[0] == bsz * num_heads:
        return bias.reshape(bsz, num_heads, bias.shape[1], bias.shape[2])
    return bias


def _padding_bias(key_padding_mask, dtype):
    """[B, S] bool/int mask (True = pad) -> additive [B, 1, 1, S] -inf bias."""
    if key_padding_mask is None:
        return None
    neg_inf = jnp.asarray(float("-inf"), dtype=jnp.float32)
    return jnp.where(
        key_padding_mask.astype(bool)[:, None, None, :], neg_inf, 0.0
    )


def _flash_ok(q, k, bias, has_pad, dropout_on, causal=False):
    from unicore_tpu.ops.backend import note_dispatch

    desc = "q%s k%d %s bias=%s pad=%s causal=%s dropout=%s" % (
        tuple(q.shape), k.shape[1], q.dtype.name,
        None if bias is None else tuple(bias.shape),
        has_pad, causal, dropout_on,
    )
    return note_dispatch("flash_attention", desc, _flash_wins(q, k, bias))


def _flash_wins(q, k, bias):
    from unicore_tpu.ops.backend import get_kernel_backend, use_pallas
    from unicore_tpu.ops.pallas import flash_attention as fa

    if not use_pallas():
        return False
    from unicore_tpu.parallel import tensor_parallel_mesh

    tp_mesh = tensor_parallel_mesh()
    if tp_mesh is not None:
        tp = dict(zip(tp_mesh.axis_names, tp_mesh.devices.shape))["tensor"]
        if q.shape[2] % tp == 0:
            # this layer's heads shard over the tensor axis, and
            # pallas_call carries no SPMD partitioning rule: GSPMD would
            # all-gather the head-sharded q/k/v around the kernel,
            # defeating TP; the einsum path partitions head-wise for free.
            # (heads not divisible -> the layer replicates; flash is fine)
            return False
    qs = (q.shape[0], q.shape[2], q.shape[1], q.shape[3])
    ks = (k.shape[0], k.shape[2], k.shape[1], k.shape[3])
    if not fa.eligible(qs, ks, None if bias is None else bias.shape):
        return False
    from unicore_tpu.ops.backend import spmd_mesh

    mesh = spmd_mesh()
    if mesh is not None:
        # the kernel runs per batch shard (_flash_per_shard): the batch
        # must split evenly and a bias must be shared or split with it
        if q.shape[0] % _batch_shards(mesh):
            return False
        if bias is not None and bias.shape[0] not in (1, q.shape[0]):
            return False
    # measured on v5e (BERT-base, T=512, trainable [1,H,T,T] bias,
    # dropout): in the SINGLE-BLOCK regime the fused backward computes
    # dq/dk/dv/dbias in one pass; isolated it is 1.6x faster than the
    # materialized einsum + fused-softmax path, end-to-end the 12-layer
    # model TIES at batch 32 (192.8 vs 193.7 samples/s interleaved; the
    # layout transposes around the kernel eat the isolated win) — but
    # flash's O(T) residual footprint is what fits batch 64 in HBM at all
    # (229.5 vs 217 samples/s best configs; the materialized path's
    # per-layer [B,H,T,T] out+softmax residuals OOM), so single-block
    # flash is preferred.  In the MULTI-block regime a trainable bias
    # still pays a separate dbias recompute sweep, which loses below
    # T=1024; flash wins again once [B,H,Tq,Tk] is HBM-prohibitive.  A
    # forced "pallas" backend always takes flash.
    if get_kernel_backend() != "pallas" and bias is not None:
        bq, bk = fa.picked_blocks(
            q.shape[1], k.shape[1], bias.shape, bias.dtype
        )
        single_block = q.shape[1] == bq and k.shape[1] == bk
        if not single_block and k.shape[1] < 1024:
            return False
    return True


def _batch_shards(mesh):
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    return shape.get("data", 1) * shape.get("fsdp", 1)


def _flash_per_shard(q, k, v, bias, key_padding_mask, rng, **kw):
    """The flash kernel, partitioned by hand where GSPMD cannot do it:
    under a multi-device mesh every batch shard runs the kernel on its
    own rows (``shard_map`` over the batch axes; a shared bias is
    replicated and its gradient summed by the transpose).  The dropout
    seeds are per GLOBAL batch row, so the masks are those of the
    unsharded call."""
    import jax
    from jax.sharding import PartitionSpec as P

    from unicore_tpu.ops.backend import spmd_mesh
    from unicore_tpu.ops.pallas.flash_attention import flash_attention
    from unicore_tpu.parallel._seed_utils import batch_shard_index

    mesh = spmd_mesh()
    if mesh is None:
        return flash_attention(q, k, v, bias=bias,
                               key_padding_mask=key_padding_mask, rng=rng,
                               **kw)
    axes = tuple(a for a in _BATCH_AXES if a in mesh.axis_names)
    local_rows = q.shape[0] // _batch_shards(mesh)
    rows = P(axes)
    # optional operands ride along as a dict: absent ones are not traced
    extra = {"bias": bias, "key_padding_mask": key_padding_mask,
             "rng": rng}
    extra = {n: x for n, x in extra.items() if x is not None}
    specs = {"bias": rows if bias is not None and bias.shape[0] != 1
             else P(), "key_padding_mask": rows, "rng": P()}

    def local(q, k, v, extra):
        return flash_attention(
            q, k, v, batch_seed_offset=batch_shard_index(axes) * local_rows,
            **extra, **kw)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(rows, rows, rows, {n: specs[n] for n in extra}),
        out_specs=rows,
        check_vma=False,  # pallas_call's out_shape carries no vma
    )(q, k, v, extra)


_warned_seq_parallel_dropout = [False]


def _seq_parallel_attend(q, k, v, scaling, dropout, key_padding_mask, bias,
                         causal=False, rng=None):
    """Sequence-parallel attention dispatch (mesh ``seq`` axis > 1).

    Returns None when the shapes don't fit the active scheme (sequence or
    batch not divisible by the mesh axes; self-attention only) — the
    caller then falls back to local attention.  Attention dropout IS
    implemented (since r4): ring derives per-(q-block, k-block) masks
    from global block identity; Ulysses decorrelates per head-shard
    device — ``--seq-parallel-skip-attention-dropout`` is retired (now a
    deprecated no-op, warned once).
    """
    import logging

    from unicore_tpu import parallel

    sp = parallel.sequence_parallel()
    if sp is None:
        return None
    mesh, impl = sp
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = shape["seq"]
    batch_div = shape.get("data", 1) * shape.get("fsdp", 1)
    t, h = q.shape[1], q.shape[2]
    if q.shape[1] != k.shape[1] or t % n != 0:
        return None
    if q.shape[0] % batch_div != 0:
        return None  # uneven batch: shard_map would hard-fail
    if impl == "ulysses" and h % n != 0:
        return None

    if dropout > 0.0 and parallel.sequence_parallel_allows_dropout_skip():
        if not _warned_seq_parallel_dropout[0]:
            _warned_seq_parallel_dropout[0] = True
            logging.getLogger(__name__).warning(
                "--seq-parallel-skip-attention-dropout is deprecated and "
                "ignored: sequence-parallel attention dropout is "
                "implemented (ring: global-block-identity seeds; Ulysses: "
                "per-device seed offsets)"
            )

    if key_padding_mask is not None:
        key_padding_mask = key_padding_mask.astype(bool)
    if bias is not None:
        while bias.ndim < 4:
            bias = bias[None]
        if bias.shape[2] != t:  # ring shards bias rows; need full [*, *, T, S]
            bias = jnp.broadcast_to(bias, bias.shape[:2] + (t, bias.shape[3]))

    # only axes the mesh actually has (a bare ("seq",) mesh is legal for
    # direct module use; shard_map rejects specs naming absent axes)
    batch_axes = tuple(a for a in ("data", "fsdp") if a in mesh.axis_names)
    attend = (
        parallel.ulysses_self_attention if impl == "ulysses"
        else parallel.ring_self_attention
    )
    return attend(
        mesh, q, k, v, bias=bias, key_padding_mask=key_padding_mask,
        causal=causal, scale=scaling, batch_axes=batch_axes,
        dropout_p=dropout, rng=rng,
    )


def _causal_bias(tq, tk, dtype=jnp.float32):
    """Additive [1, 1, tq, tk] fused-iota causal mask (shared helper:
    ``utils.causal_iota_mask``; -1e30 fill like the flash kernel — a
    literal -inf NaNs fully-masked softmax rows)."""
    from unicore_tpu.utils import causal_iota_mask

    return causal_iota_mask(tq, tk, dtype=dtype)[None, None]


def _segment_bias(segment_ids, tk, dtype=jnp.float32):
    """Additive [B, 1, T, tk] span mask for packed rows (the serve tier's
    row-span problem, PR 13, restated for training): query q may attend
    key k iff both live in the SAME nonzero segment (0 = pad).  Masked
    scores get the -1e30 fill — their softmax terms underflow to exact
    0.0, which is what makes packed per-token nll bit-equal to the padded
    run of the same logical samples.  Composed with the causal bias:
    segments are contiguous, so (causal AND same-segment) is exactly
    segment-causal, with per-segment position reset handled upstream."""
    seg_q = segment_ids[:, None, :, None]
    seg_k = segment_ids[:, None, None, :tk]
    ok = (seg_q == seg_k) & (seg_k != 0)
    return jnp.where(ok, 0.0, -1e30).astype(dtype)


def _attend(q, k, v, scaling, dropout, key_padding_mask, bias, deterministic,
            make_rng, return_attn=False, causal=False, segment_ids=None):
    """Core attention: q/k/v are [B, T, H, D].  Dispatch order: sequence
    parallelism (when the mesh's ``seq`` axis is active), then the flash
    (blockwise) Pallas kernel on TPU when eligible — the key padding mask,
    (batch-broadcast) bias, and causal masking ride into the kernel
    separately, so neither the [B, H, q, k] score matrix nor a [T, T]
    future-mask tensor is ever materialized.  The einsum + fused-softmax
    path is the reference semantics and the fallback.

    ``segment_ids`` [B, T] (nonzero per packed segment, 0 = pad) routes
    through the span-masked eager path: the seq-parallel and flash
    dispatches don't carry the segment mask yet, so packed batches take
    the reference path unconditionally."""
    dtype = q.dtype
    rng = None
    if not deterministic and dropout > 0.0:
        rng = make_rng("dropout")

    if segment_ids is None and not return_attn and q.shape[1] == k.shape[1]:
        sp_out = _seq_parallel_attend(
            q, k, v, scaling, dropout if not deterministic else 0.0,
            key_padding_mask, bias, causal=causal, rng=rng,
        )
        if sp_out is not None:
            return sp_out

    if segment_ids is None and not return_attn and _flash_ok(
        q, k, bias, key_padding_mask is not None, rng is not None,
        causal=causal,
    ):
        return _flash_per_shard(
            q, k, v, bias, key_padding_mask, rng, causal=causal,
            dropout_prob=dropout, is_training=not deterministic,
            scale=scaling,
        )

    mask = _padding_bias(key_padding_mask, dtype)
    if segment_ids is not None:
        sb = _segment_bias(segment_ids, k.shape[1])
        bias = sb if bias is None else bias + sb
    if causal:
        cb = _causal_bias(q.shape[1], k.shape[1])
        bias = cb if bias is None else bias + cb
    # [B, H, q, k] scores; contraction + batched dims map directly to MXU.
    attn_weights = jnp.einsum("bqhd,bkhd->bhqk", q * scaling, k)
    if mask is not None:
        attn_weights = attn_weights + mask.astype(jnp.float32).astype(dtype)
    if return_attn:
        attn_weights = attn_weights if bias is None else attn_weights + bias.astype(dtype)
        probs = ops.softmax_dropout(
            attn_weights, dropout, rng=rng, is_training=not deterministic
        )
    else:
        probs = ops.softmax_dropout(
            attn_weights, dropout, rng=rng, is_training=not deterministic, bias=bias
        )
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    if return_attn:
        return o, attn_weights, probs
    return o


class SelfMultiheadAttention(nn.Module):
    embed_dim: int
    num_heads: int
    dropout: float = 0.1
    bias: bool = True
    scaling_factor: float = 1.0
    rotary: bool = False
    rotary_base: float = 10000.0

    @nn.compact
    def __call__(
        self,
        query,
        key_padding_mask: Optional[jnp.ndarray] = None,
        attn_bias: Optional[jnp.ndarray] = None,
        return_attn: bool = False,
        deterministic: bool = True,
        causal: bool = False,
        decode: bool = False,
        positions: Optional[jnp.ndarray] = None,
        paged=None,
        segment_ids: Optional[jnp.ndarray] = None,
    ):
        """``decode=True`` enables KV-cache incremental decoding (beyond
        the reference, which is a trainer only): the first call (flax
        init, or the prompt prefill at full length) sizes the cache; each
        subsequent ``apply(..., mutable=["cache"])`` call appends this
        step's k/v at the running index and attends the new queries over
        the whole cache with bottom-right causal masking.  ``positions``
        [T] are the global positions of the current tokens (drives RoPE;
        defaults to arange).  A 2-D ``positions`` [B, T] makes the cache
        RAGGED: each row's tokens write at (and attend up to) their own
        per-sequence positions, with -1 marking inactive (padded) rows —
        the right-padded-prompt prefill path.

        ``paged`` (a :class:`unicore_tpu.serve.attention.PagedMeta`, with
        ``decode=True``) switches from the per-call dense cache to the
        serve tier's shared paged KV pool: k/v write into pool pages at
        ``paged.slot_mapping`` and attention gathers each sequence's
        pages through its page table (collection ``"pagedkv"``).  The
        tokens of such a call are the serve step's flat list, ``[1, N]``
        with ``positions`` [1, N], or its ``[B, T]`` rectangle: only
        the attention core sorts them into batch rows."""
        bsz, tgt_len, embed_dim = query.shape
        assert embed_dim == self.embed_dim
        head_dim = self.embed_dim // self.num_heads
        assert head_dim * self.num_heads == self.embed_dim
        scaling = (head_dim * self.scaling_factor) ** -0.5

        # fused QKV as a DenseGeneral with kernel [D, 3, H, Dh] (same math
        # and init as a [D, 3D] Dense + reshape — the features axis orders
        # q-block, k-block, v-block exactly like the reference's in_proj):
        # keeping (3, H, Dh) as real kernel dims lets tensor parallelism
        # shard the HEAD dim declaratively and propagate through the
        # activation with no resharding collective
        qkv = nn.DenseGeneral(
            features=(3, self.num_heads, head_dim),
            axis=-1,
            use_bias=self.bias,
            kernel_init=bert_init,
            name="in_proj",
        )(query)
        qkv = tp_constraint(qkv, _BATCH_AXES, None, None, "tensor", None)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]

        if self.rotary:
            from .rotary import apply_rotary_qk

            q, k = apply_rotary_qk(q, k, base=self.rotary_base,
                                   positions=positions)

        if decode:
            # the cache path supports exactly the generate() contract;
            # silently ignoring an operand the caller computed is worse
            # than refusing it
            if attn_bias is not None or key_padding_mask is not None:
                raise NotImplementedError(
                    "decode=True does not support attn_bias/"
                    "key_padding_mask (decoding assumes unpadded prompts; "
                    "generate() enforces this)"
                )
            if return_attn:
                raise NotImplementedError("decode=True with return_attn")
            if segment_ids is not None:
                raise NotImplementedError(
                    "decode=True with segment_ids (sequence packing is a "
                    "training-path feature; decode rows are one sequence "
                    "each by construction)"
                )
            if positions is None and self.rotary and not self.is_initializing():
                raise ValueError(
                    "decode=True with rotary requires positions= (the "
                    "global positions of the current tokens) — without "
                    "them every step would rotate at position 0"
                )
            if paged is not None:
                if positions is None and not self.is_initializing():
                    raise ValueError(
                        "paged decode requires positions= (the global "
                        "positions of the current tokens; they drive both "
                        "the causal mask and the page-slot bookkeeping)"
                    )
                o = self._paged_attend(q, k, v, scaling, paged, positions)
            else:
                o = self._decode_attend(q, k, v, scaling, positions)
            o = o.reshape(bsz, tgt_len, embed_dim)
            return nn.Dense(
                self.embed_dim, use_bias=self.bias, kernel_init=bert_init,
                name="out_proj",
            )(o)

        bias = _canon_bias(attn_bias, bsz, self.num_heads)
        out = _attend(
            q, k, v, scaling, self.dropout, key_padding_mask, bias,
            deterministic, self.make_rng, return_attn=return_attn,
            causal=causal, segment_ids=segment_ids,
        )
        if return_attn:
            o, attn_weights, probs = out
        else:
            o = out
        o = tp_constraint(o, _BATCH_AXES, None, "tensor", None)
        o = o.reshape(bsz, tgt_len, embed_dim)
        o = nn.Dense(
            self.embed_dim, use_bias=self.bias, kernel_init=bert_init,
            name="out_proj",
        )(o)
        # row-parallel output: GSPMD inserts the one allreduce here
        o = tp_constraint(o, _BATCH_AXES, None, None)
        if return_attn:
            return o, attn_weights, probs
        return o

    def _decode_attend(self, q, k, v, scaling, positions=None):
        """KV-cache attention (cache collection: cached_key/cached_value/
        cache_index, the flax decoding idiom).  The flax-init pass sizes
        the cache from the prototype input's length and returns plain
        causal attention; subsequent mutable-"cache" calls append k/v at
        the running index and attend over the whole cache.

        The cache carries ONE slot beyond the prototype capacity: a
        trash slot that ragged writes (2-D ``positions``, -1 = inactive
        row) park pad tokens' k/v in.  It is unattendable by
        construction — every mask compares columns against a position
        strictly below it."""
        import jax

        is_initialized = self.has_variable("cache", "cached_key")
        cap = k.shape[:1] + (k.shape[1] + 1,) + k.shape[2:]
        cached_key = self.variable("cache", "cached_key", jnp.zeros,
                                   cap, k.dtype)
        cached_value = self.variable("cache", "cached_value", jnp.zeros,
                                     cap, v.dtype)
        cache_index = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
        )
        if not is_initialized:
            from unicore_tpu.utils import causal_iota_mask

            s = jnp.einsum("bqhd,bkhd->bhqk", q * scaling, k)
            s = s + causal_iota_mask(q.shape[1], k.shape[1])[None, None]
            p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
            return jnp.einsum("bhqk,bkhd->bqhd", p, v)
        idx = cache_index.value
        if positions is not None and positions.ndim == 2:
            # ragged path: row r of sequence b writes at its OWN global
            # position (slot == position), inactive rows (-1) at the
            # trash slot; each row attends keys <= its position
            bsz, tgt_len = positions.shape
            trash = cached_key.value.shape[1] - 1
            slots = jnp.where(positions >= 0, positions, trash)
            flat = (jnp.arange(bsz, dtype=jnp.int32)[:, None]
                    * (trash + 1) + slots).reshape(-1)

            def scatter(cached, new):
                flat_pool = cached.reshape((-1,) + cached.shape[2:])
                flat_pool = flat_pool.at[flat].set(
                    new.astype(cached.dtype).reshape(
                        (-1,) + new.shape[2:])
                )
                return flat_pool.reshape(cached.shape)

            k_all = scatter(cached_key.value, k)
            v_all = scatter(cached_value.value, v)
            cached_key.value = k_all
            cached_value.value = v_all
            cache_index.value = jnp.maximum(
                idx, jnp.max(positions) + 1
            ).astype(jnp.int32)
            cols = jnp.arange(k_all.shape[1], dtype=jnp.int32)
            mask = jnp.where(
                cols[None, None, None, :] > positions[:, None, :, None],
                -1e30, 0.0,
            )
            s = jnp.einsum("bqhd,bkhd->bhqk", q * scaling, k_all)
            s = s + mask
            p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
            return jnp.einsum("bhqk,bkhd->bqhd", p, v_all)
        k_all = jax.lax.dynamic_update_slice(
            cached_key.value, k.astype(cached_key.value.dtype),
            (0, idx, 0, 0),
        )
        v_all = jax.lax.dynamic_update_slice(
            cached_value.value, v.astype(cached_value.value.dtype),
            (0, idx, 0, 0),
        )
        cached_key.value = k_all
        cached_value.value = v_all
        cache_index.value = idx + q.shape[1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q * scaling, k_all)
        s = s + _decode_mask(idx, q.shape[1], k_all.shape[1])[None, None]
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v_all)

    def _paged_attend(self, q, k, v, scaling, paged, positions):
        """Serve-tier attention over the shared paged KV pool: this
        step's k/v scatter into pool pages at ``paged.slot_mapping`` and
        each sequence attends the pages its table names, masked to its
        own positions (``unicore_tpu/serve/attention.py`` owns the math,
        the way from the step's token list to the kernel's batch rows and
        back, and the eager/Pallas dispatch).  Pool buffers live in collection
        ``"pagedkv"`` — one [num_slots, H*Dh] pair per layer, allocated
        once at engine init and donated through every jitted step."""
        is_initialized = self.has_variable("pagedkv", "k_pages")
        nslots = None if is_initialized else int(paged.num_slots)
        k_pages = self.variable("pagedkv", "k_pages", jnp.zeros,
                                (nslots, self.embed_dim), k.dtype)
        v_pages = self.variable("pagedkv", "v_pages", jnp.zeros,
                                (nslots, self.embed_dim), v.dtype)
        if not is_initialized:
            import jax

            from unicore_tpu.utils import causal_iota_mask

            s = jnp.einsum("bqhd,bkhd->bhqk", q * scaling, k)
            s = s + causal_iota_mask(q.shape[1], k.shape[1])[None, None]
            p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
            return jnp.einsum("bhqk,bkhd->bqhd", p, v)
        from unicore_tpu.serve.attention import write_and_attend

        return write_and_attend(q, k, v, k_pages, v_pages, paged, positions,
                                scaling)


def _decode_mask(idx, tgt_len, cache_len):
    """Additive [tgt_len, cache_len] mask for incremental decoding: query
    row r (global position idx + r) sees keys <= idx + r; unwritten cache
    slots (>= idx + tgt_len) are masked by the same comparison."""
    import jax

    rows = jax.lax.broadcasted_iota(jnp.int32, (tgt_len, cache_len), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (tgt_len, cache_len), 1)
    return jnp.where(cols > rows + idx, -1e30, 0.0)


class CrossMultiheadAttention(nn.Module):
    embed_dim: int
    num_heads: int
    dropout: float = 0.1
    bias: bool = True
    scaling_factor: float = 1.0

    @nn.compact
    def __call__(
        self,
        query,
        key,
        value,
        key_padding_mask: Optional[jnp.ndarray] = None,
        attn_bias: Optional[jnp.ndarray] = None,
        deterministic: bool = True,
    ):
        bsz, tgt_len, embed_dim = query.shape
        assert embed_dim == self.embed_dim
        head_dim = self.embed_dim // self.num_heads
        scaling = (head_dim * self.scaling_factor) ** -0.5

        def proj(x, name):
            y = nn.Dense(
                self.embed_dim, use_bias=self.bias, kernel_init=bert_init, name=name
            )(x)
            y = y.reshape(y.shape[0], y.shape[1], self.num_heads, head_dim)
            return tp_constraint(y, _BATCH_AXES, None, "tensor", None)

        q = proj(query, "q_proj")
        k = proj(key, "k_proj")
        v = proj(value, "v_proj")

        bias = _canon_bias(attn_bias, bsz, self.num_heads)
        o = _attend(q, k, v, scaling, self.dropout, key_padding_mask, bias,
                    deterministic, self.make_rng)
        o = tp_constraint(o, _BATCH_AXES, None, "tensor", None)
        o = o.reshape(bsz, tgt_len, embed_dim)
        o = nn.Dense(
            self.embed_dim, use_bias=self.bias, kernel_init=bert_init, name="out_proj"
        )(o)
        return tp_constraint(o, _BATCH_AXES, None, None)
