"""Sequence-packed training (ISSUE 17 tentpole B).

Tiers:

- pure collator units: first-fit determinism, every-sample-in-exactly-
  one-bin coverage, capacity/segment caps, row metadata (contiguous
  1-based segments, per-segment position reset, pad fill);
- segment-causal mask units on ``_segment_bias`` + ``_attend``: no
  cross-segment attention, pad keys unattendable;
- model-level parity: packed rows produce the padded run's per-token
  logits to a few ulps (masked scores take the -1e30 fill whose softmax
  terms underflow to exact 0.0; what remains is XLA:CPU ordering a
  reduction by the operand's shape), loss/grads agree to
  reduction-order tolerance;
- rel_pos refusal: the global-offset bias cannot reset per segment;
- trainer integration: checkpoint save -> resume on packed batches is
  bit-exact vs the uninterrupted run.
"""

from argparse import Namespace

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unicore_tpu import metrics
from unicore_tpu.data.packing import PackedTokenDataset, pack_lengths
from unicore_tpu.modules.multihead_attention import (
    SelfMultiheadAttention,
    _segment_bias,
)

VOCAB, PAD, T = 37, 0, 32


# ---------------------------------------------------------------------
# collator units
# ---------------------------------------------------------------------

def test_pack_lengths_coverage_and_determinism():
    rng = np.random.RandomState(0)
    lengths = rng.randint(1, 20, size=64).tolist()
    bins = pack_lengths(lengths, 32)
    # every sample in exactly one bin
    flat = sorted(i for b in bins for i in b)
    assert flat == list(range(64))
    # capacity respected
    for b in bins:
        assert sum(lengths[i] for i in b) <= 32
    # pure function: identical layout on recompute
    assert pack_lengths(lengths, 32) == bins
    # packing actually packs (fewer rows than samples)
    assert len(bins) < 64


def test_pack_lengths_overlong_and_segment_cap():
    bins = pack_lengths([50, 3, 3, 3], 16, max_segments=2)
    assert bins[0] == [0]            # overlong sample isolated
    for b in bins:
        assert len(b) <= 2
    assert sorted(i for b in bins for i in b) == [0, 1, 2, 3]


def test_packed_dataset_row_metadata():
    lengths = [5, 4, 7, 20]
    inputs = [np.arange(1, n + 1, dtype=np.int64) for n in lengths]
    targets = [np.arange(2, n + 2, dtype=np.int64) for n in lengths]
    ds = PackedTokenDataset(inputs, targets, lengths, 16, PAD)
    seen = 0
    for r in range(len(ds)):
        row = ds[r]
        seg, pos, src = row["segment_ids"], row["positions"], row["src_tokens"]
        # segments 1-based, contiguous, pad tail is 0/-1/PAD
        n_real = int((seg != 0).sum())
        assert (seg[:n_real] != 0).all() and (seg[n_real:] == 0).all()
        assert (pos[n_real:] == -1).all() and (src[n_real:] == PAD).all()
        for s in range(1, seg.max() + 1):
            span = np.where(seg == s)[0]
            assert (np.diff(span) == 1).all()          # contiguous
            np.testing.assert_array_equal(             # positions reset
                pos[span], np.arange(len(span))
            )
            seen += 1
    assert seen == len(lengths)
    # collater produces the static-shape nested batch
    batch = ds.collater([ds[i] for i in range(len(ds))])
    assert batch["net_input"]["src_tokens"].shape == (len(ds), 16)
    assert batch["target"].shape == (len(ds), 16)


# ---------------------------------------------------------------------
# segment-causal mask units
# ---------------------------------------------------------------------

def test_segment_bias_blocks_cross_segment_and_pad():
    seg = jnp.asarray([[1, 1, 2, 2, 2, 0]])
    b = np.asarray(_segment_bias(seg, 6))[0, 0]        # [T, T]
    for qi in range(6):
        for ki in range(6):
            same = (seg[0, qi] == seg[0, ki]) and seg[0, ki] != 0
            if same:
                assert b[qi, ki] == 0.0
            else:
                assert b[qi, ki] <= -1e29, (qi, ki)


def test_attention_no_cross_segment_leakage():
    """Perturbing segment 1's tokens must not move segment 2's outputs."""
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(1, 8, 16), jnp.float32)
    seg = jnp.asarray([[1, 1, 1, 2, 2, 2, 2, 0]])
    attn = SelfMultiheadAttention(16, 2, dropout=0.0)
    params = attn.init(jax.random.PRNGKey(0), x)
    out = attn.apply(params, x, causal=True, segment_ids=seg)
    x2 = x.at[0, 1].set(100.0)                         # poke segment 1
    out2 = attn.apply(params, x2, causal=True, segment_ids=seg)
    np.testing.assert_array_equal(
        np.asarray(out)[0, 3:7], np.asarray(out2)[0, 3:7]
    )
    assert not np.array_equal(np.asarray(out)[0, :3], np.asarray(out2)[0, :3])


def test_decode_rejects_segment_ids():
    x = jnp.zeros((1, 4, 16), jnp.float32)
    attn = SelfMultiheadAttention(16, 2, dropout=0.0)
    params = attn.init(jax.random.PRNGKey(0), x)
    with pytest.raises(NotImplementedError):
        attn.apply(params, x, decode=True,
                   segment_ids=jnp.ones((1, 4), jnp.int32),
                   mutable=["cache"])


# ---------------------------------------------------------------------
# model-level parity (packed == padded on the same logical samples)
# ---------------------------------------------------------------------

def _lm_model(rel_pos=False):
    # the shared module instance (same import path as test_decode /
    # test_serve) — a second instance would re-register the lm loss
    from examples.lm.model import TransformerLMModel

    return TransformerLMModel(
        vocab_size=VOCAB, padding_idx=PAD, decoder_layers=2,
        decoder_embed_dim=32, decoder_ffn_embed_dim=64,
        decoder_attention_heads=2, emb_dropout=0.0, dropout=0.0,
        attention_dropout=0.0, activation_dropout=0.0, max_seq_len=T,
        rel_pos=rel_pos, abs_pos=True,
    )


def _mixed_batches():
    """The same 3 logical samples, padded (one per row) and packed (one
    row, 10+7+12=29 <= 32)."""
    rng = np.random.RandomState(5)
    lens = [10, 7, 12]
    samples = [rng.randint(1, VOCAB, size=n).astype(np.int64) for n in lens]
    targets = [np.roll(s, -1) for s in samples]
    pad_src = np.full((3, T), PAD, np.int64)
    pad_tgt = np.full((3, T), PAD, np.int64)
    for i, (s, t) in enumerate(zip(samples, targets)):
        pad_src[i, : len(s)] = s
        pad_tgt[i, : len(t)] = t
    pk_src = np.full((1, T), PAD, np.int64)
    pk_tgt = np.full((1, T), PAD, np.int64)
    pk_seg = np.zeros((1, T), np.int32)
    pk_pos = np.full((1, T), -1, np.int32)
    off = 0
    for i, (s, t) in enumerate(zip(samples, targets), start=1):
        n = len(s)
        pk_src[0, off:off + n] = s
        pk_tgt[0, off:off + n] = t
        pk_seg[0, off:off + n] = i
        pk_pos[0, off:off + n] = np.arange(n)
        off += n
    return lens, (pad_src, pad_tgt), (pk_src, pk_tgt, pk_seg, pk_pos)


def test_packed_vs_padded_logits_match_to_ulps():
    """Per-token logits of a packed row equal the padded batch's to a few
    ulps.  They are the same arithmetic on the same numbers; they are not
    the same bits, because XLA:CPU orders a matmul's K-reduction by the
    shape of the whole operand: the very same rows times the very same
    weights differ in the last bit between a [3, T, D] and a [1, T, D]
    operand (checked with a bare ``x @ W``), and a packed row's keys sit
    at other column offsets than a padded row's.  Measured: 1-2 ulps
    (6e-8 at |logit| <= 0.33); the bound is 8 ulps of the largest
    logit."""
    lens, (pad_src, _), (pk_src, _, pk_seg, pk_pos) = _mixed_batches()
    model = _lm_model()
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(pad_src))["params"]
    lp = np.asarray(model.apply({"params": params}, jnp.asarray(pad_src),
                                deterministic=True))
    lk = np.asarray(model.apply({"params": params}, jnp.asarray(pk_src),
                                deterministic=True,
                                segment_ids=jnp.asarray(pk_seg),
                                positions=jnp.asarray(pk_pos)))
    atol = 8 * np.finfo(np.float32).eps * float(np.abs(lp).max())
    off = 0
    for i, n in enumerate(lens):
        np.testing.assert_allclose(lp[i, :n], lk[0, off:off + n],
                                   rtol=0, atol=atol)
        off += n


def test_packed_vs_padded_loss_and_grad_parity():
    """Total loss and grads agree to reduction-order tolerance (the sums
    traverse tokens in a different order; the per-token terms agree to
    a few ulps per the logits test above)."""
    lens, (pad_src, pad_tgt), (pk_src, pk_tgt, pk_seg, pk_pos) = \
        _mixed_batches()
    model = _lm_model()
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(pad_src))["params"]

    def loss_fn(p, src, tgt, **kw):
        logits = model.apply({"params": p}, jnp.asarray(src),
                             deterministic=True, **kw)
        lprobs = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        t = jnp.asarray(tgt)
        w = (t != PAD).astype(jnp.float32)
        safe = jnp.where(t != PAD, t, 0)
        nll = -jnp.take_along_axis(lprobs, safe[..., None], axis=-1)[..., 0]
        return jnp.sum(nll * w), jnp.sum(w)

    (l_pad, n_pad), g_pad = jax.value_and_grad(loss_fn, has_aux=True)(
        params, pad_src, pad_tgt)
    (l_pk, n_pk), g_pk = jax.value_and_grad(loss_fn, has_aux=True)(
        params, pk_src, pk_tgt, segment_ids=jnp.asarray(pk_seg),
        positions=jnp.asarray(pk_pos))
    assert float(n_pad) == float(n_pk) == sum(lens)
    np.testing.assert_allclose(float(l_pk), float(l_pad), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g_pad),
                    jax.tree_util.tree_leaves(g_pk)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)


def test_rel_pos_refuses_packing():
    _, (pad_src, _), (pk_src, _, pk_seg, pk_pos) = _mixed_batches()
    model = _lm_model(rel_pos=True)
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(pad_src))["params"]
    with pytest.raises(NotImplementedError):
        model.apply({"params": params}, jnp.asarray(pk_src),
                    segment_ids=jnp.asarray(pk_seg),
                    positions=jnp.asarray(pk_pos))


# ---------------------------------------------------------------------
# trainer integration: packed checkpoint resume
# ---------------------------------------------------------------------

def _packed_batch(rng, bsz=4):
    src = np.full((bsz, T), PAD, np.int64)
    tgt = np.full((bsz, T), PAD, np.int64)
    seg = np.zeros((bsz, T), np.int32)
    pos = np.full((bsz, T), -1, np.int32)
    for b in range(bsz):
        off = 0
        for s in range(1, 4):
            n = int(rng.randint(4, 10))
            if off + n > T:
                break
            toks = rng.randint(1, VOCAB, size=n).astype(np.int64)
            src[b, off:off + n] = toks
            tgt[b, off:off + n] = np.roll(toks, -1)
            seg[b, off:off + n] = s
            pos[b, off:off + n] = np.arange(n)
            off += n
    return {
        "net_input": {"src_tokens": src, "segment_ids": seg,
                      "positions": pos},
        "target": tgt,
    }


def _packed_trainer():
    from test_resilience import ToyLoss, ToyTask, make_args
    from unicore_tpu.models.unicore_model import BaseUnicoreModel
    from unicore_tpu.trainer import Trainer

    class PackedToyModel(BaseUnicoreModel):
        @nn.compact
        def __call__(self, src_tokens, deterministic=True, segment_ids=None,
                     positions=None, **kwargs):
            x = nn.Embed(VOCAB, 16, name="embed")(src_tokens)
            x = SelfMultiheadAttention(16, 2, dropout=0.0, name="attn")(
                x, causal=True, segment_ids=segment_ids,
                deterministic=deterministic,
            )
            return nn.Dense(VOCAB, name="out")(x)

    class PackedToyLoss(ToyLoss):
        def forward(self, model, params, sample, rng=None, is_training=True):
            logits = model.apply(
                {"params": params}, **sample["net_input"],
                deterministic=not is_training,
            )
            lprobs = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            t = sample["target"]
            w = (t != PAD).astype(jnp.float32)
            safe = jnp.where(t != PAD, t, 0)
            nll = -jnp.take_along_axis(
                lprobs, safe[..., None], axis=-1)[..., 0]
            loss = jnp.sum(nll * w)
            n = jnp.sum(w)
            return loss, n, {"loss": loss, "sample_size": n}

    args = make_args()
    task = ToyTask(args)
    return Trainer(args, task, PackedToyModel(), PackedToyLoss(task))


def test_packed_checkpoint_resume_bit_exact(tmp_path):
    """Save mid-run on packed batches, resume, continue: params bit-equal
    to the uninterrupted run (the packed operands — segment_ids,
    positions — introduce no resume-variant state)."""
    rng = np.random.RandomState(7)
    batches = [_packed_batch(rng) for _ in range(4)]
    path = str(tmp_path / "ckpt_packed.pt")

    metrics.reset()
    trainer = _packed_trainer()
    with metrics.aggregate("train"):
        for b in batches[:2]:
            trainer.train_step([b])
        trainer.flush_stats()
    trainer.save_checkpoint(path, {"train_iterator": {"epoch": 1}})
    with metrics.aggregate("train"):
        for b in batches[2:]:
            trainer.train_step([b])
        trainer.flush_stats()
    want = jax.device_get(trainer.state["params"])

    metrics.reset()
    fresh = _packed_trainer()
    fresh.load_checkpoint(path)
    with metrics.aggregate("train"):
        fresh.init_state(batches[0])
        for b in batches[2:]:
            fresh.train_step([b])
        fresh.flush_stats()
    got = jax.device_get(fresh.state["params"])
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
