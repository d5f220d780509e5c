"""Sequence-parallel attention (ring / Ulysses) vs single-device full
attention, on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unicore_tpu.parallel import ring_self_attention, ulysses_attention


def full_attention(q, k, v, bias=None, causal=False):
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d ** -0.5)
    if bias is not None:
        s = s + bias
    if causal:
        t = q.shape[1]
        s = s + jnp.triu(jnp.full((t, t), -1e30), k=1)[None, None]
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.fixture
def mesh():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs the virtual 8-device mesh")
    return jax.sharding.Mesh(np.asarray(devs[:8]).reshape(8), ("seq",))


@pytest.fixture
def qkv(rng):
    B, T, H, D = 2, 64, 8, 16
    mk = lambda: jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_module_causal_under_seq_parallel(rng, mesh, qkv, impl):
    """The decoder path under an active seq mesh axis: causal=True must
    flow to ring/Ulysses natively (NOT as a merged -inf bias — an all--inf
    remote score block would NaN the ring's online softmax)."""
    from unicore_tpu import parallel
    from unicore_tpu.modules import SelfMultiheadAttention

    B, T, H, D = 2, 64, 8, 16
    x = jnp.asarray(rng.randn(B, T, H * D).astype(np.float32))
    attn = SelfMultiheadAttention(embed_dim=H * D, num_heads=H, dropout=0.0)
    params = attn.init(jax.random.PRNGKey(0), x)
    o_ref = attn.apply(params, x, causal=True)
    parallel.enable_sequence_parallel(mesh, impl=impl)
    try:
        o_sp = attn.apply(params, x, causal=True)
    finally:
        parallel.disable_sequence_parallel()
    assert np.isfinite(np.asarray(o_sp)).all()
    np.testing.assert_allclose(
        np.asarray(o_ref), np.asarray(o_sp), atol=2e-5
    )


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(rng, mesh, qkv, causal):
    q, k, v = qkv
    out = ring_self_attention(mesh, q, k, v, causal=causal)
    ref = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ring_attention_with_bias(rng, mesh, qkv):
    q, k, v = qkv
    T, H = q.shape[1], q.shape[2]
    bias = jnp.asarray(rng.randn(1, H, T, T).astype(np.float32))
    out = ring_self_attention(mesh, q, k, v, bias=bias)
    ref = full_attention(q, k, v, bias=bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ring_attention_grads(rng, mesh, qkv):
    q, k, v = qkv

    def loss_ring(q, k, v):
        return jnp.sum(ring_self_attention(mesh, q, k, v) ** 2)

    def loss_full(q, k, v):
        return jnp.sum(full_attention(q, k, v) ** 2)

    g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, err_msg=name
        )


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full(rng, mesh, qkv, causal):
    q, k, v = qkv
    from jax.sharding import PartitionSpec as P

    spec = P(None, "seq", None, None)
    wrapped = jax.shard_map(
        lambda q_, k_, v_: ulysses_attention(
            q_, k_, v_, axis_name="seq", causal=causal
        ),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    out = wrapped(q, k, v)
    ref = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ring_attention_key_padding_mask(rng, mesh, qkv):
    q, k, v = qkv
    B, T = q.shape[0], q.shape[1]
    pad = np.zeros((B, T), dtype=bool)
    pad[:, T - 10:] = True  # last 10 keys padded
    ref = full_attention(
        q, k, v,
        bias=jnp.where(jnp.asarray(pad)[:, None, None, :], -1e30, 0.0),
    )
    out = ring_self_attention(mesh, q, k, v, key_padding_mask=jnp.asarray(pad))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ulysses_key_padding_mask_headdim1_bias(rng, mesh, qkv):
    """Ulysses with a padding mask and NO per-head bias (the case that used
    to crash on the head-dim-1 slice)."""
    from unicore_tpu.parallel import ulysses_self_attention

    q, k, v = qkv
    B, T = q.shape[0], q.shape[1]
    pad = np.zeros((B, T), dtype=bool)
    pad[:, T - 6:] = True
    ref = full_attention(
        q, k, v,
        bias=jnp.where(jnp.asarray(pad)[:, None, None, :], -1e30, 0.0),
    )
    out = ulysses_self_attention(
        mesh, q, k, v, key_padding_mask=jnp.asarray(pad)
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


# ---------------------------------------------------------------------------
# attention dropout on the sequence-parallel paths (VERDICT r3 next-5):
# ring derives masks from global block identity, Ulysses decorrelates per
# head-shard device — the escape hatch is retired
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_seq_parallel_dropout_statistics(rng, mesh, qkv, impl):
    """With v = ones, dropout(softmax) rows sum to ~1 in expectation (the
    1/(1-p) rescale is exact in the mean); p=0 reproduces the
    deterministic path; the mask is deterministic per rng and changes
    with it."""
    from unicore_tpu.parallel import ring_self_attention, ulysses_self_attention

    q, k, v = qkv
    ones = jnp.ones_like(v)
    attend = ring_self_attention if impl == "ring" else ulysses_self_attention
    key = jax.random.PRNGKey(3)

    out0 = attend(mesh, q, k, ones, dropout_p=0.0, rng=key)
    ref = full_attention(q, k, ones)
    np.testing.assert_allclose(np.asarray(out0), np.asarray(ref), atol=1e-5)

    out1 = attend(mesh, q, k, ones, dropout_p=0.3, rng=key)
    out1b = attend(mesh, q, k, ones, dropout_p=0.3, rng=key)
    out2 = attend(mesh, q, k, ones, dropout_p=0.3, rng=jax.random.PRNGKey(4))
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out1b))
    assert not np.allclose(np.asarray(out1), np.asarray(out2))
    # expectation: every entry of out1 estimates 1 (row mass)
    m = float(np.mean(np.asarray(out1)))
    assert abs(m - 1.0) < 0.1, m
    # and it is a real mask (row masses vary)
    assert float(np.std(np.asarray(out1))) > 0.01


def test_ulysses_dropout_decorrelates_head_shards(rng, mesh):
    """All heads get IDENTICAL q/k/v; with per-device seed offsets the
    sampled masks must still differ across head-shard devices (without
    the offset, local head index 0 on every device would repeat the same
    mask for different global heads)."""
    from unicore_tpu.parallel import ulysses_self_attention

    B, T, H, D = 2, 64, 8, 16
    one_head = rng.randn(B, T, 1, D).astype(np.float32)
    mk = lambda: jnp.asarray(np.repeat(one_head, H, axis=2))
    q, k = mk(), mk()
    ones = jnp.ones((B, T, H, D), jnp.float32)
    out = ulysses_self_attention(
        mesh, q, k, ones, dropout_p=0.4, rng=jax.random.PRNGKey(5)
    )
    out = np.asarray(out)  # [B, T, H, D]
    for h in range(1, H):
        assert not np.allclose(out[:, :, 0], out[:, :, h]), (
            f"head {h} mask duplicates head 0's"
        )


def test_ring_dropout_grads_finite(rng, mesh, qkv):
    from unicore_tpu.parallel import ring_self_attention

    q, k, v = qkv

    def loss(q, k, v):
        return jnp.sum(
            ring_self_attention(
                mesh, q, k, v, dropout_p=0.2, rng=jax.random.PRNGKey(0)
            ) ** 2
        )

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a in g:
        assert np.isfinite(np.asarray(a)).all()


def test_module_seq_parallel_dropout_no_raise(rng, mesh, qkv):
    """attention_dropout > 0 under sequence parallelism now WORKS (the
    r2/r3 fail-fast + --seq-parallel-skip-attention-dropout hatch is
    retired)."""
    from unicore_tpu import parallel
    from unicore_tpu.modules import multihead_attention as mha

    q, k, v = qkv
    devs = jax.devices()
    mesh3 = jax.sharding.Mesh(
        np.asarray(devs[:8]).reshape(1, 1, 8), ("data", "fsdp", "seq")
    )
    parallel.enable_sequence_parallel(mesh3, "ring")
    try:
        out = mha._seq_parallel_attend(
            q, k, v, scaling=0.25, dropout=0.1,
            key_padding_mask=None, bias=None, rng=jax.random.PRNGKey(0),
        )
        assert out is not None and np.isfinite(np.asarray(out)).all()
    finally:
        parallel.disable_sequence_parallel()


def test_flash_per_shard_matches_unsharded(rng):
    """GSPMD cannot partition a Mosaic kernel, so under a multi-device
    mesh the flash kernel runs per batch shard through shard_map.  Same
    output, same gradients (the shared bias's summed over the shards),
    and — the seeds being per GLOBAL batch row — the same dropout mask
    as the unsharded call."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from unicore_tpu.modules.multihead_attention import _flash_per_shard
    from unicore_tpu.ops import backend

    b, t, h, d = 8, 128, 2, 16
    q, k, v = (jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
               for _ in range(3))
    bias = jnp.asarray(rng.randn(1, h, t, t), jnp.float32)
    pad = jnp.asarray(rng.rand(b, t) > 0.9).at[:, 0].set(False)
    w = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    key = jax.random.PRNGKey(3)

    def loss(q, k, v, bias):
        out = _flash_per_shard(
            q, k, v, bias, pad, key, causal=False, dropout_prob=0.2,
            is_training=True, scale=d ** -0.5)
        return jnp.sum(out * w), out

    step = jax.value_and_grad(loss, argnums=(0, 1, 2, 3), has_aux=True)
    with backend.kernel_backend("pallas"):
        (want_l, want_o), want_g = jax.jit(step)(q, k, v, bias)
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2, 1, 1),
                    ("data", "fsdp", "seq", "tensor"))
        backend.set_spmd_mesh(mesh)
        rows = NamedSharding(mesh, P(("data", "fsdp")))
        (got_l, got_o), got_g = jax.jit(step)(
            *(jax.device_put(x, rows) for x in (q, k, v)),
            jax.device_put(bias, NamedSharding(mesh, P())))
    assert not got_o.sharding.is_fully_replicated
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(got_l), float(want_l), rtol=1e-5)
    for a, b_ in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4)
