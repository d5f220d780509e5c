"""Multi-head latent attention: the mixer's two forms and the paged step
behind the absorbed one (``serve/attention.py``
``write_latent_and_attend``), at a small size on the CPU.

The oracle of the op-level tests is NumPy, written per head from the
equations (keys and values expanded from the latents, one softmax a
head), and shares nothing with the op; the mixer's per-head form is
compared with ``benchmarks/reference/pangu_moe_lm.py`` in
``tests/test_serve_pangu.py``.

Tolerance: both sides are float32 on the CPU and differ in the ORDER of
the sums (absorbed: scores over ``latent + rope`` numbers against
``nope + rope``; an online softmax over page blocks against one pass), a
few ulps of values of order 1: ``TOL = 2e-5``.  A cell that read a wrong
page, a masked cell that leaked, or a rope key left out moves an output by
1e-2 and more.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unicore_tpu.modules import LatentAttentionMixer, LatentSpec
from unicore_tpu.ops import backend
from unicore_tpu.ops.pallas import paged_attention as pl_pa
from unicore_tpu.serve import attention as serve_attention
from unicore_tpu.serve.attention import PagedMeta

TOL = 2e-5
H, L, ROPE, LANES = 4, 24, 8, 128      # 24 + 8 numbers a token, one slab
PAGE = 8


class Var:
    """What ``write_latent_and_attend`` needs of a flax variable."""

    def __init__(self, value):
        self.value = value


def oracle(q, cache, positions, scale):
    """``q`` [T, H, W] absorbed queries at ``positions`` [T] of ONE
    sequence whose entries are ``cache`` [S, W]: per head, softmax over
    the entries up to the query's position of ``q . entry``, then the
    weighted sum of the entries' first ``L`` numbers."""
    out = np.zeros(q.shape[:2] + (L,), np.float64)
    for t, pos in enumerate(positions):
        if pos < 0:
            continue
        ctx = cache[:pos + 1].astype(np.float64)
        for h in range(q.shape[1]):
            s = ctx @ q[t, h].astype(np.float64) * scale
            p = np.exp(s - s.max())
            out[t, h] = (p / p.sum()) @ ctx[:, :L]
    return out


def _entries(rng, n):
    e = np.zeros((n, LANES), np.float32)
    e[:, :L + ROPE] = rng.normal(size=(n, L + ROPE))
    return e


def _queries(rng, *lead):
    q = np.zeros(lead + (H, LANES), np.float32)
    q[..., :L + ROPE] = rng.normal(size=lead + (H, L + ROPE))
    return q


def _pool_with(history, table, slots=96):
    """A pool holding ``history`` [S, W] at the pages ``table`` names."""
    pool = np.zeros((slots, LANES), np.float32)
    for pos, entry in enumerate(history):
        pool[table[pos // PAGE] * PAGE + pos % PAGE] = entry
    return pool


@pytest.mark.parametrize("cells", [512, 16, 8, 4])
@pytest.mark.parametrize("chunk", [1, 6, 8])
def test_a_row_of_cells_against_the_per_head_oracle(chunk, cells,
                                                    monkeypatch):
    """One row: ``chunk`` new tokens behind 13 cached ones.  ``cells``
    cuts the chunk's ``chunk x 4`` query cells into tiles of 128, 4, 2 and
    1 tokens (a tile is a kernel row of its own, with its own length):
    the result does not depend on the cut."""
    monkeypatch.setattr(serve_attention, "LATENT_QUERY_CELLS", cells)
    rng = np.random.default_rng(chunk)
    old = 13
    table = [3, 5, 7]
    history = _entries(rng, old + chunk)
    q = _queries(rng, 1, chunk)
    pos = np.arange(old, old + chunk, dtype=np.int32)[None]
    pages = Var(jnp.asarray(_pool_with(history[:old], table)))
    meta = PagedMeta(
        page_table=jnp.asarray([table], jnp.int32),
        slot_mapping=jnp.asarray(
            [table[p // PAGE] * PAGE + p % PAGE for p in pos[0]], jnp.int32),
        lengths=jnp.asarray([old + chunk], jnp.int32), page_size=PAGE)
    got = serve_attention.write_latent_and_attend(
        jnp.asarray(q), jnp.asarray(history[old:])[None], pages, meta,
        jnp.asarray(pos), 0.3, value_lanes=L)
    assert got.shape == (1, chunk, H, L)
    want = oracle(q[0], history, pos[0], 0.3)
    assert np.abs(got[0] - want).max() < TOL
    # the step's entries are in the pool, at their slots
    for p in pos[0]:
        assert np.array_equal(
            pages.value[table[p // PAGE] * PAGE + p % PAGE], history[p])


def _flat_step(rng, rows, width):
    """A mixed step's FLAT list: ``rows`` = [(cached, new tokens)], each
    sequence on pages of its own; returns what the op gave each token and
    what the oracle gives it."""
    B = len(rows) + 1                          # and one empty row
    N = sum(m for _, m in rows) + 3            # and three empty cells
    table = np.zeros((B, 4), np.int32)
    rect = np.full((B, width), N, np.int32)
    cell = np.zeros(N, np.int32)
    pos = np.full(N, -1, np.int32)
    slots = np.zeros(N, np.int32)
    pool = np.zeros((B * 4 * PAGE + PAGE, LANES), np.float32)
    q = _queries(rng, 1, N)
    entries = _entries(rng, N)[None]
    histories, at = [], 0
    for b, (old, m) in enumerate(rows):
        table[b] = 1 + 4 * b + np.arange(4)
        hist = _entries(rng, old + m)
        hist[old:] = entries[0, at:at + m]
        for p in range(old):
            pool[table[b, p // PAGE] * PAGE + p % PAGE] = hist[p]
        histories.append((hist, at, old, m))
        mine = np.arange(at, at + m)
        pos[mine] = old + np.arange(m)
        slots[mine] = table[b, pos[mine] // PAGE] * PAGE + pos[mine] % PAGE
        rect[b, :m] = mine
        cell[mine] = b * width + np.arange(m)
        at += m
    lengths = np.asarray([o + m for o, m in rows] + [0], np.int32)
    pages = Var(jnp.asarray(pool))
    meta = PagedMeta(
        page_table=jnp.asarray(table), slot_mapping=jnp.asarray(slots),
        lengths=jnp.asarray(lengths), page_size=PAGE,
        rect_token=jnp.asarray(rect), token_cell=jnp.asarray(cell),
        rect_positions=jnp.take(jnp.asarray(pos), jnp.asarray(rect),
                                mode="fill", fill_value=-1))
    got = serve_attention.write_latent_and_attend(
        jnp.asarray(q), jnp.asarray(entries), pages, meta,
        jnp.asarray(pos)[None], 0.25, value_lanes=L)
    assert got.shape == (1, N, H, L)
    worst = 0.0
    for hist, at, old, m in histories:
        want = oracle(q[0, at:at + m], hist, old + np.arange(m), 0.25)
        worst = max(worst, float(np.abs(got[0, at:at + m] - want).max()))
    return worst


@pytest.mark.parametrize("cells", [512, 8])
def test_a_mixed_steps_flat_list(cells, monkeypatch):
    """Decode rows (one token at 17 and at 30 cached) beside a prompt's
    chunks (8 tokens from 0, 8 more behind 8 cached: two rows of one
    prompt would look the same), an empty row and empty cells of the
    list, tiled (``cells`` 8: two tokens a tile) and not."""
    monkeypatch.setattr(serve_attention, "LATENT_QUERY_CELLS", cells)
    worst = _flat_step(np.random.default_rng(3),
                       [(17, 1), (0, 8), (8, 8), (30, 1), (4, 5)], width=8)
    assert worst < TOL


@pytest.mark.parametrize("cells", [512, 8])
def test_the_ragged_kernel_takes_the_same_rows(cells, monkeypatch):
    """The Pallas kernel (interpreted here) over ONE K/V head of 128
    lanes, handed the one pool as keys and as values, against the
    oracle: what the chip runs, at the shapes a CPU can interpret."""
    monkeypatch.setattr(serve_attention, "LATENT_QUERY_CELLS", cells)
    with backend.kernel_backend("pallas"):
        worst = _flat_step(np.random.default_rng(4),
                           [(17, 1), (0, 8), (8, 8), (4, 5)], width=8)
    assert worst < TOL
    seen = backend.dispatch_report()["latent_attention_prefill"]
    assert "pallas" in seen.values()


def test_an_empty_tile_reads_no_page_and_a_tile_stops_at_its_own_edge(
        monkeypatch):
    """The lengths the kernel gets are the TILES': a tile's last position
    + 1, and 0 for a tile of empty cells (the kernel's loop over page
    blocks runs ``cdiv(length, block)`` times)."""
    seen = {}
    real = serve_attention.paged_attention_reference

    def spy(cells, kp, vp, table, positions, lengths, page_size, scale):
        seen.update(lengths=np.asarray(lengths), table=np.asarray(table),
                    positions=np.asarray(positions), shape=cells.shape)
        return real(cells, kp, vp, table, positions, lengths, page_size,
                    scale)

    monkeypatch.setattr(serve_attention, "paged_attention_reference", spy)
    monkeypatch.setattr(serve_attention, "LATENT_QUERY_CELLS", 8)  # 2 a tile
    rng = np.random.default_rng(5)
    pos = np.asarray([[20, 21, 22, -1, -1, -1], [-1] * 6], np.int32)
    meta = PagedMeta(
        page_table=jnp.asarray([[1, 2, 3], [0, 0, 0]], jnp.int32),
        slot_mapping=jnp.zeros(12, jnp.int32),
        lengths=jnp.asarray([23, 0], jnp.int32), page_size=PAGE)
    serve_attention.write_latent_and_attend(
        jnp.asarray(_queries(rng, 2, 6)),
        jnp.asarray(_entries(rng, 12)).reshape(2, 6, LANES),
        Var(jnp.zeros((32, LANES))), meta, jnp.asarray(pos), 1.0,
        value_lanes=L)
    assert seen["shape"] == (6, 2 * H, 1, LANES)     # 2 rows x 3 tiles
    assert seen["lengths"].tolist() == [22, 23, 0, 0, 0, 0]
    assert seen["table"].tolist() == [[1, 2, 3]] * 3 + [[0, 0, 0]] * 3
    # a token's H heads are H cells at its position
    assert seen["positions"][1].tolist() == [22] * H + [-1] * H


def test_a_decode_step_and_a_chunk_are_named_apart():
    """``dispatch_report()`` names the width-1 rectangle
    ``latent_attention_decode`` and every other ``latent_attention_prefill``,
    each with its rows, cells a row and lanes."""
    rng = np.random.default_rng(6)
    for T in (1, 4):
        meta = PagedMeta(
            page_table=jnp.ones((2, 2), jnp.int32),
            slot_mapping=jnp.arange(2 * T, dtype=jnp.int32),
            lengths=jnp.full((2,), T, jnp.int32), page_size=PAGE)
        serve_attention.write_latent_and_attend(
            jnp.asarray(_queries(rng, 2, T)),
            jnp.asarray(_entries(rng, 2 * T)).reshape(2, T, LANES),
            Var(jnp.zeros((32, LANES))), meta,
            jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (2, T)), 1.0,
            value_lanes=L)
    report = backend.dispatch_report()
    assert report["latent_attention_decode"][
        f"b2 cells{H} lanes{LANES} page{PAGE} float32"] == "reference"
    assert report["latent_attention_prefill"][
        f"b2 cells{4 * H} lanes{LANES} page{PAGE} float32"] == "reference"


@pytest.mark.parametrize("lanes,ok", [(640, True), (576, False), (128, True),
                                      (512 + 128, True)])
def test_the_kernels_shape_rule_knows_a_latent_page(lanes, ok):
    """One K/V head as wide as the page: whole 128-lane slabs compile
    (512 + 64 + 64 zero lanes), the bare 576 numbers do not; the page
    block stays inside the scratch budget at the published width."""
    assert pl_pa.supported(1, lanes, 64, 4) == ok
    if ok:
        pp = pl_pa.pick_pages_per_block(132, 64, lanes, num_heads=1,
                                        itemsize=4)
        assert pp == 4 and 2 * pp * 64 * lanes * 4 <= 8 << 20


# -- the mixer: the absorbed form against the per-head form ----------------

SPEC = LatentSpec(q_lora_rank=24, kv_lora_rank=L, qk_nope_head_dim=12,
                  qk_rope_head_dim=ROPE, v_head_dim=10)
D = 32


def _mixer_params(seed):
    mixer = LatentAttentionMixer(D, H, SPEC, eps=1e-5, rope_theta=1e4)
    x = jnp.zeros((1, 3, D))
    shapes = jax.eval_shape(mixer.init, jax.random.PRNGKey(0), x)["params"]
    rng = np.random.default_rng(seed)
    params = jax.tree_util.tree_map(
        lambda s: jnp.asarray(
            (1.0 if len(s.shape) == 1 else 0.4) * rng.normal(size=s.shape)
            + (1.0 if len(s.shape) == 1 else 0.0), jnp.float32), shapes)
    return mixer, params


def test_the_mixers_parameters_and_its_cache():
    mixer, params = _mixer_params(0)
    assert {k: jax.tree_util.tree_leaves(v)[0].shape
            for k, v in params.items()} == {
        "q_a_proj": (D, 24), "q_a_layernorm": (24,),
        "q_b_proj": (24, H * 20), "kv_a_proj_with_mqa": (D, L + ROPE),
        "kv_a_layernorm": (L,), "kv_b_proj": (L, H * 22),
        "o_proj": (H * 10, D)}
    meta = PagedMeta(page_table=jnp.zeros((1, 2), jnp.int32),
                     slot_mapping=jnp.zeros((3,), jnp.int32),
                     lengths=jnp.ones((1,), jnp.int32), page_size=PAGE,
                     num_slots=48)
    shapes = jax.eval_shape(
        lambda: mixer.init(jax.random.PRNGKey(0), jnp.zeros((1, 3, D)),
                           positions=jnp.zeros((1, 3), jnp.int32),
                           paged=meta))["pagedkv"]
    # ONE vector a token for all heads: 24 + 8 numbers in one lane slab
    assert {k: (v.shape, v.dtype) for k, v in shapes.items()} == {
        "latent_pages": ((48, LANES), jnp.float32)}


@pytest.mark.parametrize("chunk", [1, 5, 16])
@pytest.mark.parametrize("seed", [0, 1])
def test_the_absorbed_and_the_per_head_form_agree_on_one_cache(seed, chunk):
    """One sequence of 21 tokens: the per-head form in one full pass
    (no pages) against the absorbed form fed ``chunk`` tokens a step
    through ONE set of latent pages, each step reading what the steps
    before it wrote."""
    mixer, params = _mixer_params(seed)
    T = 21
    x = jnp.asarray(np.random.default_rng(seed + 10).normal(size=(1, T, D)),
                    jnp.float32)
    full = mixer.apply({"params": params}, x,
                       positions=jnp.arange(T, dtype=jnp.int32)[None])
    table = [2, 4, 1]
    pages = {"latent_pages": jnp.zeros((48, LANES), jnp.float32)}
    outs = []
    for start in range(0, T, chunk):
        pos = np.arange(start, min(T, start + chunk), dtype=np.int32)
        meta = PagedMeta(
            page_table=jnp.asarray([table], jnp.int32),
            slot_mapping=jnp.asarray(
                [table[p // PAGE] * PAGE + p % PAGE for p in pos], jnp.int32),
            lengths=jnp.asarray([pos[-1] + 1], jnp.int32), page_size=PAGE)
        out, mutated = mixer.apply(
            {"params": params, "pagedkv": pages}, x[:, pos],
            positions=jnp.asarray(pos)[None], paged=meta,
            mutable=["pagedkv"])
        pages = mutated["pagedkv"]
        outs.append(out)
    got = jnp.concatenate(outs, axis=1)
    # outputs of order 10 (weights of 0.4): a few ulps of that
    assert np.abs(got - full).max() < 2e-4 * float(np.abs(full).max())
    # what a token left in the cache: normed latent | rotated rope key |
    # zeros, and nothing per head
    used = np.asarray(pages["latent_pages"])[
        [table[p // PAGE] * PAGE + p % PAGE for p in range(T)]]
    assert np.abs(used[:, :L + ROPE]).min() > 0
    assert not used[:, L + ROPE:].any()
    # the latent AFTER its norm: over its gain it has a mean square of 1
    gain = np.asarray(params["kv_a_layernorm"]["weight"])
    assert np.allclose(((used[:, :L] / gain) ** 2).mean(-1), 1.0, atol=1e-3)


def test_the_cached_rope_key_is_rotated_at_its_tokens_position():
    """The same token at two positions leaves the same latent and a rope
    key rotated differently; scores then depend on the DISTANCE alone."""
    mixer, params = _mixer_params(2)
    x = jnp.asarray(np.random.default_rng(7).normal(size=(1, 1, D)),
                    jnp.float32)
    left = []
    for p in (3, 11):
        meta = PagedMeta(page_table=jnp.asarray([[1, 2]], jnp.int32),
                         slot_mapping=jnp.asarray([PAGE + p % PAGE]),
                         lengths=jnp.asarray([p + 1]), page_size=PAGE)
        _, mutated = mixer.apply(
            {"params": params,
             "pagedkv": {"latent_pages": jnp.zeros((48, LANES))}}, x,
            positions=jnp.asarray([[p]], jnp.int32), paged=meta,
            mutable=["pagedkv"])
        left.append(np.asarray(
            mutated["pagedkv"]["latent_pages"])[PAGE + p % PAGE])
    assert np.allclose(left[0][:L], left[1][:L], atol=1e-6)
    assert np.abs(left[0][L:L + ROPE] - left[1][L:L + ROPE]).max() > 1e-2
    assert np.allclose(np.linalg.norm(left[0][L:L + ROPE]),
                       np.linalg.norm(left[1][L:L + ROPE]), rtol=1e-5)


def test_the_absorbed_step_never_expands_the_context():
    """No value of the traced paged step has the context's length beside
    the heads' own key or value width: the context stays ``[slots,
    lanes]`` entries (the per-head form's ``[B, S, H, nope + v]`` would)."""
    mixer, params = _mixer_params(3)
    meta = lambda: PagedMeta(
        page_table=jnp.zeros((2, 6), jnp.int32),
        slot_mapping=jnp.zeros((2,), jnp.int32),
        lengths=jnp.ones((2,), jnp.int32), page_size=PAGE)
    text = str(jax.make_jaxpr(lambda x, pages: mixer.apply(
        {"params": params, "pagedkv": pages}, x,
        positions=jnp.zeros((2, 1), jnp.int32), paged=meta(),
        mutable=["pagedkv"]))(
        jnp.zeros((2, 1, D)), {"latent_pages": jnp.zeros((64, LANES))}))
    S = 6 * PAGE
    assert re.search(rf"f32\[2,{S},1,{LANES}\]", text)        # the entries
    assert not re.search(rf"f32\[2,{S},{H},\d+\]", text)      # never per head


# -- the kernel's float32 dots in three bfloat16 passes --------------------


def test_three_passes_multiply_float32_as_the_rest_of_the_step_does():
    """``_dot(..., three_pass=True)`` splits each float32 operand into a
    bfloat16 head and remainder and sums three products: 2^-16 of a
    product where one pass (both operands ROUNDED to bfloat16, which is
    what a TPU's default float32 matmul does, emulated here by rounding)
    leaves 2^-8."""
    rng = np.random.default_rng(0)
    a = rng.normal(size=(64, 640)).astype(np.float32)
    b = rng.normal(size=(48, 640)).astype(np.float32)
    exact = a.astype(np.float64) @ b.astype(np.float64).T
    three = np.asarray(pl_pa._dot(jnp.asarray(a), jnp.asarray(b),
                                  ((1,), (1,)), True))
    rounded = lambda x: jnp.asarray(x).astype(jnp.bfloat16).astype(
        jnp.float32)
    one = np.asarray(pl_pa._dot(rounded(a), rounded(b), ((1,), (1,)), False))
    scale = np.abs(exact).max()
    assert np.abs(three - exact).max() < 2e-5 * scale
    assert np.abs(one - exact).max() > 1e-3 * scale
    # and the latent step asks for them, float32 against float32 only
    import inspect

    assert "three_pass=True" in inspect.getsource(
        serve_attention.write_latent_and_attend)
    # per-head K/V pages take them only where a model's step asks (PR 43:
    # the window model's); nobody gets them by default
    for fn in (serve_attention.paged_attention,
               serve_attention.write_and_attend):
        assert inspect.signature(fn).parameters["three_pass"].default is False
