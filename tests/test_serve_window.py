"""Sliding-window and global attention layers in one model, served over
two kinds of page (``laguna_lm``; ``serve/kv_pool.py``): the model's full
pass and the engine's prefill-then-decode against the plain reference
(``benchmarks/reference/laguna_lm.py``) on seeded weights at a toy size,
YaRN's table against a closed form, the four shares of the experts against
the uncut layer, the pool's two kinds under random traces, the ragged
kernel's first blocks under a window, and THE GUARD: with no window, ``write_and_attend`` and the kernel trace
the programs they traced before windows, equation for equation."""

import json
import math
import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import weights
from benchmarks.reference import laguna_lm as reference
from examples.lm.laguna import SLIDING, LagunaLMModel
from unicore_tpu.modules import RotarySpec
from unicore_tpu.modules.pattern_decoder import FULL
from unicore_tpu.modules.rotary import yarn_inv_freq
from unicore_tpu.serve import ServeEngine
from unicore_tpu.serve.kv_pool import PagedKVPool, PoolExhausted
from unicore_tpu.serve.scheduler import Request

from step_program_digests import step_digests

V, WINDOW, PAGE = 96, 8, 4
SCALES = {"kernel": 6, "router": 6, "w1": 6, "w3": 6, "w2": 6}


def toy(**kw):
    """5 layers (global, sliding x 3, global), window 8, heads 6 / 8 over
    2 K/V heads of 16, 16 experts top 2 with a shared one, YaRN at a toy
    factor on half of each global head."""
    base = dict(
        vocab_size=V, padding_idx=1,
        layer_types=(FULL, SLIDING, SLIDING, SLIDING, FULL),
        global_heads=6, sliding_heads=8, decoder_kv_heads=2, head_dim=16,
        decoder_embed_dim=64, decoder_ffn_embed_dim=96,
        sliding_window=WINDOW, num_experts=16, num_experts_per_tok=2,
        moe_ffn_embed_dim=32, global_rotary_lanes=8, yarn_factor=4.0,
        yarn_original_positions=32, yarn_beta_fast=8.0, yarn_beta_slow=1.0,
        yarn_attention_factor=0.1 * math.log(4.0) + 1.0, max_seq_len=256)
    base.update(kw)
    return LagunaLMModel(**base)


def seeded(model, seed=0):
    abstract = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32)
    )["params"]
    return weights.make(abstract, seed, scales=SCALES)


def rope_parameters(m):
    """The config's ``rope_parameters`` of a toy model, as the reference
    takes them."""
    return {
        FULL: {"rope_theta": m.global_rope_theta, "rope_type": "yarn",
               "factor": m.yarn_factor,
               "original_max_position_embeddings": m.yarn_original_positions,
               "beta_slow": m.yarn_beta_slow, "beta_fast": m.yarn_beta_fast,
               "attention_factor": m.yarn_attention_factor,
               "partial_rotary_factor": m.global_rotary_lanes / m.head_dim},
        SLIDING: {"rope_type": "default", "rope_theta": m.sliding_rope_theta,
                  "partial_rotary_factor": 1}}


PADDED = 112  # every compared sequence, so one program serves them all


def reference_logits(m, params, tokens):
    """The reference's logits of ``tokens``, computed over the sequence
    padded at its end to ``PADDED`` (a causal pass: nothing before the
    padding changes)."""
    heads = tuple(m.sliding_heads if k == SLIDING else m.global_heads
                  for k in m.layer_types)
    if m not in _REFERENCE:
        _REFERENCE[m] = jax.jit(lambda p, t: reference.forward(
            p, t, layer_types=m.layer_types, heads_per_layer=heads,
            kv_heads=m.decoder_kv_heads, head_dim=m.head_dim,
            window=m.sliding_window, rope=rope_parameters(m),
            top_k=m.num_experts_per_tok, eps=m.rms_norm_eps,
            scale=m.routed_scaling_factor))
    padded = np.full((PADDED,), 4, np.int32)
    padded[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        return _REFERENCE[m](weights.as_dict(params),
                             jnp.asarray(padded))[:len(tokens)]


_REFERENCE = {}


@pytest.fixture(scope="module")
def lm():
    model = toy()
    return model, seeded(model)


# -- the model against the reference --------------------------------------

# float32 against float32 at ``highest``, two implementations of the same
# sums in another order: a logit of order 4 moves by a few 1e-6
FULL_PASS_TOL = 5e-5


def test_full_pass_matches_the_reference(lm, rng):
    model, params = lm
    tokens = rng.randint(4, V, size=(40,))
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, jnp.asarray(tokens)[None])[0]
    want = reference_logits(model, params, tokens)
    assert float(jnp.abs(want).max()) > 1.0  # logits that say something
    assert float(jnp.abs(got - want).max()) < FULL_PASS_TOL


def _served_gap(model, params, result):
    """The widest gap by which a served token's logit lies below the
    reference's best at its position (0: every token is the reference's
    first choice)."""
    seq = result.prompt + result.tokens
    rows = reference_logits(model, params, np.asarray(seq))[
        len(result.prompt) - 1:len(seq) - 1]
    got = jnp.take_along_axis(
        rows, jnp.asarray(result.tokens)[:, None], axis=-1)[:, 0]
    return float(jnp.max(jnp.max(rows, axis=-1) - got))


# contexts of under one window to twelve; prompts that end on and beside
# page, chunk and window edges (page 4, chunk 16, window 8)
PROMPTS = (1, 5, 8, 9, 16, 23, 40, 70, 96)


@pytest.mark.parametrize("how", ["plain", "chaos", "in_flight"])
def test_prefill_then_decode_matches_the_reference(lm, rng, how):
    """Chunked prefill then decode through the two kinds of page serve
    the reference's own greedy tokens: undisturbed (rows free, so a long
    prompt takes several rows of one step), under chaos preemption and
    re-prefill, and with the batch full so that every step is launched
    behind one in flight (a window page released at one step's boundary is
    written by another row in the next)."""
    model, params = lm
    kw = {"plain": dict(max_batch=4),
          "chaos": dict(max_batch=4, chaos_rate=0.3,
                        chaos_rng=random.Random(7)),
          "in_flight": dict(max_batch=3)}[how]
    engine = ServeEngine(model, params, num_pages=90, page_size=PAGE,
                         prefill_chunk=16, **kw)
    assert engine.window == WINDOW and engine.prefix_cache_refused
    lens = PROMPTS if how != "in_flight" else (40, 23, 70)
    reqs = [Request(prompt=rng.randint(4, V, size=(n,)).tolist(),
                    max_new_tokens=12, request_id=f"r{i}")
            for i, n in enumerate(lens)]
    seqs = engine.submit(reqs)
    bound = engine.pool.window_row_pages(engine.prefill_chunk)
    while engine.has_work():
        engine.serve_step()
        engine.pool.check_invariants()
    results = [engine._result_of(s) for s in seqs]
    assert all(r.finish_reason == "length" for r in results)
    assert max(_served_gap(model, params, r) for r in results) < 1e-4
    stats = engine.pool.window_stats
    assert stats["released"] > 0 and stats["row_pages_peak"] <= bound
    assert engine.pool.is_idle()
    if how == "chaos":
        assert sum(r.evictions for r in results) >= 1
    if how == "in_flight":
        assert engine.stats["steps_run_ahead"] > 0
    # the step log's residency: two kinds held less than one table would
    rows = engine.step_log.rows()
    busy = rows[rows["resident_kv_bytes_one_table"] > 0]
    held, one = (busy["resident_kv_bytes"],
                 busy["resident_kv_bytes_one_table"])
    assert len(busy) and (held <= one).all() and (held < one).any()


def test_the_engine_counts_two_kinds_of_bytes(lm):
    model, params = lm
    engine = ServeEngine(model, params, num_pages=40, page_size=PAGE,
                         max_batch=4, prefill_chunk=16)
    # 2 global layers x (keys + values) x 2 K/V heads x 16 x 4 bytes
    assert engine.stats["cache_bytes_per_token"] == 2 * 2 * 2 * 16 * 4
    # a row of 16 queries sees 7 keys before them: 23 positions anywhere
    # in pages of 4 are at most 7 pages, in 3 sliding layers
    assert engine.window_table_width == 7
    assert engine.stats["cache_bytes_per_row_window"] == 7 * 4 * 3 * 256
    # every row's reserve, one step's tokens, the trash page
    assert engine.pool.window_reserve == 4
    assert engine.pool.num_window_pages == 1 + 4 * 4 + 64 // 4
    names = [n for n, _ in engine._step_operands(16)]
    assert names[-3:] == ["window_page_table", "window_slot_mapping",
                          "window_base"]
    assert engine.load_snapshot()["prefix_cache_refused"] is True


# -- rotary ----------------------------------------------------------------

def test_yarn_table_against_a_closed_form():
    """The published parameters: 64 rotated lanes at theta 500,000, factor
    64 over 4,096 positions, beta 64 / 1.  The corrections fall at
    ``c(r) = 64 ln(4096 / (2 pi r)) / (2 ln 500000)``: frequencies 0-5
    are kept, 21-31 divided by 64, a linear ramp between."""
    lanes, theta, factor, orig = 64, 500000.0, 64.0, 4096
    c = lambda r: lanes * math.log(orig / (2 * math.pi * r)) / (
        2 * math.log(theta))
    low, high = math.floor(c(64)), math.ceil(c(1))
    assert (low, high) == (5, 16)
    i = np.arange(lanes // 2)
    f = theta ** (-2.0 * i / lanes)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = f * (1 - ramp) + f / factor * ramp
    got = yarn_inv_freq(lanes, theta, factor, orig, 64.0, 1.0)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    np.testing.assert_allclose(got[:6], f[:6], rtol=1e-12)
    np.testing.assert_allclose(got[16:], f[16:] / 64, rtol=1e-12)
    assert abs((0.1 * math.log(64) + 1) - 1.4158883083359672) < 1e-12
    # and the reference's own table, written from the description
    rope = {"rope_theta": theta, "rope_type": "yarn", "factor": factor,
            "original_max_position_embeddings": orig, "beta_slow": 1,
            "beta_fast": 64}
    np.testing.assert_allclose(reference.inverse_frequencies(lanes, rope),
                               want, rtol=1e-12)


def test_partial_rotary_leaves_the_other_lanes():
    from unicore_tpu.modules.rotary import apply_rotary_spec

    x = jnp.asarray(np.random.RandomState(0).randn(1, 5, 2, 16), jnp.float32)
    spec = RotarySpec(theta=100.0, lanes=8, attention_factor=1.5)
    q, k = apply_rotary_spec(x, x, spec)
    np.testing.assert_array_equal(np.asarray(q[..., 8:]),
                                  np.asarray(x[..., 8:]))
    # position 0 is no rotation: the rotated lanes times the factor
    np.testing.assert_allclose(np.asarray(q[0, 0, :, :8]),
                               1.5 * np.asarray(x[0, 0, :, :8]), rtol=1e-6)
    assert not np.allclose(np.asarray(q[0, 3, :, :8]),
                           1.5 * np.asarray(x[0, 3, :, :8]))
    np.testing.assert_array_equal(np.asarray(q), np.asarray(k))


# -- the share of the experts ------------------------------------------------

def test_four_shares_of_the_experts_add_up_to_the_uncut_layer(rng):
    """One expert layer of the program at each of four shares (4 of 16
    experts), the shared expert counted once, against the reference's
    uncut layer."""
    from unicore_tpu.modules import ExpertFFN, ExpertSpec

    D, E, F = 64, 16, 32
    whole = ExpertFFN(D, ExpertSpec(E, 2, F, use_bias=False, scale=2.5,
                                    shared_experts=1))
    x = jnp.asarray(rng.randn(1, 24, D), jnp.float32)
    abstract = jax.eval_shape(whole.init, jax.random.PRNGKey(0), x)["params"]
    params = weights.as_dict(weights.make(abstract, 3, scales=SCALES))
    with jax.default_matmul_precision("highest"):
        want = reference.expert_ffn(x[0], params, top_k=2, scale=2.5,
                                    first_expert=0, precision="fp32")
        shared = reference.swiglu(
            x[0], *(params["shared_experts"][n]["kernel"]
                    for n in ("gate_proj", "up_proj", "down_proj")), "fp32")
        total = -3.0 * shared  # four shares bring the shared expert four times
        for first in range(0, E, 4):
            share = ExpertFFN(D, ExpertSpec(
                E, 2, F, use_bias=False, scale=2.5, first_expert=first,
                experts_held=4, shared_experts=1))
            mine = dict(params, **{n: params[n][first:first + 4]
                                   for n in ("w1", "w3", "w2")})
            total = total + share.apply({"params": mine}, x)[0]
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5)


# -- the pool's two kinds -----------------------------------------------------

def _window_pool(**kw):
    base = dict(num_pages=40, page_size=PAGE, prefix_cache=False,
                window=WINDOW, num_window_pages=1 + 3 * 4 + 4,
                window_slack=4)
    base.update(kw)
    return PagedKVPool(**base)


def test_window_pool_refuses_what_it_cannot_guarantee():
    pool = _window_pool()
    assert pool.window_reserve == 4 and pool.window_row_pages(16) == 7
    for sid in range(3):
        assert pool.can_alloc(20)
        pool.alloc(sid, 20)
    # global pages are free, the window kind has no fourth reserve
    assert pool.num_free_pages >= pool.pages_for(20)
    assert not pool.can_alloc(20)
    with pytest.raises(PoolExhausted):
        pool.alloc(3, 20)
    pool.check_invariants()
    with pytest.raises(ValueError):
        PagedKVPool(40, PAGE, prefix_cache=True, window=WINDOW,
                    num_window_pages=20)
    with pytest.raises(ValueError):
        _window_pool(num_window_pages=6)


def test_window_release_frees_exactly_what_no_query_can_see():
    pool = _window_pool()
    pool.alloc("a", 40)
    pool.window_extend("a", 16)
    assert len(pool._window_tables["a"]) == 4
    # the next query at 16 sees keys 9..16: page 2 (8-11) is the first
    assert pool.window_release("a", 16) == 2
    pool.window_extend("a", 17)
    pages, base = pool.window_view("a", 16, 17)
    assert base == 8 and len(pages) == 3
    # at 19 the window is 12..19: page 2 goes, not before
    assert pool.window_release("a", 18) == 0
    assert pool.window_release("a", 19) == 1
    assert pool.window_stats["released"] == 3
    pool.check_invariants()
    with pytest.raises(IndexError):
        pool.window_view("a", 30, 34)   # pages nobody extended to
    pool.free("a")
    assert pool.is_idle()


@pytest.mark.parametrize("seed", range(6))
def test_window_pool_properties_under_random_traces(seed):
    """Admission, chunked progress, decode, finish and preemption in a
    random order: a row never addresses more window pages than the bound,
    a released page is free or re-owned and never both, nothing leaks, and
    the counted capacity never runs out."""
    rnd = random.Random(seed)
    chunk, budget = 16, 16
    pool = _window_pool(num_pages=60)
    bound = pool.window_row_pages(chunk)
    live, next_sid = {}, 0   # sid -> [length, written]
    for _ in range(300):
        if rnd.random() < 0.3 and pool.can_alloc(1):
            n = rnd.choice((1, 3, 8, 9, 30, 57))
            if pool.can_alloc(n):
                pool.alloc(next_sid, n)
                live[next_sid] = [n, 0]
                next_sid += 1
        if live and rnd.random() < 0.15:
            sid = rnd.choice(sorted(live))
            pool.free(sid)          # finish, expiry, drain, preemption
            del live[sid]
        # one step: everybody releases, then the planned rows extend
        for sid, (_, written) in live.items():
            pool.window_release(sid, written)
        left = budget
        for sid in sorted(live, key=lambda s: rnd.random()):
            n, written = live[sid]
            if written >= n:                      # decode: one more token
                if n >= 100 or pool.num_free_pages == 0:
                    continue
                pool.extend(sid, 1)
                live[sid][0] = n = n + 1
            m = min(chunk, n - written, left)
            if m <= 0:
                continue
            left -= m
            pool.window_extend(sid, written + m)   # never PoolExhausted
            pages, base = pool.window_view(sid, written, written + m)
            assert len(pages) <= bound and base % PAGE == 0
            assert base <= max(written - WINDOW + 1, 0) or base == 0
            live[sid][1] = written + m
        pool.check_invariants()
        held = [p for t in pool._window_tables.values() for p in t]
        assert len(held) + len(pool._window_free) == pool.num_window_pages - 1
    for sid in list(live):
        pool.free(sid)
    pool.check_invariants()
    assert pool.is_idle()


def test_one_kind_pool_has_no_window_state():
    pool = PagedKVPool(16, PAGE)
    pool.alloc(0, 9)
    assert pool.window_reserve == 0 and pool.window_pages_in_use == 0
    assert pool.global_pages_in_use == 3
    pool.check_invariants()
    pool.free(0)
    assert pool.is_idle() and pool._window_free == []


# -- the kernel's first blocks (its window row patterns run under the
# TPU interpreter's race detector in tests/test_serve.py's
# test_ragged_kernel_row_patterns, beside the rows without a window) -------

def test_first_blocks_of_a_windowed_call():
    from unicore_tpu.ops.pallas.paged_attention import first_blocks

    positions = jnp.asarray([[40, 41, 42], [-1, -1, -1], [3, -1, -1],
                             [100, 101, -1]], jnp.int32)
    lengths = jnp.asarray([43, 0, 4, 102], jnp.int32)
    # window 16, blocks of 8: first columns 25, -, 0, 85
    got = first_blocks(positions, lengths, 16, 8)
    assert got.tolist() == [3, 0, 0, 10]
    # never past the row's last block, whatever the positions say
    assert first_blocks(positions, jnp.asarray([8, 0, 4, 16]), 16,
                        8).tolist() == [0, 0, 0, 1]


# -- THE GUARD: no window, the parent's programs ----------------------------

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "step_jaxprs_pr41.json")


@pytest.mark.parametrize("kernel", [False, True], ids=["eager", "kernel"])
def test_without_a_window_the_step_programs_are_the_parents(kernel):
    """The decode and the mixed step of the tiny opt / hybrid / lfm2 /
    pangu engines trace, equation for equation, what they traced at PR 41
    (``tests/fixtures/step_jaxprs_pr41.json``: digests of the parent's
    jaxprs, made by ``tests/step_program_digests.py`` on a checkout of commit
    7469f7f, names and source lines out).  A PR that changes these
    programs ON PURPOSE regenerates the fixture and says so:
    ``python tests/step_program_digests.py > tests/fixtures/...``."""
    with open(FIXTURE) as f:
        want = json.load(f)["kernel" if kernel else "eager"]
    got = step_digests(kernel)
    assert sorted(got) == sorted(want)
    moved = {k: (got[k], want[k]) for k in got if got[k] != want[k]}
    assert not moved, moved


def test_without_a_window_the_kernel_is_called_as_it_was(monkeypatch, rng):
    """``write_and_attend`` reaches the kernel's ``_call`` with the static
    arguments and operand shapes it had: no ``window`` keyword (the
    kernel-mode digests above hold the call itself to the parent's)."""
    from unicore_tpu.ops import backend
    from unicore_tpu.ops.pallas import paged_attention as pa
    from unicore_tpu.serve import attention

    seen = []
    real = pa._call

    def spy(*args, **kw):
        seen.append(([a.shape for a in args], dict(kw)))
        return real(*args, **kw)

    monkeypatch.setattr(pa, "_call", spy)
    q = jnp.asarray(rng.randn(2, 3, 4, 16), jnp.float32)
    pool = jnp.asarray(rng.randn(40 * 4, 4 * 16), jnp.float32)
    table = jnp.asarray(rng.randint(1, 40, size=(2, 6)), jnp.int32)
    positions = jnp.asarray([[5, 6, 7], [0, -1, -1]], jnp.int32)
    lengths = jnp.asarray([8, 1], jnp.int32)
    with backend.kernel_backend("pallas"):
        plain = attention.paged_attention(q, pool, pool, table, positions,
                                          lengths, 4, 0.25)
        windowed = attention.paged_attention(q, pool, pool, table, positions,
                                             lengths, 4, 0.25, window=3)
    assert "window" not in seen[0][1] and seen[1][1]["window"] == 3
    assert set(seen[0][1]) == {"page_size", "pages_per_block", "scale",
                               "heads", "head_dim", "interpret",
                               "three_pass"}
    assert seen[0][0] == seen[1][0]
    want = attention.paged_attention_reference(
        q, pool, pool, table, positions, lengths, 4, 0.25, window=3)
    np.testing.assert_allclose(np.asarray(windowed[0]), np.asarray(want[0]),
                               atol=2e-5)
    assert not np.allclose(np.asarray(plain[0]), np.asarray(windowed[0]))


