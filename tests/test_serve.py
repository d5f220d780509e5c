"""Serve tier (unicore_tpu/serve): KV-pool invariants, paged-attention
parity (eager + Pallas-interpret), scheduler properties under forced
eviction, engine batched correctness, and seeded-sampling determinism.

The load-bearing property everywhere: for ANY admission/eviction trace,
every request's emitted tokens are IDENTICAL to decoding that request
alone via the plain full-forward path — continuous batching and paging
are pure capacity features, never accuracy features."""

import itertools
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from examples.lm.model import TransformerLMModel
from unicore_tpu.serve import PagedKVPool, PoolExhausted, Request
from unicore_tpu.serve.engine import ServeEngine

V, D, H, F, L = 29, 32, 4, 64, 2
PAD = 0


@pytest.fixture(scope="module")
def lm():
    model = TransformerLMModel(
        vocab_size=V, padding_idx=PAD, decoder_layers=L,
        decoder_embed_dim=D, decoder_ffn_embed_dim=F,
        decoder_attention_heads=H, max_seq_len=64,
        emb_dropout=0.0, dropout=0.0, attention_dropout=0.0,
        activation_dropout=0.0, rel_pos=False, abs_pos=False, rotary=True,
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def solo_greedy(model, params, prompt, n_new, eos=None):
    """The oracle: full-forward greedy decode of one request alone."""
    toks = jnp.asarray(prompt, jnp.int32)[None]
    out = []
    for _ in range(n_new):
        logits = model.apply({"params": params}, toks)
        nxt = int(np.asarray(jnp.argmax(logits[0, -1])))
        out.append(nxt)
        if eos is not None and nxt == eos:
            break
        toks = jnp.concatenate(
            [toks, jnp.asarray([[nxt]], jnp.int32)], axis=1
        )
    return out


# -- KV pool invariants ----------------------------------------------------


def test_pool_alloc_free_round_trip():
    pool = PagedKVPool(num_pages=8, page_size=4)
    assert pool.num_usable_pages == 7  # page 0 reserved (trash)
    a = pool.alloc("a", 9)   # 3 pages
    b = pool.alloc("b", 4)   # 1 page
    pool.check_invariants()
    assert len(a) == 3 and len(b) == 1
    assert 0 not in a + b
    assert not set(a) & set(b), "page aliased across sequences"
    assert pool.occupancy() == pytest.approx(4 / 7)
    pool.free("a")
    pool.check_invariants()
    assert pool.num_free_pages == 6
    c = pool.alloc("c", 24)  # 6 pages: reuses a's pages, still disjoint
    pool.check_invariants()
    assert not set(c) & set(b)
    pool.free("b")
    pool.free("c")
    pool.check_invariants()
    assert pool.num_free_pages == 7 and pool.occupancy() == 0.0


def test_pool_extend_slots_and_page_order():
    pool = PagedKVPool(num_pages=8, page_size=4)
    pool.alloc("s", 3)
    table = pool.page_table("s")
    assert pool.slot("s", 0) == table[0] * 4
    assert pool.slot("s", 2) == table[0] * 4 + 2
    pool.extend("s", 1)  # fills the page, no new alloc
    assert pool.page_table("s") == table
    pool.extend("s", 1)  # crosses the boundary
    t2 = pool.page_table("s")
    assert t2[:1] == table and len(t2) == 2
    assert pool.slot("s", 4) == t2[1] * 4
    with pytest.raises(IndexError):
        pool.slot("s", 8)  # beyond the allocated pages
    pool.check_invariants()


def test_pool_exhaustion_and_double_free():
    pool = PagedKVPool(num_pages=4, page_size=2)  # 3 usable
    pool.alloc("a", 4)
    with pytest.raises(PoolExhausted):
        pool.alloc("b", 5)  # needs 3, only 1 free
    pool.check_invariants()  # failed alloc must not leak
    pool.alloc("b", 2)
    with pytest.raises(PoolExhausted):
        pool.extend("b", 1)
    pool.free("b")
    with pytest.raises(KeyError):
        pool.free("b")
    with pytest.raises(ValueError):
        PagedKVPool(num_pages=1, page_size=4)  # no room for the trash page


# -- paged attention parity ------------------------------------------------


def _random_paged_case(rng, B=3, P=5, ps=4, heads=4, d=16):
    num_pages = B * P + 1
    # the pool folds heads into the minor dim: [num_slots, H*D]
    pool_k = jnp.asarray(rng.randn(num_pages * ps, heads * d), jnp.float32)
    pool_v = jnp.asarray(rng.randn(num_pages * ps, heads * d), jnp.float32)
    perm = rng.permutation(num_pages - 1)[: B * P] + 1
    table = jnp.asarray(perm.reshape(B, P).astype(np.int32))
    lengths = jnp.asarray(rng.randint(1, P * ps + 1, size=(B,)), jnp.int32)
    return pool_k, pool_v, table, lengths


def test_paged_attention_eager_matches_dense(rng):
    """Gathering pages in table order must reproduce plain causal
    attention over each sequence's contiguous KV."""
    from unicore_tpu.serve.attention import paged_attention_reference

    B, P, ps, heads, d = 3, 5, 4, 4, 16
    pool_k, pool_v, table, lengths = _random_paged_case(rng, B, P, ps,
                                                       heads, d)
    q = jnp.asarray(rng.randn(B, 1, heads, d), jnp.float32)
    scale = d ** -0.5
    got = paged_attention_reference(
        q, pool_k, pool_v, table, (lengths - 1)[:, None], lengths, ps,
        scale,
    )
    from unicore_tpu.serve.attention import gather_slots

    k_seq = gather_slots(pool_k, table, ps).reshape(B, -1, heads, d)
    v_seq = gather_slots(pool_v, table, ps).reshape(B, -1, heads, d)
    for b in range(B):
        n = int(lengths[b])
        s = jnp.einsum(
            "qhd,khd->hqk", q[b] * scale, k_seq[b, :n]
        ).astype(jnp.float32)
        p = jax.nn.softmax(s, axis=-1)
        want = jnp.einsum("hqk,khd->qhd", p, v_seq[b, :n])
        np.testing.assert_allclose(
            np.asarray(got[b]), np.asarray(want), atol=1e-5, rtol=1e-5
        )


@pytest.mark.parametrize("pages_per_block,heads,d", [
    (1, 4, 16), (2, 4, 16), (3, 4, 16),
    # the serve widths: two 64-wide heads per 128-lane slab, and one
    # 128-wide head per slab
    (2, 4, 64), (2, 2, 128), (2, 3, 64),
])
def test_ragged_kernel_matches_eager(rng, pages_per_block, heads, d):
    """Pallas ragged kernel (interpret mode on CPU) vs the eager gather
    path on a MIXED batch — a decode row, prefill-chunk rows of
    different widths, ragged lengths, and an inactive (length-0) row
    all in one dispatch (the unified serve-step shape)."""
    from unicore_tpu.ops.pallas.paged_attention import (
        ragged_paged_attention,
    )
    from unicore_tpu.serve.attention import paged_attention_reference

    B, P, ps, T = 4, 5, 4, 3
    pool_k, pool_v, table, lengths = _random_paged_case(rng, B, P, ps,
                                                       heads, d)
    lengths = lengths.at[2].set(0)  # inactive batch slot
    ln = np.asarray(lengths)
    positions = np.full((B, T), -1, np.int32)
    positions[0] = [ln[0] - 3, ln[0] - 2, ln[0] - 1]  # prefill chunk
    positions[1, 0] = ln[1] - 1                       # decode row
    positions[3, :2] = [ln[3] - 2, ln[3] - 1]         # short chunk
    positions = jnp.asarray(positions)
    q = jnp.asarray(rng.randn(B, T, heads, d), jnp.float32)
    scale = d ** -0.5
    ref = paged_attention_reference(
        q, pool_k, pool_v, table, positions, lengths, ps, scale,
    )
    out = ragged_paged_attention(
        q, pool_k, pool_v, table, positions, lengths, page_size=ps,
        scale=scale, pages_per_block=pages_per_block,
    )
    assert bool(jnp.isfinite(out).all())  # padded rows finite too
    active = np.asarray(positions) >= 0
    np.testing.assert_allclose(
        np.asarray(out)[active], np.asarray(ref)[active],
        atol=2e-5, rtol=2e-5,
    )


# The four-point invariant of ``ops/pallas/paged_attention.py``, over the
# ROW PATTERNS that decide whether a prefetch carried from row to row is
# sound (``chip_smoke.py`` builds them, and runs the same ones COMPILED on
# the chip).  The pool reads POISON wherever no row may read, so a slot
# read stale or early is a gap of thousands, not noise.
#
# ``dma`` None: Pallas's plain interpreter, which runs a copy where it is
# started and so shows a wrong page, slot or parity and nothing else.
# ``"eager"`` / ``"on_wait"``: the TPU interpreter with its race detector
# on.  It reports a read of a slot whose DMA was not waited for (point 1)
# and a DMA into a slot whose reads are not all issued (point 3), whichever
# way the copy is run; ``"on_wait"`` runs a copy only where it is waited
# for, so an early read also reads stale numbers; ``"eager"`` counts a
# semaphore up where a copy is started, so one started twice, never waited
# for (point 1) or left in flight by the last program (point 4) prints a
# non-zero count at the kernel's exit; a wait for a copy nobody started
# (a row of length 0 that waits, point 2) hangs in both.  Point 2's other
# half is the count of copies: exactly those of the live rows' blocks.
_TRACE_KEYS = itertools.count()
_ROW_SIZE = dict(page_size=8, heads=2, head_dim=64, rows=16, num_pages=128,
                 table_pages=24, latent_heads=2)
_ROW_CASES = [
    (pattern, pp, parity, width, None)
    for pattern in ("zeros_first", "zeros_last", "zeros_between",
                    "one_block", "odd_even", "partial_last")
    for pp in (1, 2, 4) for parity in (0, 1) for width in (1, 3)
] + [
    ("latent_decode", pp, parity, 1, None)
    for pp in (1, 2, 4) for parity in (0, 1)
] + [
    # 208 and 256 rows: seven seconds a case in the interpreter
    (pattern, pp, parity, 1, None)
    for pattern in ("zeros_200", "latent_mixed")
    for pp, parity in ((1, 0), (2, 1), (4, 0), (4, 1))
] + [
    (pattern, pp, parity, 3, "eager")
    for pattern in ("zeros_between", "one_block", "odd_even",
                    "partial_last")
    for pp in (1, 2, 4) for parity in (0, 1)
] + [
    ("zeros_between", 1, 1, 3, "on_wait"), ("one_block", 2, 0, 1, "on_wait"),
    ("odd_even", 4, 1, 3, "on_wait"), ("partial_last", 2, 1, 3, "on_wait"),
    ("zeros_first", 4, 0, 1, "on_wait"), ("zeros_last", 1, 0, 3, "on_wait"),
    ("latent_decode", 2, 1, 1, "eager"), ("latent_decode", 4, 0, 1, "on_wait"),
    ("latent_mixed", 4, 1, 1, "eager"), ("latent_mixed", 2, 0, 1, "on_wait"),
] + [
    # a sliding layer's rows (PR 43; ``chip_smoke.window_row_case``)
    ("window", pp, parity, width, None)
    for pp in (1, 2, 4) for parity in (0, 1) for width in (1, 3)
] + [
    ("window", 1, 1, 3, "eager"), ("window", 2, 0, 1, "eager"),
    ("window", 4, 1, 3, "eager"), ("window", 2, 1, 3, "on_wait"),
    ("window", 4, 0, 1, "on_wait"),
]


@pytest.mark.parametrize(
    "pattern,pages_per_block,parity,width,dma", _ROW_CASES,
    ids=["%s-pp%d-parity%d-w%d-%s" % (*c[:4], c[4] or "plain")
         for c in _ROW_CASES])
def test_ragged_kernel_row_patterns(pattern, pages_per_block, parity, width,
                                    dma, monkeypatch, capfd):
    """Rows of length 0 first / last / between live rows / 200 in a row,
    rows of exactly one block, odd and even block counts side by side, a
    last block partly filled, and the latent step's shape (one head of
    640 lanes, one pool as keys and values, three passes, 256 rows of
    which 31 are live, the tiles of one prompt sharing a page table with
    lengths growing by 4), and a sliding layer's rows (a window that
    starts mid-page, mid-block and in block 0, a row shorter than the
    window, an empty row after a windowed one: only the blocks from each
    row's first visible one are copied), at 1, 2 and 4 pages a block with
    the rows' blocks starting in either K/V slot."""
    import chip_smoke as cs
    from unicore_tpu.ops.pallas import paged_attention as pa

    size = dict(cs.KERNEL, **_ROW_SIZE)
    if pattern.startswith("latent_"):
        case = cs.latent_row_case(pattern[len("latent_"):], size,
                                  pages_per_block, parity, seed=5)
    elif pattern == "window":
        case = cs.window_row_case(size, pages_per_block, parity, width,
                                  seed=5)
    else:
        case = cs.ragged_row_case(pattern, size, pages_per_block, parity,
                                  width, seed=5)
    copies = {"started": 0, "waited": 0}
    if dma is not None:
        from jax._src.pallas.mosaic.interpret import (
            interpret_pallas_call as tpu_interpreter,
        )
        from jax.experimental.pallas import tpu as pltpu

        def counted(name, fn):
            def call(*args, **kwargs):
                copies[name] += 1
                return fn(*args, **kwargs)
            return call

        for name, fn in (("started", "dma_start"), ("waited", "dma_wait")):
            monkeypatch.setattr(tpu_interpreter, fn, counted(
                name, getattr(tpu_interpreter, fn)))
        # a seed of its own keys a trace of its own under ``_call``'s jit,
        # so the counters above are the ones the trace calls
        params = pltpu.InterpretParams(
            dma_execution_mode=dma, detect_races=True,
            random_seed=next(_TRACE_KEYS))
        monkeypatch.setattr(pa, "pallas_interpret", lambda: params)
    gap, finite = cs.ragged_case_gap(case)
    assert finite  # empty rows and masked cells too
    assert gap <= (2e-5 if not case["three_pass"] else 1e-4), gap
    if dma is None:
        return
    # points 1 and 3: no read of a slot before its copy was waited for, no
    # copy into a slot that is still read
    assert not tpu_interpreter.races.races_found
    # points 1, 2 and 4: exactly the live rows' blocks were copied (keys
    # and values of each page), each waited for once, none left in flight
    blocks = sum(-(-int(n) // (pages_per_block * size["page_size"]))
                 for n in case["lengths"])
    if pattern == "window":  # blocks behind the window: not read
        assert case["blocks"] < blocks
        blocks = case["blocks"]
    assert copies == {"started": 2 * pages_per_block * blocks,
                      "waited": 2 * pages_per_block * blocks}, copies
    assert "non-zero count" not in capfd.readouterr().out


def test_ragged_decode_wrapper_matches_eager(rng):
    """The T=1 decode wrapper stays available and exact."""
    from unicore_tpu.ops.pallas.paged_attention import (
        ragged_decode_attention,
    )
    from unicore_tpu.serve.attention import paged_attention_reference

    B, P, ps, heads, d = 4, 5, 4, 4, 16
    pool_k, pool_v, table, lengths = _random_paged_case(rng, B, P, ps,
                                                       heads, d)
    q = jnp.asarray(rng.randn(B, 1, heads, d), jnp.float32)
    scale = d ** -0.5
    ref = paged_attention_reference(
        q, pool_k, pool_v, table, (lengths - 1)[:, None], lengths, ps,
        scale,
    )
    out = ragged_decode_attention(
        q, pool_k, pool_v, table, lengths, page_size=ps, scale=scale,
        pages_per_block=2,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5,
    )


# -- engine batched correctness (the PR acceptance property) ---------------


@pytest.mark.slow  # ~67s (8 solo-oracle full forwards); tier-1 keeps
# the scheduler property traces, parity, and pool-exhausted recovery
# tests; CI's full suite + serve smoke run this acceptance oracle
def test_engine_mixed_batch_matches_solo_decode(lm, rng):
    """>= 8 requests, mixed prompt lengths, pool sized to force
    eviction at least once: every emitted sequence must be
    token-identical to its solo full-forward greedy decode."""
    model, params = lm
    engine = ServeEngine(
        model, params, num_pages=9, page_size=4, max_batch=4,
        chaos_rate=0.25, chaos_rng=random.Random(7),
    )
    lens = [3, 5, 7, 4, 9, 6, 8, 5]
    reqs = [
        Request(
            prompt=rng.randint(1, V, size=(n,)).tolist(),
            max_new_tokens=8, seed=i, eos_id=5, request_id=f"r{i}",
        )
        for i, n in enumerate(lens)
    ]
    results = engine.generate(reqs)
    assert engine.stats["evictions"] >= 1, (
        "the test must exercise eviction; shrink the pool or raise "
        "chaos_rate"
    )
    assert [r.request_id for r in results] == [f"r{i}"
                                               for i in range(len(lens))]
    for res, req in zip(results, reqs):
        want = solo_greedy(model, params, req.prompt, req.max_new_tokens,
                           eos=req.eos_id)
        assert res.tokens == want, (req.prompt, res.tokens, want)
        assert res.finish_reason in ("eos", "length")
        assert res.ttft_ms >= 0.0
    assert engine.stats["peak_pool_occupancy"] > 0.5


def test_scheduler_admit_race_returns_partial_or_reraises_empty():
    """The accounting race inside admit() (can_alloc said yes,
    alloc raised anyway): with earlier admissions in the same call the
    partial batch is RETURNED (so the engine prefills them — an escape
    would strand allocated-but-never-prefilled KV pages in `running`),
    and only an empty admission re-raises for the engine's recovery.
    Either way the raced sequence stays at waiting[0]."""
    from unicore_tpu.serve.scheduler import Scheduler

    pool = PagedKVPool(num_pages=16, page_size=4)
    sched = Scheduler(pool, max_batch=4, prefill_token_budget=64)
    for i in range(3):
        sched.add(Request(prompt=[1] * 6, max_new_tokens=2,
                          seed=i, request_id=f"r{i}"))
    real_can_alloc, lies = pool.can_alloc, {"calls": 0}

    def lie_on_second(n, tokens=None):  # 2nd admission's alloc races
        lies["calls"] += 1
        return True if lies["calls"] == 2 else real_can_alloc(n)

    real_alloc = pool.alloc

    def alloc(sid, n, tokens=None):
        if lies["calls"] == 2 and not real_can_alloc(n):
            raise PoolExhausted("raced")
        return real_alloc(sid, n, tokens=tokens)

    pool.can_alloc, pool.alloc = lie_on_second, alloc
    del pool._free[:-2]  # 2 free pages left: fits ONE 6-token prompt
    admitted = sched.admit()
    assert [s.req.request_id for s in admitted] == ["r0"], admitted
    assert sched.waiting[0].req.request_id == "r1", "raced seq lost"
    assert [s.req.request_id for s in sched.running] == ["r0"]
    # empty admission: the race now escapes (the engine's recovery path)
    lies["calls"] = 1  # next can_alloc call lies again
    with pytest.raises(PoolExhausted):
        sched.admit()
    assert sched.waiting[0].req.request_id == "r1", "raced seq lost"
    assert [s.req.request_id for s in sched.running] == ["r0"]


def test_engine_recovers_from_pool_exhausted_admission_race(lm):
    """A PoolExhausted that escapes admit() (which, per the scheduler
    contract above, means NOTHING was admitted in that call) must not
    escape the engine: it preempts the scheduler's LIFO victim, counts
    ``pool_exhausted_recoveries``, re-admits the still-queued sequence,
    and every request's tokens remain identical to solo decode — the
    race is a capacity hiccup, never an accuracy or liveness event."""
    model, params = lm
    engine = ServeEngine(
        model, params, num_pages=7, page_size=4, max_batch=3,
        prefill_token_budget=16,
        chaos_rate=0.2, chaos_rng=random.Random(3),
    )
    sched = engine.scheduler
    real_admit, races = sched.admit, {"n": 0}

    def racing_admit(bucket=None):
        # the empty-admission escape, mid-run (a victim must exist)
        if races["n"] < 2 and sched.running and sched.waiting:
            races["n"] += 1
            raise PoolExhausted("admission race")
        return real_admit(bucket=bucket)

    sched.admit = racing_admit
    trng = np.random.RandomState(3)
    reqs = [
        Request(
            prompt=trng.randint(1, V, size=(int(n),)).tolist(),
            max_new_tokens=5, seed=i, eos_id=5, request_id=f"r{i}",
        )
        for i, n in enumerate([3, 7, 5, 8, 4])
    ]
    results = engine.generate(reqs)
    assert races["n"] == 2, "the race was never exercised"
    assert engine.stats["pool_exhausted_recoveries"] >= 1
    engine.pool.check_invariants()
    assert [r.request_id for r in results] == [f"r{i}" for i in range(5)]
    for res, req in zip(results, reqs):
        want = solo_greedy(model, params, req.prompt, req.max_new_tokens,
                           eos=req.eos_id)
        assert res.tokens == want, (req.prompt, res.tokens, want)


@pytest.mark.parametrize("chaos_seed", [11, 23])
def test_scheduler_property_random_traces(lm, chaos_seed):
    """Randomized admission/eviction traces (seeded chaos preemption on
    a tiny pool): outputs stay token-identical to solo decode — no
    request's tokens are lost or duplicated."""
    model, params = lm
    trng = np.random.RandomState(chaos_seed)
    engine = ServeEngine(
        model, params, num_pages=7, page_size=4, max_batch=3,
        prefill_token_budget=16,
        chaos_rate=0.4, chaos_rng=random.Random(chaos_seed),
    )
    reqs = [
        Request(
            prompt=trng.randint(1, V, size=(int(n),)).tolist(),
            max_new_tokens=int(m), seed=i, eos_id=5,
        )
        for i, (n, m) in enumerate(
            zip(trng.randint(1, 11, size=8), trng.randint(1, 7, size=8))
        )
    ]
    results = engine.generate(reqs)
    for res, req in zip(results, reqs):
        want = solo_greedy(model, params, req.prompt, req.max_new_tokens,
                           eos=req.eos_id)
        assert res.tokens == want, (req.prompt, res.tokens, want)


def test_engine_seeded_sampling_deterministic(lm):
    """Same seeds -> same sampled tokens, run to run, and eviction
    pressure must not change a sampled continuation (step keys fold in
    the absolute step index)."""
    model, params = lm
    prompts = [[3, 7, 2], [11, 4, 9, 8, 1], [6, 2], [13, 5, 5, 20]]

    def run(chaos):
        engine = ServeEngine(
            model, params, num_pages=8, page_size=4, max_batch=4,
            chaos_rate=0.5 if chaos else 0.0,
            chaos_rng=random.Random(3) if chaos else None,
        )
        reqs = [
            Request(prompt=p, max_new_tokens=6, temperature=0.8,
                    top_k=5, seed=100 + i)
            for i, p in enumerate(prompts)
        ]
        return [r.tokens for r in engine.generate(reqs)]

    base = run(chaos=False)
    assert all(len(toks) == 6 for toks in base)
    assert base == run(chaos=False), "same seeds must replay identically"
    assert base == run(chaos=True), (
        "eviction/re-prefill changed a seeded sampling stream"
    )


def test_engine_rejects_oversized_prompt(lm):
    model, params = lm
    engine = ServeEngine(model, params, num_pages=4, page_size=4,
                         max_batch=2)  # context = 12 slots
    with pytest.raises(ValueError, match="context"):
        engine.generate(
            [Request(prompt=list(range(1, 15)), max_new_tokens=2)]
        )


def test_engine_capacity_finish(lm):
    """A request bounded by pool capacity is truncated with reason
    "capacity" instead of wedging the scheduler — and the truncated
    tokens still match the solo decode."""
    model, params = lm
    engine = ServeEngine(model, params, num_pages=4, page_size=4,
                         max_batch=2)  # 12 usable slots = max_context
    [res] = engine.generate(
        [Request(prompt=[3, 7, 2, 9], max_new_tokens=20)]
    )
    assert res.finish_reason == "capacity"
    # the last decode writes KV at slot max_context-1 and samples one
    # final token beyond it: max_context - len(prompt) + 1 tokens
    assert len(res.tokens) == 12 - 4 + 1
    want = solo_greedy(model, params, [3, 7, 2, 9], len(res.tokens))
    assert res.tokens == want


# -- robustness: deadlines, shedding, starvation, quarantine, drain --------
# (ISSUE 7; docs/serving.md#robustness)


class _Clock:
    """Manual host clock for exact deadline/drain timing in tests."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _tick_per_decode(engine, clock, dt=10.0, hook=None):
    """Advance the fake clock after every ragged dispatch (as if each
    step took ``dt`` seconds); ``hook(step_count)`` runs after the
    tick."""
    orig = engine._dispatch

    def ticking(seqs):
        orig(seqs)
        clock.t += dt
        if hook is not None:
            hook(engine.stats["decode_steps"])

    engine._dispatch = ticking


def test_deadline_expiry_mid_decode_frees_pages(lm):
    """A running request whose TTL blows mid-stream finishes 'expired'
    at the next decode boundary, its pages free immediately, and the
    tokens it DID emit match the solo oracle prefix; requests without a
    deadline are untouched."""
    model, params = lm
    clock = _Clock()
    engine = ServeEngine(model, params, num_pages=16, page_size=4,
                         max_batch=4, clock=clock)
    _tick_per_decode(engine, clock, dt=10.0)  # 10 "seconds" per step
    reqs = [
        Request(prompt=[3, 7, 2], max_new_tokens=12, request_id="dies",
                deadline_ms=25_000.0),
        Request(prompt=[11, 4, 9], max_new_tokens=12,
                request_id="lives"),
    ]
    by = {r.request_id: r for r in engine.generate(reqs)}
    assert by["dies"].finish_reason == "expired"
    assert 0 < len(by["dies"].tokens) < 12
    want = solo_greedy(model, params, [3, 7, 2], 12)
    assert by["dies"].tokens == want[: len(by["dies"].tokens)]
    assert by["lives"].finish_reason == "length"
    assert by["lives"].tokens == solo_greedy(model, params, [11, 4, 9],
                                             12)
    assert engine.stats["expired"] == 1
    engine.pool.check_invariants()
    assert engine.pool.is_idle()


def test_deadline_expiry_in_waiting_queue(lm):
    """A request that never leaves the waiting queue before its TTL
    expires at the ADMISSION boundary: zero tokens, no TTFT, no pages
    ever held."""
    model, params = lm
    clock = _Clock()
    engine = ServeEngine(model, params, num_pages=16, page_size=4,
                         max_batch=1, clock=clock)
    _tick_per_decode(engine, clock, dt=10.0)
    reqs = [
        Request(prompt=[3, 7, 2], max_new_tokens=6, request_id="runs"),
        Request(prompt=[5, 9], max_new_tokens=4, request_id="starves",
                deadline_ms=15_000.0),
    ]
    by = {r.request_id: r for r in engine.generate(reqs)}
    assert by["starves"].finish_reason == "expired"
    assert by["starves"].tokens == [] and by["starves"].ttft_ms is None
    assert by["runs"].finish_reason == "length"
    assert by["runs"].tokens == solo_greedy(model, params, [3, 7, 2], 6)
    engine.pool.check_invariants()
    assert engine.pool.is_idle()


def test_flood_shed_deterministic_and_bounded(lm):
    """2x-capacity flood against a bounded waiting queue: shed
    decisions are deterministic (reject-newest, same run to run), the
    queue never exceeds the bound, and admitted requests still match
    the solo oracle."""
    model, params = lm

    def run():
        engine = ServeEngine(model, params, num_pages=16, page_size=4,
                             max_batch=2, max_waiting=3)
        reqs = [
            Request(prompt=[2 + i, 5, 9], max_new_tokens=4,
                    request_id=f"r{i}")
            for i in range(9)
        ]
        return engine, reqs, engine.generate(reqs)

    e1, reqs, r1 = run()
    _, _, r2 = run()
    shed1 = [r.request_id for r in r1 if r.finish_reason == "shed"]
    shed2 = [r.request_id for r in r2 if r.finish_reason == "shed"]
    # reject-newest with free decode slots as headroom: on an idle
    # engine the first max_batch + max_waiting requests are kept, the
    # rest shed — the bound engages against OVERLOAD, never against
    # capacity the batch has free
    assert shed1 == [f"r{i}" for i in range(5, 9)]
    assert shed1 == shed2, "shed decisions must be deterministic"
    assert e1.stats["peak_waiting"] <= 3 + 2  # max_waiting + max_batch
    assert e1.stats["shed"] == 4
    for req, res in zip(reqs, r1):
        if res.finish_reason == "shed":
            assert res.tokens == [] and res.ttft_ms is None
        else:
            assert res.tokens == solo_greedy(model, params, req.prompt, 4)
    e1.pool.check_invariants()
    assert e1.pool.is_idle()


def test_starvation_freedom_under_chaos_promotion(lm):
    """Heavy seeded chaos preemption on a tiny pool with a small
    re-prefill budget: every admitted request still finishes (the
    budget promotes over-evicted sequences out of the victim scans) and
    every result stays token-identical to the solo oracle."""
    model, params = lm
    trng = np.random.RandomState(5)
    engine = ServeEngine(
        model, params, num_pages=7, page_size=4, max_batch=3,
        prefill_token_budget=16, request_retries=2,
        chaos_rate=0.6, chaos_rng=random.Random(5),
    )
    reqs = [
        Request(prompt=trng.randint(1, V, size=(int(n),)).tolist(),
                max_new_tokens=5, seed=i, eos_id=5, request_id=f"r{i}")
        for i, n in enumerate([3, 7, 5, 8, 4, 6])
    ]
    results = engine.generate(reqs)
    assert engine.stats["evictions"] >= 1
    for req, res in zip(reqs, results):
        assert res.finish_reason in ("eos", "length"), res
        want = solo_greedy(model, params, req.prompt, req.max_new_tokens,
                           eos=req.eos_id)
        assert res.tokens == want, (req.request_id, res.tokens, want)
    engine.pool.check_invariants()
    assert engine.pool.is_idle()


def test_scheduler_expire_and_promotion_units():
    from unicore_tpu.serve.scheduler import Scheduler

    pool = PagedKVPool(num_pages=8, page_size=4)
    sched = Scheduler(pool, max_batch=4, request_retries=1,
                      chaos_rate=1.0, chaos_rng=random.Random(0))
    a = sched.add(Request(prompt=[1, 2], max_new_tokens=2,
                          deadline_ms=100.0))
    b = sched.add(Request(prompt=[1, 2, 3], max_new_tokens=2))
    a.enqueued_at = b.enqueued_at = 0.0
    sched.admit()
    assert sched.expire(now=0.05) == []      # 50ms: TTL not blown
    assert sched.expire(now=0.2) == [a]      # 200ms > 100ms TTL
    assert a.finish_reason == "expired"
    pool.check_invariants()
    # promotion: an over-budget sequence is skipped by both victim scans
    c = sched.add(Request(prompt=[4, 5], max_new_tokens=2))
    c.enqueued_at = 0.0
    sched.admit()
    assert [s is b or s is c for s in sched.running] == [True, True]
    b.evictions = 1  # at the budget -> promoted
    assert sched._pick_victim() is c
    c.evictions = 1
    # everyone promoted: organic eviction falls back to LIFO (liveness)
    assert sched._pick_victim() is c
    # ...but chaos preemption skips promoted sequences entirely
    assert sched.chaos_preempt() is None
    # bounded add: free decode slots count as headroom (an idle engine
    # keeps max_batch + max_waiting); once the batch is saturated the
    # waiting line holds at exactly max_waiting
    pool2 = PagedKVPool(num_pages=16, page_size=4)
    s2 = Scheduler(pool2, max_batch=2, max_waiting=1)
    kept = [s2.add(Request(prompt=[1], max_new_tokens=1))
            for _ in range(4)]
    assert [q.finish_reason for q in kept] == [None, None, None, "shed"]
    s2.admit()  # 2 run, 1 waits: saturated
    late = s2.add(Request(prompt=[1], max_new_tokens=1))
    assert late.finish_reason == "shed" and len(s2.waiting) == 1
    pool2.check_invariants()


def test_poisoned_request_quarantined_survivors_identical(lm):
    """The fault-isolation oracle: one request's logits row is poisoned
    (NaN) inside the jitted step; it finishes 'failed' with its pages
    freed while every other request's tokens are bit-identical to solo
    decode."""
    model, params = lm
    trng = np.random.RandomState(11)
    prompts = [trng.randint(1, V, size=(n,)).tolist()
               for n in [3, 6, 4, 7]]
    engine = ServeEngine(model, params, num_pages=12, page_size=4,
                         max_batch=4, poison_requests=["r1"])
    reqs = [Request(prompt=p, max_new_tokens=6, eos_id=5,
                    request_id=f"r{i}") for i, p in enumerate(prompts)]
    by = {r.request_id: r for r in engine.generate(reqs)}
    assert by["r1"].finish_reason == "failed"
    assert by["r1"].tokens == []  # poisoned at prefill: nothing emitted
    assert engine.stats["quarantined"] == 1
    for i, p in enumerate(prompts):
        if i == 1:
            continue
        want = solo_greedy(model, params, p, 6, eos=5)
        assert by[f"r{i}"].tokens == want, (i, by[f"r{i}"].tokens, want)
        assert by[f"r{i}"].finish_reason in ("eos", "length")
    engine.pool.check_invariants()
    assert engine.pool.is_idle()


def test_poison_mid_stream_quarantines_on_decode_boundary(lm):
    """Poison arriving mid-stream (decode path, not prefill): the
    victim keeps its pre-fault tokens — which still match the solo
    prefix — and the batch survivors are untouched."""
    model, params = lm
    engine = ServeEngine(model, params, num_pages=12, page_size=4,
                         max_batch=2, poison_requests=["__armed__"])
    orig = engine._dispatch

    def arm_later(seqs):
        orig(seqs)
        if engine.stats["decode_steps"] == 2:
            engine._poison_ids = frozenset(["r0"])

    engine._dispatch = arm_later
    reqs = [Request(prompt=[3, 7, 2], max_new_tokens=8,
                    request_id="r0"),
            Request(prompt=[11, 4, 9, 8], max_new_tokens=8,
                    request_id="r1")]
    by = {r.request_id: r for r in engine.generate(reqs)}
    assert by["r0"].finish_reason == "failed"
    assert 0 < len(by["r0"].tokens) < 8
    assert by["r0"].tokens == solo_greedy(
        model, params, [3, 7, 2], 8)[: len(by["r0"].tokens)]
    assert by["r1"].finish_reason == "length"
    assert by["r1"].tokens == solo_greedy(model, params, [11, 4, 9, 8], 8)
    engine.pool.check_invariants()
    assert engine.pool.is_idle()


def test_host_fault_fails_inflight_not_engine(lm):
    """A host-side step exception fails the in-flight sequences with
    reason 'failed' and frees their pages; the engine survives and the
    next batch decodes clean."""
    model, params = lm
    engine = ServeEngine(model, params, num_pages=12, page_size=4,
                         max_batch=2)
    orig = engine._dispatch
    state = {"raised": False}

    def flaky(seqs):
        if not state["raised"] and engine.stats["decode_steps"] >= 1:
            state["raised"] = True
            raise RuntimeError("sampler exploded (host side)")
        orig(seqs)

    engine._dispatch = flaky
    reqs = [Request(prompt=[3, 7, 2], max_new_tokens=5,
                    request_id="a"),
            Request(prompt=[11, 4], max_new_tokens=5, request_id="b")]
    results = engine.generate(reqs)
    assert [r.finish_reason for r in results] == ["failed", "failed"]
    assert engine.stats["host_faults"] == 1
    engine.pool.check_invariants()
    assert engine.pool.is_idle()
    # the engine is still servable, token-identically
    [clean] = engine.generate(
        [Request(prompt=[6, 2, 9], max_new_tokens=5,
                 request_id="clean")])
    assert clean.tokens == solo_greedy(model, params, [6, 2, 9], 5)


def test_first_call_fault_of_a_step_propagates(lm):
    """A step that cannot be traced, lowered or compiled is a broken
    PROGRAM, not a bad request: failing the in-flight requests and
    carrying on would end a run with exit 0 and no token served.  The
    first call of a (width, sampling) step raises past the per-request
    isolation; once a step has run, a later fault is isolated as
    before (the test above)."""
    from unicore_tpu.serve.engine import StepCompileError

    model, params = lm
    engine = ServeEngine(model, params, num_pages=12, page_size=4,
                         max_batch=2)

    def refused(*args):
        raise ValueError("Block spec for args[2] ... (the TPU lowering)")

    engine._ragged_step_fn = lambda width, sampling: refused
    with pytest.raises(StepCompileError, match="first call"):
        engine.generate([Request(prompt=[3, 7, 2], max_new_tokens=2,
                                 request_id="a")])
    assert engine.stats["host_faults"] == 0


def test_row_assembly_fault_fails_only_that_request(lm):
    """Per-request isolation survives the unified dispatch: a host-side
    fault in ONE row's assembly (a poisoned slot lookup for that
    sequence) fails only that request — the rest of the batch stays
    token-identical to the solo oracle."""
    model, params = lm
    engine = ServeEngine(model, params, num_pages=16, page_size=4,
                         max_batch=3)
    victim_sid = {}
    real_table = engine.pool.page_table

    def bad_table(sid):
        if sid == victim_sid.get("sid"):
            raise RuntimeError("corrupted per-sequence state")
        return real_table(sid)

    engine.pool.page_table = bad_table
    reqs = [Request(prompt=[3, 7, 2], max_new_tokens=5,
                    request_id="a"),
            Request(prompt=[11, 4, 9, 8], max_new_tokens=5,
                    request_id="bad"),
            Request(prompt=[6, 2], max_new_tokens=5, request_id="c")]
    seqs = engine.submit(reqs)
    victim_sid["sid"] = seqs[1].sid
    while engine.serve_step():
        pass
    by = {r.request_id: r for r in engine.collect_finished()}
    assert by["bad"].finish_reason == "failed"
    assert engine.stats["host_faults"] == 1
    for rid, prompt in (("a", [3, 7, 2]), ("c", [6, 2])):
        assert by[rid].finish_reason == "length"
        assert by[rid].tokens == solo_greedy(model, params, prompt, 5)
    engine.pool.check_invariants()
    assert engine.pool.is_idle()


def test_capacity_failfast_instead_of_livelock(lm):
    """Satellite fix: a request whose prompt+generated prefix can never
    fit the pool terminates with reason 'capacity' (counted in stats)
    instead of cycling the preempt-retry recovery forever; neighbors
    are unaffected."""
    model, params = lm
    engine = ServeEngine(model, params, num_pages=4, page_size=4,
                         max_batch=2)  # 3 usable pages = 12 slots
    sched = engine.scheduler
    good = sched.add(Request(prompt=[3, 7, 2], max_new_tokens=3,
                             request_id="fits"))
    bad = sched.add(Request(prompt=[2] * 8, max_new_tokens=4,
                            request_id="huge"))
    good.enqueued_at = bad.enqueued_at = 0.0
    # simulate a preempted-and-resumed request whose prefix outgrew the
    # whole pool (16 tokens -> 4 pages > 3 usable)
    bad.generated = [1] * 8
    engine._run_to_completion(sched)
    assert bad.finish_reason == "capacity"
    assert engine.stats["capacity_failfast"] == 1
    assert good.finish_reason == "length"
    engine.pool.check_invariants()
    assert engine.pool.is_idle()


def test_graceful_drain_sheds_within_timeout(lm):
    """SIGTERM-equivalent drain with drain_timeout=0: admission closes,
    waiting requests shed immediately, running ones shed at the next
    boundary past the deadline — partial tokens preserved (and still
    oracle-exact), pool idle, drain report emitted.  The engine stays
    drained afterwards."""
    import signal as _signal

    from unicore_tpu.resilience.preemption import GracefulShutdown

    model, params = lm
    sd = GracefulShutdown()  # not installed: programmatic trigger
    engine = ServeEngine(model, params, num_pages=16, page_size=4,
                         max_batch=2, shutdown=sd, drain_timeout=0.0)
    orig = engine._dispatch

    def trip(seqs):
        orig(seqs)
        if engine.stats["decode_steps"] == 2:
            sd.request(_signal.SIGTERM)

    engine._dispatch = trip
    reqs = [Request(prompt=[3 + i, 7, 2], max_new_tokens=10,
                    request_id=f"r{i}") for i in range(4)]
    results = engine.generate(reqs)
    assert all(r.finish_reason == "shed" for r in results)
    report = engine.drain_report
    assert report and report["requested"] and report["signal"] == "SIGTERM"
    assert report["pool_idle"] and engine.pool.is_idle()
    engine.pool.check_invariants()
    for req, res in zip(reqs, results):
        if res.tokens:
            want = solo_greedy(model, params, req.prompt, 10)
            assert res.tokens == want[: len(res.tokens)]
    # a drained engine sheds everything submitted later
    [late] = engine.generate([Request(prompt=[5, 5], max_new_tokens=2,
                                      request_id="late")])
    assert late.finish_reason == "shed"


def test_graceful_drain_finishes_inflight_within_timeout(lm):
    """With a generous drain_timeout, in-flight requests run their tail
    out and finish normally (solo-oracle-exact); only the never-admitted
    waiting request is shed."""
    from unicore_tpu.resilience.preemption import GracefulShutdown

    model, params = lm
    sd = GracefulShutdown()
    engine = ServeEngine(model, params, num_pages=16, page_size=4,
                         max_batch=2, shutdown=sd, drain_timeout=60.0)
    orig = engine._dispatch

    def trip(seqs):
        orig(seqs)
        if engine.stats["decode_steps"] == 1:
            sd.request()

    engine._dispatch = trip
    reqs = [Request(prompt=[3, 7, 2], max_new_tokens=6,
                    request_id="r0"),
            Request(prompt=[11, 4, 9], max_new_tokens=6,
                    request_id="r1"),
            Request(prompt=[6, 2], max_new_tokens=6, request_id="r2")]
    by = {r.request_id: r for r in engine.generate(reqs)}
    assert by["r2"].finish_reason == "shed"  # never admitted
    for rid, prompt in (("r0", [3, 7, 2]), ("r1", [11, 4, 9])):
        assert by[rid].finish_reason == "length"
        assert by[rid].tokens == solo_greedy(model, params, prompt, 6)
    assert engine.drain_report["deadline_exceeded"] is False
    engine.pool.check_invariants()
    assert engine.pool.is_idle()


# -- ragged unification + shared-prefix dedup (ISSUE 13) -------------------


def test_chunked_prefill_matches_unchunked(lm):
    """A long prompt admitted in bounded-TTFT chunks emits tokens
    identical to the single-slice admission (and to the solo oracle) —
    chunked prefill is a latency feature, never an accuracy one."""
    model, params = lm
    trng = np.random.RandomState(17)
    prompts = [trng.randint(1, V, size=(n,)).tolist()
               for n in [23, 7, 30, 12]]

    def run(chunk):
        engine = ServeEngine(model, params, num_pages=24, page_size=4,
                             max_batch=4, prefill_chunk=chunk)
        reqs = [Request(prompt=p, max_new_tokens=5, seed=i, eos_id=5,
                        request_id=f"r{i}")
                for i, p in enumerate(prompts)]
        return [r.tokens for r in engine.generate(reqs)], engine

    base, _ = run(chunk=64)          # every prompt in one slice
    small, eng = run(chunk=4)        # 23-token prompt -> 6 slices
    assert base == small
    for toks, p in zip(base, prompts):
        assert toks == solo_greedy(model, params, p, 5, eos=5)
    assert eng.prefill_chunk == 4
    eng.pool.check_invariants()


def test_split_dispatch_matches_unified(lm):
    """The bench A/B baseline (unified=False: prefill rows and decode
    rows as two separate programs per step) is token-identical to the
    unified mixed dispatch — the comparison isolates performance."""
    model, params = lm
    trng = np.random.RandomState(3)
    prompts = [trng.randint(1, V, size=(n,)).tolist()
               for n in [3, 9, 6, 12, 5]]

    def run(unified):
        engine = ServeEngine(model, params, num_pages=16, page_size=4,
                             max_batch=3, unified=unified)
        reqs = [Request(prompt=p, max_new_tokens=6, seed=i,
                        request_id=f"r{i}")
                for i, p in enumerate(prompts)]
        return [r.tokens for r in engine.generate(reqs)]

    assert run(True) == run(False)


def test_pool_prefix_dedup_refcounts_and_reclaim():
    """Dedup invariants: a second sequence sharing a registered prefix
    references the SAME full pages (refcount 2), the partial tail page
    is never shared, freeing drops references without freeing shared
    pages, and a fully-released registered page parks in the cache
    (reclaimable, pool still idle)."""
    pool = PagedKVPool(num_pages=16, page_size=4)
    toks = list(range(100, 118))  # 18 tokens: 4 full pages + tail of 2
    t_a = pool.alloc("a", len(toks), tokens=toks)
    assert pool.cached_tokens("a") == 0  # nothing registered yet
    pool.register_prefix("a", toks)
    pool.check_invariants()
    t_b = pool.alloc("b", len(toks), tokens=toks)
    pool.check_invariants()
    # the 4 full pages are shared by reference; the tail is private
    assert t_b[:4] == t_a[:4]
    assert t_b[4] != t_a[4]
    assert pool.cached_tokens("b") == 16
    assert pool.prefix_stats["hits"] == 1
    assert pool.prefix_stats["tokens_saved"] == 16
    # freeing the REGISTRANT keeps the shared pages live for b
    pool.free("a")
    pool.check_invariants()
    assert pool.page_table("b")[:4] == t_a[:4]
    # freeing b parks the registered pages in the cache: reclaimable
    # capacity, pool idle, and a third sequence still hits
    pool.free("b")
    pool.check_invariants()
    assert pool.is_idle()
    assert pool.num_free_pages == pool.num_usable_pages
    t_c = pool.alloc("c", len(toks), tokens=toks)
    assert t_c[:4] == t_a[:4] and pool.prefix_stats["hits"] == 2
    pool.free("c")
    pool.check_invariants()


def test_pool_page_aligned_prefix_keeps_tail_private():
    """A prompt whose full length is page-aligned AND fully indexed
    must still re-prefill its last page privately (at least one token
    — the one whose logits seed sampling — is never dedup'd), so no
    sequence ever writes into a shared page: the CoW-by-recompute
    contract."""
    pool = PagedKVPool(num_pages=16, page_size=4)
    toks = list(range(200, 216))  # exactly 4 pages
    t_a = pool.alloc("a", len(toks), tokens=toks)
    pool.register_prefix("a", toks)
    t_b = pool.alloc("b", len(toks), tokens=toks)
    assert pool.cached_tokens("b") == 12  # capped at len - 1 -> 3 pages
    assert t_b[:3] == t_a[:3] and t_b[3] != t_a[3]
    # every write position b issues (>= cached_tokens) lands in a
    # page b owns exclusively
    for pos in range(pool.cached_tokens("b"), len(toks)):
        slot = pool.slot("b", pos)
        assert slot // pool.page_size not in t_a, (pos, slot)
    pool.free("a")
    pool.free("b")
    pool.check_invariants()


def test_engine_warm_prefix_skips_prefill_tokens(lm):
    """The tentpole property: a repeat of a warm shared prefix becomes
    a page-table lookup — the second request's ragged prefill starts
    past the shared pages — while its tokens stay solo-oracle exact."""
    model, params = lm
    trng = np.random.RandomState(29)
    system = trng.randint(1, V, size=(18,)).tolist()
    tails = [trng.randint(1, V, size=(4,)).tolist() for _ in range(2)]
    engine = ServeEngine(model, params, num_pages=24, page_size=4,
                         max_batch=2, prefill_chunk=8)
    [cold] = engine.generate(
        [Request(prompt=system + tails[0], max_new_tokens=4,
                 request_id="cold")])
    assert engine.pool.prefix_stats["hits"] == 0
    [warm] = engine.generate(
        [Request(prompt=system + tails[1], max_new_tokens=4,
                 request_id="warm")])
    # 18 shared tokens -> 4 full pages (16 tokens) dedup'd
    assert engine.pool.prefix_stats["hits"] == 1
    assert engine.pool.prefix_stats["tokens_saved"] == 16
    assert engine.stats["prefix_hits"] == 1
    snap = engine.load_snapshot()
    assert snap["prefix_hits"] == 1 and snap["prefix_hit_rate"] > 0
    for res, tail in zip((cold, warm), tails):
        assert res.tokens == solo_greedy(model, params, system + tail, 4)
    engine.pool.check_invariants()
    assert engine.pool.is_idle()


def test_prefix_cache_on_off_and_eviction_deterministic(lm):
    """Prefix-cache determinism: the same request stream emits
    IDENTICAL tokens with the cache on, off, and across cache eviction
    pressure (a tiny pool forces cached pages to be reclaimed and
    re-registered) — dedup is a capacity feature, never an accuracy
    one."""
    model, params = lm
    trng = np.random.RandomState(41)
    system = trng.randint(1, V, size=(9,)).tolist()
    reqs_spec = [(system + trng.randint(1, V, size=(3,)).tolist(), i)
                 for i in range(5)]

    def run(prefix_cache, num_pages):
        engine = ServeEngine(model, params, num_pages=num_pages,
                             page_size=4, max_batch=2,
                             prefix_cache=prefix_cache)
        reqs = [Request(prompt=p, max_new_tokens=4, seed=i,
                        request_id=f"r{i}") for p, i in reqs_spec]
        out = [r.tokens for r in engine.generate(reqs)]
        engine.pool.check_invariants()
        return out, engine

    base, _ = run(prefix_cache=False, num_pages=24)
    cached, e1 = run(prefix_cache=True, num_pages=24)
    tight, e2 = run(prefix_cache=True, num_pages=8)  # eviction pressure
    assert base == cached == tight
    assert e1.pool.prefix_stats["hits"] >= 1
    # the tight pool really did evict cached pages (the determinism
    # claim is vacuous otherwise)
    assert e2.pool.prefix_stats["cache_evictions"] >= 1
    # and two identical tight runs make identical hit/miss decisions
    tight2, e3 = run(prefix_cache=True, num_pages=8)
    assert tight2 == tight
    assert e3.pool.prefix_stats == e2.pool.prefix_stats


def test_quarantined_prefix_sharer_leaves_survivor_exact(lm):
    """A poisoned request whose pages are prefix-SHARED is quarantined
    while the survivor sharing the prefix stays token-identical — the
    quarantine drops one reference, never the shared pages."""
    model, params = lm
    trng = np.random.RandomState(7)
    system = trng.randint(1, V, size=(10,)).tolist()
    t0, t1 = ([int(x) for x in trng.randint(1, V, size=(3,))]
              for _ in range(2))
    engine = ServeEngine(model, params, num_pages=24, page_size=4,
                         max_batch=2, poison_requests=["bad"])
    [good0] = engine.generate(
        [Request(prompt=system + t0, max_new_tokens=4,
                 request_id="seed-prefix")])
    by = {r.request_id: r for r in engine.generate([
        Request(prompt=system + t1, max_new_tokens=4,
                request_id="bad"),
        Request(prompt=system + t0, max_new_tokens=4,
                request_id="survivor"),
    ])}
    assert engine.pool.prefix_stats["hits"] >= 2  # both shared pages
    assert by["bad"].finish_reason == "failed"
    want = solo_greedy(model, params, system + t0, 4)
    assert good0.tokens == want
    assert by["survivor"].tokens == want
    assert by["survivor"].finish_reason in ("eos", "length")
    engine.pool.check_invariants()
    assert engine.pool.is_idle()


# -- CLI -------------------------------------------------------------------


def test_serve_cli_demo(tmp_path):
    import json

    from unicore_tpu.serve.cli import main

    out = tmp_path / "serve.json"
    rc = main([
        "--demo", "--num-requests", "3", "--max-new-tokens", "5",
        "--page-size", "4", "--num-pages", "16", "--max-batch", "3",
        "--prompt-len-range", "3,9", "--json", str(out),
        "--max-waiting", "8", "--drain-timeout", "5",
    ])
    assert rc == 0
    report = json.loads(out.read_text())
    assert len(report["results"]) == 3
    for res in report["results"]:
        assert res["finish_reason"] in ("eos", "length", "capacity")
        assert len(res["tokens"]) == 5
    assert report["stats"]["generated_tokens"] == 15
    # robustness surface: no drain happened, the pool ended clean, and
    # the lifecycle counters rode along at zero
    assert report["drain"] is None and report["pool_clean"] is True
    for key in ("shed", "expired", "quarantined", "capacity_failfast"):
        assert report["stats"][key] == 0, (key, report["stats"])
