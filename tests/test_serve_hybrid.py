"""A decoder with recurrent (gated-delta-rule) layers beside paged full
attention through ``ServeEngine``, at a small size (two periods of
``linear, linear, linear, full``, width 64, 2 heads), on seeded weights
drawn the way the benchmark draws them.

The oracle is the benchmark's plain reference
(``benchmarks/reference/olmo_hybrid_lm.py``): one full causal pass, the
rule computed token by token, nothing shared with the program.  The
engine's logits are read where it samples from them, so what is compared
went through chunked prefill, the page pool AND the state store.

Tolerances, each with its reason:

- ``TOL = 2e-4`` on a logit (logits here are of order 1).  Both sides
  compute in float32 on the CPU; they differ in the ORDER of the sums
  (chunked rule against token-by-token, paged attention against blocks of
  queries), and the state carries that rounding through every later
  token.  The widest gap seen over the seeds below is 2e-5; the limit is
  ten times that.
- a state zeroed once at a chunk boundary moves a logit by 5e-2 and
  more, 250 times the tolerance: a dropped state cannot hide inside it.
"""

import importlib
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import serve_cell, weights
from benchmarks.reference import olmo_hybrid_lm as reference
from examples.lm.hybrid import HybridLMModel, parse_layer_types
from unicore_tpu.ops import backend
from unicore_tpu.ops.gated_delta_rule import (
    gated_delta_rule, gated_delta_step, short_conv,
)
from unicore_tpu.serve import PagedKVPool, PoolExhausted, Request
from unicore_tpu.serve.engine import ServeEngine

# the module: ``unicore_tpu.ops`` exports the function under the same name
gdr = importlib.import_module("unicore_tpu.ops.gated_delta_rule")

V, D, F, H = 128, 64, 128, 2
DK, DV = 24, 48
LIN, FULL = "linear_attention", "full_attention"
TOL = 2e-4
POOL = dict(num_pages=40, page_size=8, max_batch=4, prefill_token_budget=64)


def build(seed=7):
    model = HybridLMModel(
        vocab_size=V, padding_idx=1, layer_types=(LIN, LIN, LIN, FULL) * 2,
        decoder_embed_dim=D, decoder_ffn_embed_dim=F,
        decoder_attention_heads=H, linear_num_heads=H,
        linear_key_head_dim=DK, linear_value_head_dim=DV, max_seq_len=256)
    params = weights.make(serve_cell.abstract_params(model), seed,
                          scales={"A_log": 200, "conv_kernel": 25})
    return model, params


@pytest.fixture(scope="module")
def lm():
    return build()


def reference_logits(params, tokens):
    return np.asarray(reference.forward(
        weights.as_dict(params), jnp.asarray(tokens, jnp.int32), heads=H,
        linear_heads=H, linear_key_dim=DK, linear_value_dim=DV))


class Tap:
    """Record the logits every dispatch samples from (row 0: the tests
    that use it run one request at a time)."""

    def __init__(self, monkeypatch):
        self.rows = []
        real = ServeEngine._pick_tokens

        def tapped(logits, *args):
            jax.debug.callback(lambda x: self.rows.append(np.asarray(x[0])),
                               logits)
            return real(logits, *args)

        monkeypatch.setattr(ServeEngine, "_pick_tokens", staticmethod(tapped))

    def take(self):
        jax.effects_barrier()
        rows, self.rows = self.rows, []
        return np.stack(rows)


def served_logits(engine, tap, prompt, n_new):
    """Tokens and the sampled-from logits of one request served alone:
    one row per dispatch, at positions chunk-1, 2*chunk-1, ..., P-1 of
    the prompt and then one per decoded token."""
    res = engine.generate([Request(prompt=prompt, max_new_tokens=n_new)])[0]
    engine.pool.check_invariants()
    chunk = engine.prefill_chunk
    at = list(range(chunk - 1, len(prompt) - 1, chunk)) + [len(prompt) - 1]
    at += list(range(len(prompt), len(prompt) + n_new - 1))
    return res.tokens, at, tap.take()


def prompt_of(rng, n):
    return rng.integers(4, V, n).tolist()


# -- the ops ------------------------------------------------------------


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_the_chunked_rule_is_the_one_step_rule_repeated(chunk, monkeypatch):
    monkeypatch.setattr(gdr, "CHUNK", chunk)
    rng = np.random.default_rng(0)
    B, T, Hh, dk, dv = 2, 37, 3, 8, 16
    f = lambda *s: rng.normal(size=s).astype(np.float32)
    q, k, v = f(B, T, Hh, dk), f(B, T, Hh, dk), f(B, T, Hh, dv)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -0.5 * np.exp(2 * f(B, T, Hh))     # decays from ~1 to ~0 a token
    beta = 2 / (1 + np.exp(-f(B, T, Hh)))  # (0, 2): negative eigenvalues too
    S = S0 = jnp.asarray(f(B, Hh, dk, dv))
    outs = []
    for t in range(T):
        o, S = gated_delta_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                beta[:, t], S)
        outs.append(o)
    o2, S2 = gated_delta_rule(q, k, v, g, beta, S0)
    # float32 sums in another order; values are of order 10
    np.testing.assert_allclose(o2, jnp.stack(outs, 1), atol=2e-4)
    np.testing.assert_allclose(S2, S, atol=2e-5)


def test_a_padded_column_changes_neither_state_nor_tail(monkeypatch):
    monkeypatch.setattr(gdr, "CHUNK", 4)
    rng = np.random.default_rng(1)
    B, T, Hh, dk, dv, C = 2, 6, 2, 4, 8, 5
    f = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))
    q, k, v, S0 = f(B, T, Hh, dk), f(B, T, Hh, dk), f(B, T, Hh, dv), \
        f(B, Hh, dk, dv)
    real = jnp.asarray([[1] * 4 + [0] * 2, [0] * 6], bool)  # row 1 is empty
    g = jnp.where(real[..., None], -jnp.abs(f(B, T, Hh)), 0.0)
    beta = jnp.where(real[..., None], jnp.full((B, T, Hh), 1.3), 0.0)
    _, S = gated_delta_rule(q, k, v, g, beta, S0)
    _, S4 = gated_delta_rule(q[:, :4], k[:, :4], v[:, :4], g[:, :4],
                             beta[:, :4], S0)
    np.testing.assert_allclose(S[0], S4[0], atol=1e-6)
    np.testing.assert_array_equal(S[1], S0[1])
    x, w, tail = f(B, T, C), f(4, C), f(B, 3, C)
    y, new = short_conv(x, w, tail, jnp.asarray([4, 0], jnp.int32))
    np.testing.assert_array_equal(new[0], x[0, 1:4])   # the last 3 REAL
    np.testing.assert_array_equal(new[1], tail[1])     # an empty row: as was
    # the current token is tap 3, the oldest of the tail tap 0
    want = (tail[0, 0] * w[0] + tail[0, 1] * w[1] + tail[0, 2] * w[2]
            + x[0, 0] * w[3])
    np.testing.assert_allclose(y[0, 0], want, rtol=1e-6)


def test_the_op_is_in_the_dispatch_report(lm):
    model, params = lm
    model.apply({"params": params}, jnp.zeros((1, 5), jnp.int32))
    seen = backend.dispatch_report()["gated_delta_rule"]
    assert seen and set(seen.values()) == {"reference"}


@pytest.mark.parametrize("dtype,want", [
    (jnp.float32, jax.lax.Precision.HIGH), (jnp.bfloat16, None)])
def test_float32_weights_are_multiplied_in_float32(lm, dtype, want):
    """Every projection, the FFN and the head: float32 operands at HIGH
    (a TPU's default would round them to bfloat16 first), bfloat16 weights
    and activations in the one exact pass they need.  Read off the jaxpr:
    the CPU multiplies float32 in float32 whatever it is told."""
    model, params = lm
    params = jax.tree_util.tree_map(lambda x: x.astype(dtype), params)
    jaxpr = jax.make_jaxpr(lambda p, t: model.apply({"params": p}, t))(
        params, jnp.zeros((1, 5), jnp.int32))
    seen = {}

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "dot_general":
                rhs = eqn.invars[1].aval
                if rhs.ndim == 2:    # x @ kernel; the rule's are batched
                    pr = eqn.params["precision"]
                    seen[rhs.shape] = None if pr is None else pr[0]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    # q/k, v/g, o of a linear layer, the full layer's four, a/b, the FFN's
    # three, the head
    assert {(D, H * DK), (D, H * DV), (H * DV, D), (D, D), (D, H), (D, F),
            (F, D), (D, V)} <= set(seen)
    by_activation = {k: v for k, v in seen.items() if k != (D, H)}
    assert set(by_activation.values()) == {want}
    # a_proj / b_proj read the float32 copy of x whatever the weights
    assert seen[(D, H)] == jax.lax.Precision.HIGH


def test_layer_types_patterns_are_parsed():
    assert parse_layer_types("lllf") == (LIN, LIN, LIN, FULL)
    assert parse_layer_types("linear_attention,full_attention") == (LIN, FULL)


# -- the state store ----------------------------------------------------


def test_a_state_slot_lives_and_dies_with_its_sequences_pages():
    pool = PagedKVPool(num_pages=16, page_size=4, prefix_cache=False,
                       state_slots=2)
    pool.alloc("a", 5)
    pool.alloc("b", 3)
    pool.check_invariants()
    assert {pool.state_slot("a"), pool.state_slot("b")} == {0, 1}
    assert not pool.can_alloc(1)          # pages there are, a slot there is not
    with pytest.raises(PoolExhausted, match="state slot"):
        pool.alloc("c", 1)
    pool.check_invariants()               # the refused alloc took nothing
    slot = pool.state_slot("a")
    pool.free("a")
    pool.check_invariants()
    with pytest.raises(KeyError):
        pool.state_slot("a")
    pool.alloc("c", 9)
    assert pool.state_slot("c") == slot   # the freed slot is handed out again
    pool.free("b")
    pool.free("c")
    pool.check_invariants()
    assert pool.is_idle() and pool.state_stats == {"taken": 3, "peak": 2}


def test_check_invariants_sees_a_leaked_slot():
    pool = PagedKVPool(num_pages=8, page_size=4, state_slots=2)
    pool.alloc("a", 3)
    pool._state_free.pop()                # a slot nobody holds and nobody can take
    with pytest.raises(AssertionError, match="leaked"):
        pool.check_invariants()


def test_a_pool_without_state_slots_is_as_it_was():
    pool = PagedKVPool(num_pages=8, page_size=4)
    pool.alloc("a", 3)
    pool.check_invariants()
    assert pool.num_state_slots == 0 and pool.state_stats["taken"] == 0
    with pytest.raises(KeyError):
        pool.state_slot("a")


# -- the engine against the reference ------------------------------------


@pytest.mark.parametrize("seed", [7, 11])
def test_engine_logits_through_both_caches_match_the_references_one_pass(
        seed, monkeypatch):
    model, params = build(seed)
    tap = Tap(monkeypatch)
    eng = ServeEngine(model, params, prefill_chunk=16, **POOL)
    prompt = prompt_of(np.random.default_rng(seed), 45)
    tokens, at, got = served_logits(eng, tap, prompt, 12)
    want = reference_logits(params, prompt + tokens)
    assert np.abs(got - want[at]).max() < TOL
    assert tokens == np.argmax(want[len(prompt) - 1:-1], -1).tolist()
    assert eng.stats["state_resets"] == 1 and eng.stats["state_slots_peak"] == 1
    assert eng.pool.is_idle()


def test_one_chunk_or_several_dispatches_give_the_same_logits(
        lm, monkeypatch):
    model, params = lm
    tap = Tap(monkeypatch)
    prompt = prompt_of(np.random.default_rng(3), 45)
    want = reference_logits(params, prompt)[-1]
    for chunk in (64, 16, 5):   # one dispatch, three, nine
        eng = ServeEngine(model, params, prefill_chunk=chunk, **POOL)
        _, at, got = served_logits(eng, tap, prompt, 1)
        assert at[-1] == len(prompt) - 1
        assert np.abs(got[-1] - want).max() < TOL, chunk


def test_a_recurrent_model_gets_one_row_a_sequence_a_dispatch(lm):
    """The row-packing rule: the paged layers could take several chunks
    of one prompt in one dispatch (each layer's scatter lands before its
    gather), the recurrent layers cannot (chunk k needs the state chunk
    k-1 leaves), so the planner gives such a model one row a sequence."""
    model, params = lm
    eng = ServeEngine(model, params, prefill_chunk=8, **POOL)
    rng = np.random.default_rng(5)
    seqs = eng.submit([Request(prompt=prompt_of(rng, n), max_new_tokens=2)
                       for n in (40, 9)])
    eng.scheduler.admit()
    rows = eng._plan_rows(eng.scheduler.prepare_decode())
    assert [(r[0].sid, r[1], r[2]) for r in rows] == [
        (seqs[0].sid, 0, 8), (seqs[1].sid, 0, 8)]
    # the same plan for a model without recurrent layers soaks the rows
    eng.recurrent = False
    packed = eng._plan_rows(eng.scheduler.running)
    assert len(packed) == eng.max_batch
    assert sum(1 for r in packed if r[0] is seqs[0]) == 2


def test_several_rows_of_one_prompt_in_one_dispatch_would_be_wrong(
        lm, monkeypatch):
    """What the rule above prevents: with the planner told the model is
    not recurrent, the rows of one prompt all start from the state the
    store held before the dispatch, and the logits are off by far more
    than the tolerance."""
    model, params = lm
    tap = Tap(monkeypatch)
    prompt = prompt_of(np.random.default_rng(3), 45)
    want = reference_logits(params, prompt)[-1]
    eng = ServeEngine(model, params, prefill_chunk=16, **POOL)
    real = eng._plan_rows

    def packing(seqs):
        eng.recurrent = False
        try:
            return real(seqs)
        finally:
            eng.recurrent = True

    eng._plan_rows = packing
    # rows of one sequence share a state slot: take the uniqueness promise
    # off the scatter for this demonstration by keeping the LAST row's slot
    real_dispatch = eng._dispatch
    eng._dispatch = lambda rows: real_dispatch(rows[-1:]) or None
    eng.generate([Request(prompt=prompt, max_new_tokens=1)])
    got = tap.take()[-1]
    assert np.abs(got - want).max() > 50 * TOL


def test_zeroing_the_state_at_a_chunk_boundary_breaks_the_comparison(
        lm, monkeypatch):
    model, params = lm
    tap = Tap(monkeypatch)
    prompt = prompt_of(np.random.default_rng(3), 45)
    want = reference_logits(params, prompt)[-1]
    eng = ServeEngine(model, params, prefill_chunk=16, **POOL)
    eng.submit([Request(prompt=prompt, max_new_tokens=1)])
    eng.serve_step()                      # positions 0..15
    eng.serve_step()                      # positions 16..31
    flat, tree = jax.tree_util.tree_flatten_with_path(eng.pages)
    eng.pages = jax.tree_util.tree_unflatten(tree, [
        leaf * 0 if "ssm_state" in jax.tree_util.keystr(path) else leaf
        for path, leaf in flat])
    while eng.serve_step():
        pass
    got = tap.take()[-1]
    gap = np.abs(got - want).max()
    assert gap > 250 * TOL, gap


def test_a_document_asked_twice_gives_the_logits_of_asking_it_cold(
        lm, monkeypatch, caplog):
    """Prefix hits are refused for a model with recurrent layers: the
    second ask of the same document prefills from position 0 again."""
    model, params = lm
    tap = Tap(monkeypatch)
    with caplog.at_level("WARNING", logger="unicore_tpu.serve.engine"):
        eng = ServeEngine(model, params, prefill_chunk=16, prefix_cache=True,
                          **POOL)
    assert sum("prefix cache REFUSED" in r.message
               for r in caplog.records) == 1
    assert eng.prefix_cache_refused and not eng.pool.prefix_cache
    assert eng.load_snapshot()["prefix_cache_refused"] is True
    prompt = prompt_of(np.random.default_rng(9), 40)   # five full pages
    _, _, cold = served_logits(eng, tap, prompt, 3)
    _, _, again = served_logits(eng, tap, prompt, 3)
    np.testing.assert_array_equal(again, cold)
    assert eng.pool.prefix_stats["hits"] == 0
    assert eng.pool.cached_tokens(0) == 0
    assert eng.stats["state_resets"] == 2
    want = reference_logits(params, prompt)[-1]
    assert np.abs(again[2] - want).max() < TOL      # rows 0, 1: chunk ends


def test_a_model_without_recurrent_layers_keeps_its_prefix_cache():
    from examples.lm.model import TransformerLMModel

    model = TransformerLMModel(
        vocab_size=29, padding_idx=0, decoder_layers=1, decoder_embed_dim=16,
        decoder_ffn_embed_dim=32, decoder_attention_heads=2, max_seq_len=32,
        emb_dropout=0.0, dropout=0.0, attention_dropout=0.0,
        rel_pos=False, abs_pos=False, rotary=True)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    eng = ServeEngine(model, params, num_pages=8, page_size=4, max_batch=2)
    assert not eng.recurrent and not eng.prefix_cache_refused
    assert eng.pool.prefix_cache and eng.pool.num_state_slots == 0
    assert eng.load_snapshot()["prefix_cache_refused"] is False


# -- preemption, adoption, drain ------------------------------------------


def _requests(rng, n=6):
    return [Request(prompt=prompt_of(rng, int(rng.integers(5, 40))),
                    max_new_tokens=int(rng.integers(3, 10)),
                    request_id=f"r{i}") for i in range(n)]


@pytest.fixture(scope="module")
def undisturbed(lm):
    model, params = lm
    eng = ServeEngine(model, params, prefill_chunk=8, **POOL)
    reqs = _requests(np.random.default_rng(21))
    return reqs, [r.tokens for r in eng.generate(reqs)]


@pytest.mark.parametrize("chaos_seed", [1, 2, 3])
def test_chaos_preemption_reproduces_the_undisturbed_tokens(
        lm, undisturbed, chaos_seed):
    """A preempted sequence loses its pages AND its state slot; on
    re-admission it prefills prompt + generated from position 0, from a
    zeroed state, and continues token-identically."""
    model, params = lm
    reqs, want = undisturbed
    eng = ServeEngine(model, params, prefill_chunk=8, chaos_rate=0.3,
                      chaos_rng=random.Random(chaos_seed), **POOL)
    eng.submit(reqs)
    while eng.serve_step():
        eng.pool.check_invariants()       # no slot is ever leaked
    got = {r.request_id: r.tokens for r in eng.collect_finished()}
    assert [got[r.request_id] for r in reqs] == want
    assert eng.scheduler.num_evictions >= 1
    # every (re-)admission that reached a dispatch started from zero (one
    # preempted in the step that admitted it never took a row)
    assert (len(reqs) < eng.stats["state_resets"]
            <= len(reqs) + eng.scheduler.num_evictions)
    assert eng.pool.is_idle() and not eng.pool._state_of


def test_adopt_continues_a_salvaged_stream_from_zero_state(lm, undisturbed):
    model, params = lm
    reqs, want = undisturbed
    dying = ServeEngine(model, params, prefill_chunk=8, **POOL)
    dying.submit(reqs)
    for _ in range(6):
        dying.serve_step()
    salvaged = dying.reclaim_waiting(include_running=True)
    dying.pool.check_invariants()
    assert dying.pool.is_idle() and not dying.pool._state_of
    assert any(generated for _, generated in salvaged)
    heir = ServeEngine(model, params, prefill_chunk=8, **POOL)
    for req, generated in salvaged:
        heir.adopt(req, generated=generated)
    while heir.serve_step():
        heir.pool.check_invariants()
    got = {r.request_id: r.tokens for r in heir.collect_finished()}
    assert [got[r.request_id] for r in reqs] == want


def test_expiry_and_drain_free_the_state_slots(lm):
    model, params = lm
    now = [0.0]
    eng = ServeEngine(model, params, prefill_chunk=8, clock=lambda: now[0],
                      drain_timeout=0.0, **POOL)
    rng = np.random.default_rng(4)
    eng.submit([Request(prompt=prompt_of(rng, 20), max_new_tokens=50,
                        deadline_ms=1000.0),
                Request(prompt=prompt_of(rng, 20), max_new_tokens=50)])
    eng.serve_step()
    assert len(eng.pool._state_of) == 2
    now[0] = 2.0                          # the first request's deadline blows
    eng.serve_step()
    eng.pool.check_invariants()
    assert len(eng.pool._state_of) == 1
    eng.request_drain()
    now[0] = 3.0
    while eng.serve_step():
        pass
    eng.pool.check_invariants()
    assert eng.pool.is_idle() and not eng.pool._state_of
    assert eng.drain_report["pool_idle"] is True


# -- the compile surface ---------------------------------------------------


def test_trace_step_fns_and_swap_weights_keep_working(lm):
    model, params = lm
    eng = ServeEngine(model, params, prefill_chunk=16, **POOL)
    arts = eng.trace_step_fns()
    assert sorted(arts) == ["ragged-w1", "ragged-w16"]
    # the state store is donated with the pages: one tree, argument 1
    for art in arts.values():
        assert "jaxpr" in art and art["lowered"] is not None
    rng = np.random.default_rng(2)
    prompt = prompt_of(rng, 30)
    first = eng.generate([Request(prompt=prompt, max_new_tokens=4)])[0].tokens
    assert set(eng._step_fns) == {(16, "greedy"), (1, "greedy")}
    other = build(8)[1]
    eng.swap_weights(other)
    second = eng.generate([Request(prompt=prompt, max_new_tokens=4)])[0].tokens
    assert set(eng._step_fns) == {(16, "greedy"), (1, "greedy")}  # no recompile
    want = reference_logits(other, prompt + second)
    assert second == np.argmax(want[len(prompt) - 1:-1], -1).tolist()
    assert first != second
