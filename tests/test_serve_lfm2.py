"""A decoder of gated short-convolution and grouped-query attention
layers with sparse experts (``lfm2_moe_lm``) through ``ServeEngine``, at a
small size (the benchmark's pattern ``conv, full, conv, conv, conv``: one
dense layer and one period; width 64, 4 query heads over 2 K/V heads, 8
experts of which 2 a token), on seeded weights drawn the way the
benchmark draws them.

The oracle is the benchmark's plain reference
(``benchmarks/reference/lfm2_moe_lm.py``): one full causal pass, every
expert applied to every token with a dense weight, nothing shared with
the program.  The engine's logits are read where it samples from them, so
what is compared went through chunked prefill, the page pool, the conv
tails' slots, the rotary positions of the step and the expert dispatch.

Tolerances, each with its reason:

- ``TOL = 2e-4`` on a logit (logits here are of order 1).  Both sides
  compute in float32 on the CPU and differ in the ORDER of the sums
  (paged attention against blocks of queries, a token's experts summed
  four at a time against 8 with zeros); the widest gap seen over the
  seeds below is 3e-6.  A dropped tail moves a logit by 1e-2 and more, a
  wrong expert by 1e-1: neither hides inside it.  With seeded random
  weights a token whose selection scores tie to the last bit could flip
  its expert between the two sides; none does on these seeds.
- ``BF16_TOL = 0.15`` with bfloat16 weights and pools, against the SAME
  program's one full pass in bfloat16 (the materialised path): the two
  round differently at every layer (8 mantissa bits on values of order
  1), and a dropped tail or a stale page would still move a logit by
  more.
"""

import random
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import serve_cell, weights
from benchmarks.reference import lfm2_moe_lm as reference
from examples.lm.lfm2_moe import Lfm2MoeLMModel, parse_layer_types
from test_serve_hybrid import Tap, served_logits
from unicore_tpu.serve import Request
from unicore_tpu.serve import attention as serve_attention
from unicore_tpu.serve.engine import ServeEngine

V, D, F, H, KV = 128, 64, 96, 4, 2
E, K, FE = 8, 2, 32
TOL, BF16_TOL = 2e-4, 0.15
POOL = dict(num_pages=40, page_size=8, max_batch=4, prefill_token_budget=64)
# the benchmark's scales, and every matrix 6 times wider: at width 64 a
# draw from N(0, 0.02^2) shrinks what it multiplies sixfold (0.16 a layer
# against 0.9 at the published 2,048), the residual stream stays the
# token's own embedding and the tied head echoes the input whatever the
# layers do
SCALES = {"expert_bias": 2.5, "conv_kernel": 25, "kernel": 6, "router": 6,
          "w1": 6, "w3": 6, "w2": 6}


def build(seed=7, kv_heads=KV, dtype=None):
    model = Lfm2MoeLMModel(
        vocab_size=V, padding_idx=1, decoder_embed_dim=D,
        decoder_ffn_embed_dim=F, decoder_attention_heads=H,
        decoder_kv_heads=kv_heads, num_experts=E, num_experts_per_tok=K,
        moe_ffn_embed_dim=FE, max_seq_len=256)
    abstract = serve_cell.abstract_params(model)
    if dtype is not None:
        abstract = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, dtype), abstract)
    return model, weights.make(abstract, seed, scales=SCALES)


@pytest.fixture(scope="module")
def lm():
    return build()


def reference_logits(params, tokens, kv_heads=KV):
    return np.asarray(reference.forward(
        weights.as_dict(params), jnp.asarray(tokens, jnp.int32), heads=H,
        kv_heads=kv_heads, top_k=K, theta=1e6))


def prompt_of(rng, n):
    return rng.integers(4, V, n).tolist()


# -- the engine against the reference ------------------------------------


@pytest.mark.parametrize("seed", [7, 11])
def test_engine_logits_match_the_references_one_pass(seed, monkeypatch):
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


def test_bfloat16_weights_serve_from_bfloat16_pools(monkeypatch):
    model, params = build(7, dtype=jnp.bfloat16)
    tap = Tap(monkeypatch)
    eng = ServeEngine(model, params, prefill_chunk=16, **POOL)
    # the pool takes its type from the model's init (float32) whatever
    # the served weights are: hand it bfloat16 pages and tails
    eng.pages = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x,
        eng.pages)
    kinds = {jax.tree_util.keystr(p).split("'")[-2]: leaf.dtype
             for p, leaf in jax.tree_util.tree_flatten_with_path(eng.pages)[0]}
    assert kinds == {"k_pages": jnp.bfloat16, "v_pages": jnp.bfloat16,
                     "conv_tail": jnp.bfloat16, "moe_load": jnp.int32,
                     "moe_touched": jnp.int32}
    prompt = prompt_of(np.random.default_rng(7), 45)
    tokens, at, got = served_logits(eng, tap, prompt, 6)
    assert got.dtype == jnp.bfloat16
    full = model.apply({"params": params},
                       jnp.asarray([prompt + tokens], jnp.int32))[0]
    gap = np.abs(got.astype(np.float32)
                 - np.asarray(full, np.float32)[at]).max()
    assert gap < BF16_TOL


def test_a_chunk_edge_goes_through_the_conv_tail(lm, monkeypatch):
    """One dispatch, three, nine: a chunk's first two tokens convolve
    with the two ``z`` the chunk before left in the sequence's slot."""
    model, params = lm
    tap = Tap(monkeypatch)
    prompt = prompt_of(np.random.default_rng(3), 45)
    want = reference_logits(params, prompt)[-1]
    for chunk in (64, 16, 5):
        eng = ServeEngine(model, params, prefill_chunk=chunk, **POOL)
        _, at, got = served_logits(eng, tap, prompt, 1)
        assert at[-1] == len(prompt) - 1
        assert np.abs(got[-1] - want).max() < TOL, chunk


def test_dropping_the_tails_between_steps_breaks_the_comparison(
        lm, monkeypatch):
    model, params = lm
    real = ServeEngine._dispatch

    def dropping(self, rows):
        real(self, rows)
        flat, tree = jax.tree_util.tree_flatten_with_path(self.pages)
        self.pages = jax.tree_util.tree_unflatten(tree, [
            leaf * 0 if "conv_tail" in jax.tree_util.keystr(path) else leaf
            for path, leaf in flat])

    monkeypatch.setattr(ServeEngine, "_dispatch", dropping)
    tap = Tap(monkeypatch)
    eng = ServeEngine(model, params, prefill_chunk=16, **POOL)
    prompt = prompt_of(np.random.default_rng(3), 45)
    tokens, at, got = served_logits(eng, tap, prompt, 4)
    want = reference_logits(params, prompt + tokens)
    assert np.abs(got - want[at]).max() > 50 * TOL


@pytest.mark.parametrize("kv_heads", [4, 2, 1])
def test_grouped_queries_against_the_materialised_path(kv_heads, monkeypatch):
    """g = 1, 2, 4 query heads a K/V head: the paged step (the fold into
    query cells, pages ``kv_heads * 16`` wide) against the model's own
    full pass (K/V repeated, scores materialised) and the reference."""
    model, params = build(5, kv_heads=kv_heads)
    tap = Tap(monkeypatch)
    eng = ServeEngine(model, params, prefill_chunk=16, **POOL)
    widths = {leaf.shape[-1] for p, leaf in
              jax.tree_util.tree_flatten_with_path(eng.pages)[0]
              if "k_pages" in jax.tree_util.keystr(p)}
    assert widths == {kv_heads * (D // H)}
    prompt = prompt_of(np.random.default_rng(kv_heads), 37)
    tokens, at, got = served_logits(eng, tap, prompt, 8)
    seq = prompt + tokens
    full = np.asarray(model.apply({"params": params},
                                  jnp.asarray([seq], jnp.int32))[0])
    assert np.abs(got - full[at]).max() < TOL
    assert np.abs(got - reference_logits(params, seq, kv_heads)[at]).max() < TOL


@pytest.mark.parametrize("g,folds", [(1, False), (2, True), (4, True)])
def test_one_query_head_a_kv_head_folds_nothing(g, folds):
    """With as many K/V heads as query heads ``write_and_attend`` traces
    as it did before grouped queries: nothing is folded."""
    B, T, kv, d, page, slots = 2, 3, 2, 8, 4, 32

    class Var:
        def __init__(self, value):
            self.value = value

    def step(q, k, v, kp, vp):
        meta = serve_attention.PagedMeta(
            page_table=jnp.zeros((B, 2), jnp.int32),
            slot_mapping=jnp.arange(B * T, dtype=jnp.int32),
            lengths=jnp.full((B,), T, jnp.int32), page_size=page)
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        return serve_attention.write_and_attend(
            q, k, v, Var(kp), Var(vp), meta, pos, 1.0)

    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    text = str(jax.make_jaxpr(step)(
        f32(B, T, kv * g, d), f32(B, T, kv, d), f32(B, T, kv, d),
        f32(slots, kv * d), f32(slots, kv * d)))
    # the fold goes through [B, T, kv, g, D]: the only 5-D value there is
    assert bool(re.search(r"f32\[\d+(,\d+){4}\]", text)) == folds


def test_decode_rows_beside_prefill_rows_rotate_at_their_own_positions(lm):
    """A mixed step carries decode rows (one token at position 40-odd)
    beside a prompt's chunk (positions 0-15): every token is rotated at
    its own position, so each request's greedy tokens are those of the
    reference's pass over it alone."""
    model, params = lm
    rng = np.random.default_rng(9)
    eng = ServeEngine(model, params, prefill_chunk=16, **POOL)
    first = Request(prompt=prompt_of(rng, 41), max_new_tokens=14,
                    request_id="a")
    later = [Request(prompt=prompt_of(rng, n), max_new_tokens=5,
                     request_id=f"b{n}") for n in (35, 9)]
    eng.submit([first])
    for _ in range(5):      # the first request is decoding by now
        eng.serve_step()
    mixed0 = eng.stats["mixed_steps"]
    eng.submit(later)
    while eng.serve_step():
        eng.pool.check_invariants()
    assert eng.stats["mixed_steps"] > mixed0
    for res in eng.collect_finished():
        want = reference_logits(params, res.prompt + res.tokens)
        assert res.tokens == np.argmax(
            want[len(res.prompt) - 1:-1], -1).tolist(), res.request_id


# -- the state slot: a tail alone -----------------------------------------


def test_the_state_of_a_sequence_is_a_tail_alone(lm):
    model, params = lm
    assert model.has_recurrent_state
    eng = ServeEngine(model, params, prefill_chunk=16, **POOL)
    assert eng.recurrent and eng.prefix_cache_refused
    assert eng.load_snapshot()["prefix_cache_refused"] is True
    shapes = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(eng.pages)[0]:
        shapes.setdefault(jax.tree_util.keystr(path).split("'")[-2],
                          set()).add(leaf.shape)
    assert shapes == {
        "conv_tail": {(POOL["max_batch"], 2, D)},        # 4 conv layers
        "k_pages": {(40 * 8, KV * (D // H))}, "v_pages": {(40 * 8, KV * 16)},
        "moe_load": {(E,)}, "moe_touched": {()}}
    assert parse_layer_types("cfccc") == tuple(model.layer_types)
    assert not Lfm2MoeLMModel(
        layer_types=("full_attention",) * 2).has_recurrent_state


def test_a_freed_slot_is_reused_from_zeros(lm, monkeypatch):
    """The second request takes the slot (and the pages) the first left
    full: its row starts at position 0, so its tails start from zeros and
    its logits are those of serving it on a fresh engine."""
    model, params = lm
    tap = Tap(monkeypatch)
    rng = np.random.default_rng(13)
    one, two = prompt_of(rng, 45), prompt_of(rng, 21)
    eng = ServeEngine(model, params, prefill_chunk=16, **POOL)
    served_logits(eng, tap, one, 6)
    tokens, at, got = served_logits(eng, tap, two, 6)
    assert eng.pool.state_stats["taken"] == 2
    assert eng.stats["state_slots_peak"] == 1      # the same slot, twice
    want = reference_logits(params, two + tokens)
    assert np.abs(got - want[at]).max() < TOL


def _requests(rng, n=6):
    return [Request(prompt=prompt_of(rng, int(rng.integers(5, 50))),
                    max_new_tokens=int(rng.integers(4, 12)),
                    request_id=f"r{i}") for i in range(n)]


@pytest.fixture(scope="module")
def undisturbed(lm):
    model, params = lm
    eng = ServeEngine(model, params, prefill_chunk=8, **POOL)
    reqs = _requests(np.random.default_rng(21))
    return reqs, [r.tokens for r in eng.generate(reqs)]


@pytest.mark.parametrize("chaos_seed", [1, 2, 3])
def test_preemption_and_resume_reproduce_the_undisturbed_tokens(
        lm, undisturbed, chaos_seed):
    """A preempted sequence loses its pages AND its tails' slot; on
    re-admission it prefills prompt + generated from position 0, from
    zeroed tails, and continues token-identically."""
    model, params = lm
    reqs, want = undisturbed
    eng = ServeEngine(model, params, prefill_chunk=8, chaos_rate=0.3,
                      chaos_rng=random.Random(chaos_seed), **POOL)
    eng.submit(reqs)
    while eng.serve_step():
        eng.pool.check_invariants()
    got = {r.request_id: r.tokens for r in eng.collect_finished()}
    assert [got[r.request_id] for r in reqs] == want
    assert eng.scheduler.num_evictions >= 1
    assert eng.pool.is_idle() and not eng.pool._state_of


# -- the routing counters ---------------------------------------------------


def _host_routing(params, tokens):
    """Per expert layer, the experts the reference's own pieces choose
    for every position of ``tokens``: ``[layers][T, K]``."""
    tree = weights.as_dict(params)
    dec = tree["decoder"]
    x = tree["embed_tokens"]["embedding"][jnp.asarray(tokens)]
    chosen = []
    for i in range(5):
        p = dec[f"layers_{i}"]
        normed = reference.rms_norm(x, p["operator_norm"]["weight"], 1e-5)
        if "conv" in p:
            mixed = reference.short_conv(normed, p["conv"], precision="fp32")
        else:
            mixed = reference.full_attention(
                normed, p["self_attn"], heads=H, kv_heads=KV, theta=1e6,
                eps=1e-5, precision="fp32")
        h = x + mixed
        normed = reference.rms_norm(h, p["ffn_norm"]["weight"], 1e-5)
        ff = p["feed_forward"]
        if "router" in ff:
            scores = jax.nn.sigmoid(jnp.dot(
                normed, ff["router"], precision=jax.lax.Precision.HIGHEST))
            chosen.append(np.asarray(jax.lax.top_k(
                scores + ff["expert_bias"], K)[1]))
            ffn = reference.expert_ffn(normed, ff, top_k=K, scale=1.0,
                                       first_expert=0, precision="fp32")
        else:
            ffn = reference.swiglu(
                normed, ff["gate_proj"]["kernel"], ff["up_proj"]["kernel"],
                ff["down_proj"]["kernel"], "fp32")
        x = h + ffn
    return chosen


def test_the_device_counters_against_a_host_count(lm):
    """One request served alone: every position goes through every
    expert layer once, in dispatches of 16, 16, 13 tokens and then one at
    a time, and the cells of a dispatch nobody carries count nowhere."""
    model, params = lm
    eng = ServeEngine(model, params, prefill_chunk=16, **POOL)
    assert eng.moe_layers == 4 and eng.stats["moe_assignments"] == 0
    assert eng.load_snapshot()["moe_load_max_over_mean"] == 0.0
    prompt = prompt_of(np.random.default_rng(17), 45)
    res = eng.generate([Request(prompt=prompt, max_new_tokens=9)])[0]
    seq = prompt + res.tokens[:-1]        # the last token is never fed
    chosen = _host_routing(params, seq)
    dispatches = [range(0, 16), range(16, 32), range(32, 45)] + [
        range(t, t + 1) for t in range(45, len(seq))]
    got = eng.moe_stats()
    for layer, sel in enumerate(chosen):
        assert got["load"][layer] == np.bincount(
            sel.ravel(), minlength=E).tolist()
        assert got["experts_touched"][layer] == sum(
            len(set(sel[list(d)].ravel())) for d in dispatches)
    assert got["assignments"] == len(seq) * K * 4
    # what came back with the tokens, step by step, is the same count
    assert eng.stats["moe_assignments"] == got["assignments"]
    assert eng.stats["moe_experts_touched"] == sum(got["experts_touched"])
    skew = max(max(l) / (sum(l) / E) for l in got["load"])
    assert got["load_max_over_mean"] == pytest.approx(skew)
    snap = eng.load_snapshot()
    assert snap["moe_assignments"] == got["assignments"]
    assert snap["moe_load_max_over_mean"] == pytest.approx(skew, abs=1e-4)


def test_a_model_without_experts_counts_nothing_and_fetches_as_before():
    from test_serve_hybrid import build as build_hybrid

    model, params = build_hybrid()
    eng = ServeEngine(model, params, prefill_chunk=16, num_pages=40,
                      page_size=8, max_batch=4)
    assert eng.moe_layers == 0 and eng.moe_stats() is None
    out = jax.eval_shape(
        eng._ragged_step_fn(1, "greedy"), eng.params, eng.pages,
        jax.ShapeDtypeStruct(
            (eng._packed_size(eng._step_operands(1)),), jnp.int32),
        jax.ShapeDtypeStruct((eng._out_size(),), jnp.int32))[0]
    assert out.shape == (4,)              # the tokens and nothing behind


def test_trace_step_fns_fetch_the_counts_behind_the_tokens(lm):
    model, params = lm
    eng = ServeEngine(model, params, prefill_chunk=16, **POOL)
    arts = eng.trace_step_fns()
    assert sorted(arts) == ["ragged-w1", "ragged-w16"]
    for art in arts.values():
        toks = art["jaxpr"].out_avals[0]
        assert toks.shape == (POOL["max_batch"] + 2,)
