"""The serve step's token layout (ISSUE 28, ISSUE 30): every model runs
its dense layers on a FLAT list of the tokens the step carries
(``max_batch`` at width 1, ``ServeEngine.mixed_tokens`` at the prefill
width), sorts them into the ``[max_batch, width]`` rectangle only inside
the mixers that need rows (attention, a recurrent layer's chain), and
runs its head on each row's last token; a model that holds a recurrent
state gets its rows' state slots as one more packed operand.  CPU, the
eager attention path.

The oracle is the one of ``test_serve.py``: whatever the step's layout,
every request's tokens equal the full-forward decode of that request
alone."""

import dataclasses
import json

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from examples.lm.hybrid import HybridLMModel
from examples.lm.model import TransformerLMModel
from unicore_tpu.analysis.trace_audit import _iter_eqns
from unicore_tpu.serve import Request
from unicore_tpu.serve import engine as engine_mod
from unicore_tpu.serve.engine import ServeEngine, StepCompileError

V, D, H, F, L = 29, 40, 4, 80, 2
PAD = 0
# 6 rows x chunks of 8: the rectangle holds 48 tokens, the flat list 32
ENGINE = dict(num_pages=40, page_size=4, max_batch=6, prefill_chunk=8,
              prefill_token_budget=64)


@pytest.fixture(autouse=True)
def toy_token_list(monkeypatch):
    """The token list cut to the toy's size: 32 tokens, under the
    rectangle's 48 (the engine's own 512 would hand a toy the whole
    rectangle)."""
    monkeypatch.setattr(engine_mod, "MIXED_STEP_TOKENS", 32)


@pytest.fixture(scope="module", params=["rotary", "abs_pos"])
def lm(request):
    rotary = request.param == "rotary"
    model = TransformerLMModel(
        vocab_size=V, padding_idx=PAD, decoder_layers=L,
        decoder_embed_dim=D, decoder_ffn_embed_dim=F,
        decoder_attention_heads=H, max_seq_len=128,
        emb_dropout=0.0, dropout=0.0, attention_dropout=0.0,
        activation_dropout=0.0, rel_pos=False, abs_pos=not rotary,
        rotary=rotary,
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def solo_greedy(model, params, prompt, n_new):
    toks = jnp.asarray(prompt, jnp.int32)[None]
    out = []
    for _ in range(n_new):
        logits = model.apply({"params": params}, toks)
        out.append(int(np.asarray(jnp.argmax(logits[0, -1]))))
        toks = jnp.concatenate(
            [toks, jnp.asarray([[out[-1]]], jnp.int32)], axis=1)
    return out


def prompt_of(rng, n):
    return rng.integers(1, V, n).tolist()


def record_plans(engine):
    """Every dispatch's planned rows as ``(sid, start, m, is_decode)``."""
    plans, real = [], engine._dispatch

    def recording(rows):
        plans.append([(r[0].sid, r[1], r[2], r[4]) for r in rows])
        return real(rows)

    engine._dispatch = recording
    return plans


def drain(engine):
    while engine.serve_step():
        pass
    return {r.request_id: r for r in engine.collect_finished()}


# -- (a) the flat mixed step serves the reference's tokens -------------------


def _several_chunks_in_one_dispatch(model, params, rng):
    eng = ServeEngine(model, params, **ENGINE)
    plans = record_plans(eng)
    prompt = prompt_of(rng, 30)
    got = eng.generate([Request(prompt=prompt, max_new_tokens=4)])[0].tokens
    # 30 tokens fit the list of 32: four rows of ONE prompt, one dispatch
    assert [(s, m) for _, s, m, _ in plans[0]] == [
        (0, 8), (8, 8), (16, 8), (24, 6)]
    assert got == solo_greedy(model, params, prompt, 4)


def _cut_by_the_budget_mid_chunk(model, params, rng):
    eng = ServeEngine(model, params, **ENGINE)
    plans = record_plans(eng)
    first, second = prompt_of(rng, 5), prompt_of(rng, 45)
    eng.submit([Request(prompt=first, max_new_tokens=9, request_id="a")])
    eng.serve_step()
    eng.serve_step()                       # "a" decodes
    eng.submit([Request(prompt=second, max_new_tokens=3, request_id="b")])
    done = drain(eng)
    # a decode row takes one token of the 32: the long prompt gets 31, so
    # its fourth chunk is cut to 7 and continues from 31 in the next step
    cut = next(p for p in plans if len(p) == 5)
    assert [(s, m, d) for _, s, m, d in cut] == [
        (len(first) + 1, 1, True), (0, 8, False), (8, 8, False),
        (16, 8, False), (24, 7, False)]
    after = plans[plans.index(cut) + 1]
    assert [(s, m) for _, s, m, d in after if not d] == [(31, 8), (39, 6)]
    assert done["a"].tokens == solo_greedy(model, params, first, 9)
    assert done["b"].tokens == solo_greedy(model, params, second, 3)


def _decode_rows_beside_prefill_rows(model, params, rng):
    eng = ServeEngine(model, params, **ENGINE)
    plans = record_plans(eng)
    early = [prompt_of(rng, n) for n in (3, 9, 6)]
    late = [prompt_of(rng, n) for n in (17, 11)]
    eng.submit([Request(prompt=p, max_new_tokens=8, request_id=f"e{i}")
                for i, p in enumerate(early)])
    for _ in range(3):
        eng.serve_step()
    eng.submit([Request(prompt=p, max_new_tokens=5, request_id=f"l{i}")
                for i, p in enumerate(late)])
    done = drain(eng)
    mixed = [p for p in plans
             if any(d for *_, d in p) and any(m > 1 for _, _, m, _ in p)]
    assert mixed, plans
    for i, p in enumerate(early):
        assert done[f"e{i}"].tokens == solo_greedy(model, params, p, 8)
    for i, p in enumerate(late):
        assert done[f"l{i}"].tokens == solo_greedy(model, params, p, 5)


def _prefix_hit_starts_past_shared_pages(model, params, rng):
    eng = ServeEngine(model, params, **ENGINE)
    plans = record_plans(eng)
    shared = prompt_of(rng, 21)
    a = shared + prompt_of(rng, 3)
    b = shared + prompt_of(rng, 13)
    got_a = eng.generate([Request(prompt=a, max_new_tokens=3)])[0].tokens
    del plans[:]
    got_b = eng.generate([Request(prompt=b, max_new_tokens=4)])[0].tokens
    assert eng.pool.prefix_stats["hits"] >= 1
    # five whole pages of four are shared: the prompt starts at 20
    assert [(s, m) for _, s, m, _ in plans[0]] == [(20, 8), (28, 6)]
    assert got_a == solo_greedy(model, params, a, 3)
    assert got_b == solo_greedy(model, params, b, 4)


def _a_quarantined_row(model, params, rng):
    prompts = [prompt_of(rng, n) for n in (19, 12, 7)]
    eng = ServeEngine(model, params, poison_requests=["r1"], **ENGINE)
    res = eng.generate([
        Request(prompt=p, max_new_tokens=5, request_id=f"r{i}")
        for i, p in enumerate(prompts)])
    assert res[1].finish_reason == "failed" and res[1].tokens == []
    assert eng.stats["quarantined"] == 1
    for i in (0, 2):
        assert res[i].tokens == solo_greedy(model, params, prompts[i], 5)
    assert eng.pool.is_idle()


def _temp_sampling_with_seeds(model, params, rng):
    """Sampled streams do not depend on the layout: an engine whose
    chunk is ONE token has only the width-1 program (the rectangle is the
    list), and samples the same tokens from the same seeds."""
    prompts = [prompt_of(rng, n) for n in (23, 4, 14)]

    def run(chunk, top_k):
        eng = ServeEngine(model, params, **{**ENGINE, "prefill_chunk": chunk})
        reqs = [Request(prompt=p, max_new_tokens=6, temperature=0.9,
                        top_k=top_k, seed=40 + i)
                for i, p in enumerate(prompts)]
        out = [r.tokens for r in eng.generate(reqs)]
        return out, eng

    for top_k, mode in ((0, "temp"), (5, "topk")):
        flat, eng = run(8, top_k)
        assert (8, mode) in eng._step_fns
        narrow, one = run(1, top_k)
        assert one.serve_step_widths() == (1,)
        assert flat == narrow
        assert flat == run(8, top_k)[0]


FLAT_CASES = {
    "several_chunks_of_one_prompt_in_one_dispatch":
        _several_chunks_in_one_dispatch,
    "a_prompt_cut_by_the_token_budget_mid_chunk": _cut_by_the_budget_mid_chunk,
    "decode_rows_beside_prefill_rows": _decode_rows_beside_prefill_rows,
    "a_prefix_hit_that_starts_past_shared_pages":
        _prefix_hit_starts_past_shared_pages,
    "a_quarantined_row": _a_quarantined_row,
    "temp_sampling_with_seeds": _temp_sampling_with_seeds,
}


@pytest.mark.parametrize("case", sorted(FLAT_CASES))
def test_flat_mixed_step_serves_the_reference_tokens(lm, case):
    model, params = lm
    FLAT_CASES[case](model, params, np.random.default_rng(28))


def test_rows_of_one_sequence_are_consecutive_and_ascending(lm):
    """What ``seq.prefilled = start + m`` and the benchmark harness's row
    rebuilding rely on: within a step a sequence's rows are consecutive
    chunks of ``prefill_chunk`` from its watermark, only the last short."""
    model, params = lm
    eng = ServeEngine(model, params, **ENGINE)
    plans = record_plans(eng)
    rng = np.random.default_rng(3)
    eng.generate([Request(prompt=prompt_of(rng, n), max_new_tokens=2)
                  for n in (50, 9, 33, 20)])
    at = {}
    for plan in plans:
        assert len(plan) <= eng.max_batch
        assert sum(m for _, _, m, _ in plan) <= eng.mixed_tokens
        rows = {}
        for sid, start, m, dec in plan:
            rows.setdefault(sid, []).append((start, m, dec))
        for sid, mine in rows.items():
            for start, m, dec in mine:
                assert start == at.get(sid, 0), (sid, plan)
                at[sid] = start + m
            if not mine[0][2]:
                assert all(m == eng.prefill_chunk for _, m, _ in mine[:-1])


# -- (b) what the mixed program holds --------------------------------------


def dense_tokens(jaxpr):
    """``(tokens, features)`` of every matmul without batch dimensions in
    the program: the product of the left operand's free dimensions, and
    the last dimension of the result.  Attention's batched contractions
    are left out."""
    out = []
    for eqn in _iter_eqns(jaxpr.jaxpr):
        if eqn.primitive.name != "dot_general":
            continue
        (lhs_c, _), (lhs_b, _) = eqn.params["dimension_numbers"]
        if lhs_b:
            continue
        shape = eqn.invars[0].aval.shape
        free = [d for i, d in enumerate(shape) if i not in lhs_c]
        out.append((int(np.prod(free)), eqn.outvars[0].aval.shape[-1]))
    return out


def vocab_rows(jaxpr):
    """Rows of every value of the program whose last axis is the
    vocabulary (the tied head's transposed table apart: a weight)."""
    return [int(np.prod(v.aval.shape[:-1]))
            for eqn in _iter_eqns(jaxpr.jaxpr) for v in eqn.outvars
            if eqn.primitive.name != "transpose"
            and getattr(v.aval, "shape", ()) and v.aval.shape[-1] == V]


def test_mixed_program_runs_dense_layers_on_the_list_and_the_head_on_rows(lm):
    model, params = lm
    eng = ServeEngine(model, params, **ENGINE)
    B, w, N = eng.max_batch, eng.prefill_chunk, eng.mixed_tokens
    assert (B * w, N) == (48, 32)
    arts = eng.trace_step_fns()
    assert sorted(arts) == ["ragged-w1", "ragged-w8"]
    for name, n in (("ragged-w8", N), ("ragged-w1", B)):
        dense = dense_tokens(arts[name]["jaxpr"])
        # in_proj, out_proj, fc1, fc2 per layer, and the head
        assert len(dense) == 4 * L + 1
        assert all(t == n for t, f in dense if f != V), (name, dense)
        assert [t for t, f in dense if f == V] == [B]
        rows = vocab_rows(arts[name]["jaxpr"])
        assert rows and max(rows) == B, (name, rows)


# -- (c) two programs, both met by the harness's warm-up --------------------


def test_two_programs_and_the_warm_up_recipe_meets_both(lm):
    model, params = lm
    eng = ServeEngine(model, params, **ENGINE)
    assert eng.serve_step_widths() == (1, eng.prefill_chunk)
    assert [eng.width_fn(m) for m in range(1, 10)] == [1] + [8] * 8
    rng = np.random.default_rng(5)
    # benchmarks/lib/serve_cell.py warm(): two prompts of chunk + 7, 3 answers
    eng.generate([Request(prompt=prompt_of(rng, eng.prefill_chunk + 7),
                          max_new_tokens=3) for _ in range(2)])
    assert set(eng._step_fns) == {(1, "greedy"), (8, "greedy")}
    # one operand a step beside weights and pool: ONE transfer, whatever
    # the program (and the array the step before handed back, which is
    # on the device already)
    B, W, N = eng.max_batch, eng.table_width, eng.mixed_tokens
    eng._input_capture = lambda key, args: packed.__setitem__(
        key[0], [tuple(a.shape) for a in args[2:]])
    packed = {}
    sizes = {k: f._cache_size() for k, f in eng._step_fns.items()}
    assert sizes == {(1, "greedy"): 1, (8, "greedy"): 1}
    # mixed batches of every planned shape: nothing compiles
    plans = record_plans(eng)
    eng.submit([Request(prompt=prompt_of(rng, 40), max_new_tokens=12)])
    eng.serve_step()
    for n in (1, 2, 9, 31, 50, 8):
        eng.submit([Request(prompt=prompt_of(rng, n), max_new_tokens=4)])
        eng.serve_step()
        eng.serve_step()
    drain(eng)
    shapes = {tuple(sorted(m for _, _, m, _ in p)) for p in plans}
    assert len(shapes) > 8, shapes
    assert set(eng._step_fns) == {(1, "greedy"), (8, "greedy")}
    assert {k: f._cache_size() for k, f in eng._step_fns.items()} == sizes
    assert packed == {1: [(4 * B + B * W + 6 * B,), (B,)],
                      8: [(5 * N + B * W + 6 * B + B * 8,), (B,)]}


def test_mixed_tokens_is_derived_from_chunk_and_rows(lm, monkeypatch):
    model, params = lm

    def tokens(max_batch, chunk):
        return ServeEngine(model, params, num_pages=40, page_size=4,
                           max_batch=max_batch,
                           prefill_chunk=chunk).mixed_tokens

    assert tokens(6, 8) == 32               # the list, whatever the rows
    assert tokens(6, 4) == 6 * 4            # never more than the rectangle
    assert tokens(2, 8) == 2 * 8
    assert tokens(5, 1) == 5
    assert tokens(40, 2) == 40 + 2          # a chunk beside a token for every row
    for max_batch, chunk in ((6, 8), (2, 8), (12, 2), (40, 2), (1, 8)):
        assert tokens(max_batch, chunk) > max_batch
    monkeypatch.undo()                      # the engine's own constant
    assert tokens(32, 128) == 512           # both opt_1.3b cells
    assert tokens(8, 32) == 8 * 32          # a small engine: the rectangle


# -- (d) a recurrent model takes the same flat list --------------------------
# the hybrid of tests/test_serve_hybrid.py: 4 rows x chunks of 16, so the
# rectangle holds 64 tokens and (the fixture above) the list 32


@pytest.fixture(scope="module")
def hybrid():
    from tests.test_serve_hybrid import build

    return build()


def hybrid_engine(hybrid, **kwargs):
    from tests import test_serve_hybrid as th

    model, params = hybrid
    eng = ServeEngine(model, params, prefill_chunk=16, **{**th.POOL, **kwargs})
    assert eng.recurrent
    assert (eng.max_batch * 16, eng.mixed_tokens) == (64, 32)
    return eng


def reference_tokens(params, prompt, tokens):
    from tests import test_serve_hybrid as th

    want = th.reference_logits(params, prompt + tokens)
    return np.argmax(want[len(prompt) - 1:-1], -1).tolist()


# ISSUE 37: ``token_src`` closes the list every step packs: for each cell
# of the token list the row of the step BEFORE that sampled its token
# (that step's output, still on the device, is the program's fourth
# input), or -1 for the token the host wrote
PARENT_OPERANDS = ["tokens", "positions", "page_table", "slot_mapping",
                   "lengths", "last", "seeds", "steps", "temperature",
                   "top_k", "token_src"]


def test_a_recurrent_model_takes_one_packed_operand_and_returns_one_array(
        hybrid):
    from tests import test_serve_hybrid as th

    eng = hybrid_engine(hybrid, poison_requests=["r1"])
    B, W, N = eng.max_batch, eng.table_width, eng.mixed_tokens
    names = {w: [n for n, _ in eng._step_operands(w)] for w in (1, 16)}
    assert names[1] == PARENT_OPERANDS + ["poison", "state_slots"]
    assert names[16] == names[1] + ["rect_token", "token_cell"]
    seen, outs = [], []
    eng._input_capture = lambda key, args: seen.append(
        (key[0], [tuple(a.shape) for a in args[2:]]))
    real = eng._ragged_step_fn

    def spying(width, sampling):
        fn = real(width, sampling)

        def call(*args):
            out = fn(*args)
            outs.append(out)
            return out

        return call

    eng._ragged_step_fn = spying
    rng = np.random.default_rng(2)
    prompts = [th.prompt_of(rng, n) for n in (40, 9, 21)]
    res = eng.generate([Request(prompt=p, max_new_tokens=3,
                                request_id=f"r{i}")
                        for i, p in enumerate(prompts)])
    # ONE operand beside weights and pool, whatever the width: tokens,
    # positions, slot_mapping, token_src (and token_cell) over the list,
    # the table, eight [B] entries with poison and state_slots, the map
    # [B, 16]; and the step before's tokens, which cost no transfer
    assert {w for w, _ in seen} == {1, 16}
    for width, shapes in seen:
        assert shapes == [(4 * B + B * W + 8 * B,) if width == 1 else
                          (5 * N + B * W + 8 * B + B * 16,), (B,)]
    # ... and ONE array back beside the pool: a token a row, -1 where the
    # row's logits were not finite
    assert all(len(out) == 2 and out[0].shape == (B,)
               and out[0].dtype == jnp.int32 for out in outs)
    assert sum(int((np.asarray(out[0]) < 0).sum()) for out in outs) == 1
    assert res[1].finish_reason == "failed" and res[1].tokens == []
    assert eng.stats["quarantined"] == 1
    for i in (0, 2):
        assert res[i].tokens == reference_tokens(
            hybrid[1], prompts[i], res[i].tokens)
    eng.pool.check_invariants()
    assert eng.pool.is_idle()


def test_a_recurrent_models_dense_layers_see_the_list_and_its_head_rows(
        hybrid):
    eng = hybrid_engine(hybrid)
    B, N = eng.max_batch, eng.mixed_tokens
    arts = eng.trace_step_fns()
    assert sorted(arts) == ["ragged-w1", "ragged-w16"]
    # q/k/v/b/a/g/o of a linear layer (6 of them), q/k/v/o of a full one
    # (2), three of every FFN, the head; the rule's own contractions are
    # batched over rows and heads and left out
    for name, n in (("ragged-w16", N), ("ragged-w1", B)):
        dense = dense_tokens(arts[name]["jaxpr"])
        assert len(dense) == 6 * 7 + 2 * 4 + 8 * 3 + 1
        assert dense[-1][0] == B                  # the head: a row a token
        assert {t for t, _ in dense[:-1]} == {n}, (name, dense)
    # nothing dense is left on the max_batch x width rectangle
    assert B * 16 not in {t for t, _ in dense_tokens(
        arts["ragged-w16"]["jaxpr"])}


def test_a_recurrent_model_serves_the_reference_tokens_from_the_list(hybrid):
    """Prompts of several chunks beside decode rows, a chunk cut by the
    token budget, a row starting at position 0 beside a row continuing,
    and empty rows: every request's tokens are the plain reference's."""
    from tests import test_serve_hybrid as th

    eng = hybrid_engine(hybrid)
    plans = record_plans(eng)
    rng = np.random.default_rng(30)
    prompts = {"a": th.prompt_of(rng, 5), "b": th.prompt_of(rng, 40),
               "c": th.prompt_of(rng, 20), "d": th.prompt_of(rng, 19)}
    new = {"a": 14, "b": 4, "c": 5, "d": 3}

    def ask(*ids):
        eng.submit([Request(prompt=prompts[i], max_new_tokens=new[i],
                            request_id=i) for i in ids])

    ask("a")
    eng.serve_step()
    eng.serve_step()                       # "a" decodes from here on
    ask("b", "c")
    eng.serve_step()
    eng.pool.check_invariants()
    # one token of the 32 is a's: b takes a chunk, c is cut to 15
    assert [(s, m, d) for _, s, m, d in plans[-1]] == [
        (len(prompts["a"]) + 1, 1, True), (0, 16, False), (0, 15, False)]
    eng.serve_step()
    assert [(s, m) for _, s, m, d in plans[-1] if not d] == [(16, 16), (15, 5)]
    ask("d")                               # starts at 0 beside b continuing
    eng.serve_step()
    starts = [s for _, s, m, d in plans[-1] if not d]
    assert 0 in starts and any(s > 0 for s in starts), plans[-1]
    while eng.serve_step():
        eng.pool.check_invariants()
    done = {r.request_id: r for r in eng.collect_finished()}
    assert any(len(p) < eng.max_batch for p in plans)      # empty rows
    for plan in plans:                     # one row a sequence a dispatch
        assert len({sid for sid, *_ in plan}) == len(plan)
        assert sum(m for _, _, m, _ in plan) <= eng.mixed_tokens
    for i, prompt in prompts.items():
        assert len(done[i].tokens) == new[i]
        assert done[i].tokens == reference_tokens(
            hybrid[1], prompt, done[i].tokens), i
    st = eng.stats
    assert st["state_resets"] == 4 and st["quarantined"] == 0
    assert st["mixed_tokens_capacity"] == st["mixed_steps"] * 32
    assert eng.pool.is_idle()


def test_linear_attention_mixer_rows_as_rectangle_or_as_list_are_the_same():
    """The mixer alone at a toy width: a fresh chunk, a continuing chunk,
    a decode row and an empty row, handed once as the ``[rows, width]``
    rectangle and once as a flat list with its maps, give the same
    outputs, states and tails."""
    from unicore_tpu.modules.pattern_decoder import LinearAttentionMixer
    from unicore_tpu.serve.attention import PagedMeta

    Dm, Hh, dk, dv, K = 32, 2, 8, 16, 4
    rows, width, slots_n, N = 4, 8, 5, 20
    mixer = LinearAttentionMixer(Dm, Hh, dk, dv, K)
    rng = np.random.default_rng(4)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape).astype(np.float32))
    # (first position, tokens) of each row; row 3 is empty
    spans = [(0, 8), (11, 5), (7, 1), None]
    slots = jnp.asarray([2, 0, 3, slots_n + 3], jnp.int32)
    positions = np.full((rows, width), -1, np.int32)
    rect_token = np.full((rows, width), N, np.int32)
    token_cell = np.zeros(N, np.int32)
    flat_positions = np.full(N, -1, np.int32)
    x_rect = np.zeros((rows, width, Dm), np.float32)
    x_flat = np.asarray(f(N, Dm))          # tokens nobody carries: noise
    at = 0
    for b, span in enumerate(spans):
        if span is None:
            continue
        start, m = span
        positions[b, :m] = start + np.arange(m)
        rect_token[b, :m] = at + np.arange(m)
        token_cell[at:at + m] = b * width + np.arange(m)
        flat_positions[at:at + m] = positions[b, :m]
        x_rect[b, :m] = x_flat[at:at + m]
        at += m

    def meta(**maps):
        return PagedMeta(
            page_table=jnp.zeros((rows, 1), jnp.int32),
            slot_mapping=jnp.zeros((1,), jnp.int32),
            lengths=jnp.zeros((rows,), jnp.int32), page_size=4,
            state_slots=slots, num_state_slots=slots_n, **maps)

    init = mixer.init(jax.random.PRNGKey(0), jnp.zeros((1, 2, Dm)),
                      paged=meta())
    params = jax.tree_util.tree_map(lambda p: f(*p.shape) * 0.3,
                                    init["params"])
    store = {"ssm_state": f(slots_n, Hh, dk, dv),
             "conv_tail": f(slots_n, K - 1, 2 * Hh * dk + Hh * dv)}

    def run(x, pos, **maps):
        return mixer.apply({"params": params, "pagedkv": store}, x,
                           positions=jnp.asarray(pos), paged=meta(**maps),
                           mutable=["pagedkv"])

    out_rect, kept_rect = run(jnp.asarray(x_rect), positions)
    out_flat, kept_flat = run(
        jnp.asarray(x_flat)[None], flat_positions[None],
        rect_token=jnp.asarray(rect_token),
        rect_positions=jnp.asarray(positions),
        token_cell=jnp.asarray(token_cell))
    assert out_rect.shape == (rows, width, Dm)
    assert out_flat.shape == (1, N, Dm)
    assert bool(jnp.isfinite(out_flat).all())
    at = 0
    for b, span in enumerate(spans):
        if span is None:
            continue
        m = span[1]
        np.testing.assert_allclose(out_flat[0, at:at + m], out_rect[b, :m],
                                   atol=1e-5)
        at += m
    for name in ("ssm_state", "conv_tail"):
        np.testing.assert_allclose(kept_flat["pagedkv"][name],
                                   kept_rect["pagedkv"][name], atol=1e-6)
    # the store moved where a row wrote and nowhere else: slot 2 from
    # zeros, 0 and 3 from what they held, 1 and 4 untouched
    after, before = kept_flat["pagedkv"]["ssm_state"], store["ssm_state"]
    for slot, moved in enumerate([True, False, True, True, False]):
        assert bool(jnp.any(after[slot] != before[slot])) == moved, slot


def test_a_model_without_a_state_packs_no_state_slots(lm):
    model, params = lm
    eng = ServeEngine(model, params, **ENGINE)
    assert not eng.recurrent
    assert [n for n, _ in eng._step_operands(1)] == PARENT_OPERANDS
    assert [n for n, _ in eng._step_operands(8)] == PARENT_OPERANDS + [
        "rect_token", "token_cell"]
    poisoned = ServeEngine(model, params, poison_requests=["x"], **ENGINE)
    assert [n for n, _ in poisoned._step_operands(8)] == PARENT_OPERANDS + [
        "poison", "rect_token", "token_cell"]


def test_a_step_launched_ahead_takes_its_decode_tokens_from_the_device(lm):
    """ISSUE 37: with the batch full a step is launched before the tokens
    of the step before are fetched.  Its decode rows' cells of the list
    hold no token: ``token_src`` names the row of that step that samples
    it, and the program's fourth input IS that step's output."""
    model, params = lm
    eng = ServeEngine(model, params, **{**ENGINE, "max_batch": 2})
    seen, outs = [], []
    eng._input_capture = lambda key, args: seen.append(
        (key[0], np.asarray(args[2]), args[3]))
    real = eng._ragged_step_fn

    def spying(width, sampling):
        fn = real(width, sampling)

        def call(*args):
            out = fn(*args)
            outs.append(out[0])
            return out

        return call

    eng._ragged_step_fn = spying
    rng = np.random.default_rng(4)
    prompts = [prompt_of(rng, 5), prompt_of(rng, 7)]
    res = eng.generate([Request(prompt=p, max_new_tokens=5)
                        for p in prompts])
    for p, r in zip(prompts, res):
        assert r.tokens == solo_greedy(model, params, p, 5)
    # two rows, two requests: every step but the first is launched ahead,
    # until the last tokens are known to be the last
    assert eng.stats["steps_run_ahead"] == len(seen) - 1 == 4
    assert [w for w, _, _ in seen] == [8, 1, 1, 1, 1]
    for i, (width, packed, prev) in enumerate(seen):
        o = eng._cut(packed, eng._step_operands(width))
        if i == 0:      # the prompts: the host wrote every token
            assert (o["token_src"] == -1).all()
            assert not np.asarray(prev).any()
            continue
        # row b's one token is the one row b of the step before samples
        assert o["token_src"].tolist() == [0, 1]
        assert o["tokens"].tolist() == [[0, 0]]
        assert o["steps"].tolist() == [i, i]        # the sampling index
        assert prev is outs[i - 1]
    # still one program a width: the fourth input has one shape
    assert {k: f._cache_size() for k, f in eng._step_fns.items()} == {
        (1, "greedy"): 1, (8, "greedy"): 1}


# -- (f) the engine owns the last-token contract ----------------------------


class AllTokenLogits(nn.Module):
    """A served model that never heard of ``PagedMeta.last_token``: it
    returns logits for every token it was handed."""
    inner: nn.Module

    max_seq_len = property(lambda self: self.inner.max_seq_len)
    padding_idx = property(lambda self: self.inner.padding_idx)

    @nn.compact
    def __call__(self, src_tokens, paged=None, **kwargs):
        if paged is not None:
            paged = dataclasses.replace(paged, last_token=None)
        return self.inner(src_tokens, paged=paged, **kwargs)


def all_full_hybrid():
    model = HybridLMModel(
        vocab_size=V, padding_idx=PAD, layer_types=("full_attention",) * 2,
        decoder_embed_dim=D, decoder_ffn_embed_dim=F,
        decoder_attention_heads=H, max_seq_len=128)
    assert not model.has_recurrent_state
    return model


def lm_ignoring_last_token():
    return AllTokenLogits(TransformerLMModel(
        vocab_size=V, padding_idx=PAD, decoder_layers=L,
        decoder_embed_dim=D, decoder_ffn_embed_dim=F,
        decoder_attention_heads=H, max_seq_len=128, emb_dropout=0.0,
        dropout=0.0, attention_dropout=0.0, activation_dropout=0.0,
        rel_pos=False, abs_pos=False, rotary=True))


OTHER_MODELS = {
    # (model, rows of the widest [*, vocab] value in the mixed program)
    "an_all_full_pattern_decoder": (all_full_hybrid, ENGINE["max_batch"]),
    "a_model_that_ignores_last_token": (lm_ignoring_last_token, 32),
}


@pytest.mark.parametrize("name", sorted(OTHER_MODELS))
@pytest.mark.parametrize("sampling", ["greedy", "temp"])
def test_any_attention_only_model_samples_each_rows_last_token(name, sampling):
    """Whatever the model makes of ``last_token``, a mixed step samples
    row b from the logits of row b's LAST token, not of flat token b."""
    build, head_rows = OTHER_MODELS[name]
    model = build()
    params = model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng(11)
    early = [prompt_of(rng, n) for n in (5, 11)]
    late = [prompt_of(rng, n) for n in (30, 13)]

    def serve(chunk):
        eng = ServeEngine(model, params, **{**ENGINE, "prefill_chunk": chunk})
        temp = dict(temperature=0.8) if sampling == "temp" else {}
        eng.submit([Request(prompt=p, max_new_tokens=7, request_id=f"e{i}",
                            seed=70 + i, **temp)
                    for i, p in enumerate(early)])
        eng.serve_step()
        eng.serve_step()
        eng.submit([Request(prompt=p, max_new_tokens=4, request_id=f"l{i}",
                            seed=80 + i, **temp)
                    for i, p in enumerate(late)])
        return drain(eng), eng

    done, eng = serve(8)
    assert eng.stats["mixed_steps"] >= 2 and eng.stats["quarantined"] == 0
    if sampling == "greedy":
        for i, p in enumerate(early):
            assert done[f"e{i}"].tokens == solo_greedy(model, params, p, 7)
        for i, p in enumerate(late):
            assert done[f"l{i}"].tokens == solo_greedy(model, params, p, 4)
    else:  # the same seeds sample the same tokens from the width-1 program
        narrow, _ = serve(1)
        assert ({k: r.tokens for k, r in done.items()}
                == {k: r.tokens for k, r in narrow.items()})
    rows = vocab_rows(eng.trace_step_fns(widths=(8,))["ragged-w8"]["jaxpr"])
    assert max(rows) == head_rows


def test_logits_of_another_shape_fail_the_first_dispatch(lm):
    class Rectangle(nn.Module):
        inner: nn.Module
        max_seq_len = property(lambda self: self.inner.max_seq_len)
        padding_idx = property(lambda self: self.inner.padding_idx)

        @nn.compact
        def __call__(self, src_tokens, paged=None, **kwargs):
            out = self.inner(src_tokens, paged=paged, **kwargs)
            return out if paged is None else out.reshape(
                (2, -1) + out.shape[2:])

    model, params = lm
    eng = ServeEngine(Rectangle(model), {"inner": params}, **ENGINE)
    with pytest.raises(StepCompileError) as err:
        eng.generate([Request(prompt=[3, 4, 5, 6, 7], max_new_tokens=2)])
    assert "expected [1, 6, vocab]" in str(err.value.__cause__)


# -- (e) the fill of the mixed program ---------------------------------------


def test_mixed_step_counters_reach_the_snapshot(lm):
    model, params = lm
    eng = ServeEngine(model, params, **ENGINE)
    snap = eng.load_snapshot()
    assert (snap["mixed_steps"], snap["mixed_tokens_carried"],
            snap["mixed_tokens_capacity"]) == (0, 0, 0)
    plans = record_plans(eng)
    rng = np.random.default_rng(9)
    eng.generate([Request(prompt=prompt_of(rng, n), max_new_tokens=4)
                  for n in (50, 3, 12)])
    mixed = [p for p in plans if max(m for _, _, m, _ in p) > 1]
    st = eng.stats
    assert st["mixed_steps"] == len(mixed) > 1
    assert st["mixed_tokens_carried"] == sum(
        m for p in mixed for _, _, m, _ in p)
    assert st["mixed_tokens_capacity"] == len(mixed) * eng.mixed_tokens
    assert 0 < st["mixed_tokens_carried"] <= st["mixed_tokens_capacity"]
    snap = eng.load_snapshot()
    for key in ("mixed_steps", "mixed_tokens_carried",
                "mixed_tokens_capacity"):
        assert snap[key] == st[key] and isinstance(snap[key], int)


def test_mixed_step_counters_reach_the_json_report(tmp_path):
    from unicore_tpu.serve.cli import main

    out = tmp_path / "serve.json"
    assert main([
        "--demo", "--num-requests", "3", "--max-new-tokens", "4",
        "--page-size", "4", "--num-pages", "24", "--max-batch", "3",
        "--prompt-len-range", "5,19", "--prefill-chunk", "4",
        "--json", str(out)]) == 0
    stats = json.loads(out.read_text())["stats"]
    assert stats["mixed_steps"] >= 1
    assert (0 < stats["mixed_tokens_carried"]
            <= stats["mixed_tokens_capacity"])
    assert stats["mixed_tokens_capacity"] % stats["mixed_steps"] == 0
