"""The step in flight (ISSUE 37, engine.py's docstring): a full batch
launches step N+1 before it fetches step N's tokens, and what every
request gets is what a synchronous engine gives.

Each case drives the same requests twice through the same toy decoder:
once with as many rows as requests run at a time (the batch is full, the
engine runs ahead) and once with one row more (a row is always free, so
every step is fetched in the call that launched it).  Token streams,
``finish_reason``s, ``prefix_stats`` and the pool's final occupancy must
be identical; the counters say which of the two engines ran ahead.

Requests are sent the way the benchmark's closed loop sends them: ``ROWS``
at first, and a follow-up the moment one of them is seen to finish.  A
clock, where a case needs one, counts emitted tokens, so both engines
read the same time at the same point of the same stream."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from examples.lm.model import TransformerLMModel
from unicore_tpu.serve import Request
from unicore_tpu.serve.engine import ServeEngine

ROWS = 3          # requests running at a time
V = 128


def _multi_head():
    model = TransformerLMModel(
        vocab_size=V, padding_idx=1, decoder_layers=2,
        decoder_embed_dim=32, decoder_ffn_embed_dim=64,
        decoder_attention_heads=4, max_seq_len=256,
        emb_dropout=0.0, dropout=0.0, attention_dropout=0.0,
        activation_dropout=0.0, rel_pos=False, abs_pos=False, rotary=True)
    # wide draws: a toy at flax's default scale echoes one token for ever
    leaves, tree = jax.tree_util.tree_flatten(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    keys = jax.random.split(jax.random.PRNGKey(3), len(leaves))
    return model, jax.tree_util.tree_unflatten(tree, [
        x if x.ndim < 2 else 0.35 * jax.random.normal(k, x.shape, x.dtype)
        for k, x in zip(keys, leaves)])


def _hybrid():
    from tests import test_serve_hybrid

    return test_serve_hybrid.build()


def _lfm2():
    from tests import test_serve_lfm2

    return test_serve_lfm2.build()


def _latent():
    from tests import test_serve_pangu

    return test_serve_pangu.build()


BUILDERS = {"multi_head": _multi_head, "hybrid": _hybrid, "lfm2": _lfm2,
            "latent": _latent}
_built = {}


@pytest.fixture(params=sorted(BUILDERS))
def lm(request):
    if request.param not in _built:
        _built[request.param] = BUILDERS[request.param]()
    return _built[request.param]


def engine_of(lm, rows, **kw):
    model, params = lm
    kw = {"num_pages": 48, "page_size": 8, "prefill_chunk": 16,
          "prefill_token_budget": 64, **kw}
    return ServeEngine(model, params, max_batch=rows, **kw)


def prompts(n, seed=5, shared=0, longest=40):
    """``n`` prompts of 6-40 tokens; the first ``shared`` tokens are the
    same in all of them (two full pages: a prefix the cache can hit).
    ``longest=16``: one chunk at most, so a prompt is one row whatever
    the rows an engine has, and both engines' steps hold the same rows
    (a case whose clock or trigger counts emitted tokens needs that)."""
    rng = np.random.default_rng(seed)
    head = rng.integers(4, V, shared).tolist()
    return [head + rng.integers(4, V, int(k)).tolist()
            for k in rng.integers(6, longest, n)]


def count_launches(engine):
    """Launches made with a step in flight, counted from outside: a
    ``_dispatch`` entered with one and left with ANOTHER."""
    ahead, real = [], engine._dispatch

    def counting(rows):
        before = engine._in_flight
        try:
            return real(rows)
        finally:
            ahead.append(before is not None
                         and engine._in_flight is not before)

    engine._dispatch = counting
    return ahead


def drive(engine, requests, between=None):
    """The closed loop: ``ROWS`` requests at first, the next one when a
    finished one is collected; ``between(engine, emitted)`` runs after
    every call.  Returns ``{request_id: ServeResult}`` and how many calls
    returned with a step in flight."""
    todo = list(requests)
    engine.submit(todo[:ROWS])
    del todo[:ROWS]
    done, left_in_flight = {}, 0
    while engine.has_work() or todo:
        engine.serve_step()
        left_in_flight += engine._in_flight is not None
        engine.pool.check_invariants()
        for res in engine.collect_finished():
            done[res.request_id] = res
            if todo:
                engine.submit([todo.pop(0)])
        if between is not None:
            between(engine, engine.stats["generated_tokens"])
    assert engine._in_flight is None
    return done, left_in_flight


def both(lm, requests, between=None, setup=None, **kw):
    """The same requests through a full batch and through one with a row
    free; returns both engines and both result maps, after checking that
    they agree on everything a client or an operator could see."""
    out = []
    for rows in (ROWS, ROWS + 1):
        engine = engine_of(lm, rows, **kw)
        if setup is not None:
            setup(engine)
        ahead = count_launches(engine)
        done, left = drive(engine, requests(), between)
        out.append((engine, done, ahead, left))
    (full, got, ahead, left), (free, want, ahead_free, left_free) = out
    assert sorted(got) == sorted(want)
    for rid in want:
        assert got[rid].tokens == want[rid].tokens, rid
        assert got[rid].finish_reason == want[rid].finish_reason, rid
        assert got[rid].evictions == want[rid].evictions, rid
    assert full.pool.prefix_stats == free.pool.prefix_stats
    assert full.pool.occupancy() == free.pool.occupancy()
    assert full.pool.num_free_pages == free.pool.num_free_pages
    for key in ("generated_tokens", "quarantined", "expired", "shed",
                "host_faults", "pool_exhausted_recoveries"):
        assert full.stats[key] == free.stats[key], key
    # the counter is the launches the test counted, and a row always
    # free never leaves a step in flight
    assert full.stats["steps_run_ahead"] == sum(ahead)
    assert full.load_snapshot()["steps_run_ahead"] == sum(ahead)
    assert (free.stats["steps_run_ahead"], sum(ahead_free), left_free,
            free.stats["tokens_overrun"]) == (0, 0, 0, 0)
    return full, free, got, left


def reqs(n=6, new=(9, 14, 7, 11, 8, 12), longest=40):
    def make():
        return [Request(prompt=p, max_new_tokens=new[i % len(new)],
                        request_id=f"r{i}")
                for i, p in enumerate(prompts(n, longest=longest))]
    return make


# -- the cases ---------------------------------------------------------------


def _ends_by_length(lm):
    full, _, got, left = both(lm, reqs())
    assert all(r.finish_reason == "length" for r in got.values())
    # an end by max_new_tokens is known ahead: its row is not launched
    # again, so nothing is ever dropped
    assert full.stats["tokens_overrun"] == 0
    assert full.stats["steps_run_ahead"] > 10 and left > 10


def _eos_mid_run(lm):
    # the streams without an end of sequence tell which token to take
    plain = drive(engine_of(lm, ROWS + 1), reqs(3, new=(14,))())[0]
    tokens = plain["r1"].tokens
    at = next(i for i in range(3, len(tokens))
              if tokens[i] not in tokens[:i])
    eos = tokens[at]

    def make():
        rs = reqs(3, new=(14,))()
        rs[1].eos_id = eos
        return rs

    full, _, got, _ = both(lm, make)
    assert got["r1"].finish_reason == "eos"
    assert got["r1"].tokens == tokens[:at + 1]      # nothing after it
    assert got["r0"].tokens == plain["r0"].tokens
    # its next row was in flight when the token came back
    assert full.stats["tokens_overrun"] == 1
    assert full.load_snapshot()["tokens_overrun"] == 1


def _quarantine_in_flight(lm):
    def poison_from_the_fifth_token(engine):
        engine._poison_row = lambda seq: (
            seq.req.request_id == "r1"
            and len(seq.generated) + seq.in_flight >= 4)

    full, _, got, _ = both(lm, reqs(3, new=(12,)),
                           setup=poison_from_the_fifth_token,
                           poison_requests=["__armed__"])
    assert got["r1"].finish_reason == "failed"
    assert len(got["r1"].tokens) == 4
    assert full.stats["quarantined"] == 1
    assert full.stats["tokens_overrun"] == 1
    assert {got["r0"].finish_reason, got["r2"].finish_reason} == {"length"}


def _deadline_in_flight(lm):
    def make():
        rs = reqs(3, new=(12,), longest=16)()
        rs[2].deadline_ms = 17_500.0     # blown when 18 tokens are out
        return rs

    out = []
    for rows in (ROWS, ROWS + 1):
        box = {}
        engine = engine_of(
            lm, rows,
            clock=lambda box=box: float(
                box["engine"].stats["generated_tokens"]) if box else 0.0)
        box["engine"] = engine
        out.append((engine, drive(engine, make())[0]))
    (full, got), (free, want) = out
    assert {r: (got[r].tokens, got[r].finish_reason) for r in got} == {
        r: (want[r].tokens, want[r].finish_reason) for r in want}
    assert want["r2"].finish_reason == "expired"
    assert 0 < len(want["r2"].tokens) < 12
    assert full.stats["tokens_overrun"] == 1 and full.stats["expired"] == 1
    assert free.stats["steps_run_ahead"] == 0 < full.stats["steps_run_ahead"]
    assert full.pool.num_free_pages == free.pool.num_free_pages


def _preemption_settles_first(lm):
    # 8 usable pages of 8: three prompts take 2 each, and 14 answer
    # tokens each cross page edges until one has to give its pages up
    def make():
        return [Request(prompt=(p + p)[:10 + 2 * i], max_new_tokens=14,
                        request_id=f"r{i}")
                for i, p in enumerate(prompts(3, seed=9))]

    full, free, got, _ = both(lm, make, num_pages=9)
    assert full.scheduler.num_evictions == free.scheduler.num_evictions > 0
    assert all(r.finish_reason == "length" for r in got.values())
    assert full.stats["steps_run_ahead"] > 0


def _drain_mid_run(lm):
    def drain_at_twelve(engine, emitted):
        if emitted >= 12:
            engine.request_drain()

    full, _, got, _ = both(lm, reqs(5, new=(10,), longest=16),
                           between=drain_at_twelve, drain_timeout=0.0)
    reasons = sorted(r.finish_reason for r in got.values())
    assert reasons.count("shed") >= 3, reasons
    assert full.drain_report["pool_idle"] and full.pool.is_idle()
    assert full.stats["steps_run_ahead"] > 0


def _prefix_hit_after_an_end(lm):
    def make():
        return [Request(prompt=p, max_new_tokens=6 + 2 * i,
                        request_id=f"r{i}")
                for i, p in enumerate(prompts(5, shared=16))]

    full, free, got, _ = both(lm, make)
    if full.prefix_cache_refused:
        assert full.pool.prefix_stats["hits"] == 0
    else:
        # the follow-ups are admitted the call after an end, and find the
        # shared pages the first prompts registered
        assert full.pool.prefix_stats["hits"] >= 2
    assert full.stats["steps_run_ahead"] > 0


def _generate_returns_settled(lm):
    engine = engine_of(lm, ROWS)
    want = drive(engine_of(lm, ROWS + 1), reqs(3)())[0]
    got = engine.generate(reqs(3)())
    assert engine._in_flight is None and not engine.has_work()
    assert engine.stats["steps_run_ahead"] > 0
    assert [r.tokens for r in got] == [want[f"r{i}"].tokens
                                       for i in range(3)]
    assert engine.pool.is_idle()
    # and what the harness does to an engine it is done with strands
    # nothing: a step in flight keeps its one array
    engine.submit(reqs(3)())
    engine.serve_step()
    assert engine.has_work() and engine._in_flight is not None
    engine.pages = None
    engine._step_fns.clear()
    assert engine._settle() and engine._in_flight is None


def _swap_and_moe_stats_between_steps(lm):
    model, params = lm
    calls = []

    def poke(engine, emitted):
        calls.append(emitted)
        if len(calls) % 4 == 2:
            engine.swap_weights(jax.tree_util.tree_map(jnp.copy, params))
            assert engine._in_flight is None
        if len(calls) % 4 == 0:
            stats = engine.moe_stats()
            assert engine._in_flight is None
            if stats is not None:
                # device sums and host counters cover the same steps
                assert stats["assignments"] == engine.stats[
                    "moe_assignments"]

    full, _, _, _ = both(lm, reqs(4), between=poke)
    assert full.weight_swaps > 2 and full.stats["steps_run_ahead"] > 0


CASES = {
    "ends_by_length": _ends_by_length,
    "eos_mid_run": _eos_mid_run,
    "a_poisoned_row_quarantined_with_its_next_row_in_flight":
        _quarantine_in_flight,
    "a_deadline_expiring_in_flight": _deadline_in_flight,
    "a_preemption_settles_first": _preemption_settles_first,
    "request_drain_mid_run": _drain_mid_run,
    "a_prefix_hit_admitted_the_step_after_an_end": _prefix_hit_after_an_end,
    "generate_returns_with_nothing_in_flight": _generate_returns_settled,
    "swap_weights_and_moe_stats_between_steps":
        _swap_and_moe_stats_between_steps,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_full_batch_serves_what_a_batch_with_a_row_free_serves(lm, case):
    CASES[case](lm)


# -- the mechanism's own units -----------------------------------------------


def test_has_work_is_true_while_a_step_is_in_flight():
    engine = engine_of(_built.setdefault("multi_head", _multi_head()), 1)
    engine.submit([Request(prompt=[5, 9, 4], max_new_tokens=2,
                           request_id="one")])
    # one row, one request: full.  The prompt's step stays in flight
    assert engine.serve_step() is True
    assert engine._in_flight is not None and engine.has_work()
    assert engine.stats["generated_tokens"] == 0
    # the decode step is launched behind it, its token handed out
    assert engine.serve_step() is True
    assert engine.stats["generated_tokens"] == 1
    assert engine.stats["steps_run_ahead"] == 1
    assert engine.collect_finished() == []
    # the token in flight is the request's last, known ahead: this call
    # only settles, and the scheduler's queues were empty before it did
    assert engine.serve_step() is False
    assert not engine.has_work()
    [res] = engine.collect_finished()
    assert (len(res.tokens), res.finish_reason) == (2, "length")
    assert engine.stats["steps_run_ahead"] == 1
    # a request of ONE token ends with the step that carries its prompt:
    # known ahead, so that step is fetched in the call that launched it
    engine.submit([Request(prompt=[5, 9, 4], max_new_tokens=1)])
    assert engine.serve_step() is False and engine._in_flight is None


def test_the_watchdog_names_the_step_it_waits_for():
    engine = engine_of(_built.setdefault("multi_head", _multi_head()), 1,
                       step_timeout=600.0)
    armed = []
    real = engine.watchdog.armed
    engine.watchdog.armed = lambda phase, detail=None: (
        armed.append(phase) or real(phase, detail))
    engine.generate([Request(prompt=list(range(4, 24)), max_new_tokens=3)])
    # a mixed launch left in flight, a decode launch behind it waiting
    # for the MIXED step's tokens, a decode launch waiting for a decode
    # step's, a call that only settles
    assert armed == ["serve/ragged-w16", "serve/ragged-w16",
                     "serve/ragged-w16", "serve/ragged-w1",
                     "serve/ragged-w1"]
    assert "in_flight=none" in engine._watchdog_context()
    engine.watchdog.close()


def test_the_split_baseline_and_a_chaos_engine_stay_synchronous():
    import random

    lm = _built.setdefault("multi_head", _multi_head())
    for kw in ({"unified": False},
               {"chaos_rate": 0.2, "chaos_rng": random.Random(3)}):
        engine = engine_of(lm, ROWS, **kw)
        left = drive(engine, reqs(4)())[1]
        assert (left, engine.stats["steps_run_ahead"]) == (0, 0), kw
