"""Fleet tier (unicore_tpu/fleet): consistent-hash ring properties
(balance, minimal remap, cross-process stability), seeded trace-replay
determinism, SLO-aware routing (overflow BEFORE a deadline blows),
rolling-restart zero-drop, and the aggregate fleet report.

The load-bearing property, inherited from the serve tier and extended
across replicas: for ANY routing/restart trace, every request's tokens
are IDENTICAL to decoding that request alone — affinity, overflow, and
rolling restarts are capacity/latency features, never accuracy
features."""

import dataclasses
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from examples.lm.model import TransformerLMModel
from unicore_tpu.fleet import (SCENARIOS, FleetAutoscaler, FleetRouter,
                               HashRing, clip_trace, generate_trace,
                               replay_trace, scenario_trace)
from unicore_tpu.fleet.health import (CircuitBreaker, ReplicaHealth,
                                      PROGRESS_KEYS)
from unicore_tpu.fleet.ring import stable_hash
from unicore_tpu.serve.engine import ServeEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

V, PAD = 29, 0
POOL = dict(num_pages=24, page_size=4, max_batch=4)
MAX_CONTEXT = (POOL["num_pages"] - 1) * POOL["page_size"]


@pytest.fixture(scope="module")
def lm():
    model = TransformerLMModel(
        vocab_size=V, padding_idx=PAD, decoder_layers=2,
        decoder_embed_dim=32, decoder_ffn_embed_dim=64,
        decoder_attention_heads=4, max_seq_len=64,
        emb_dropout=0.0, dropout=0.0, attention_dropout=0.0,
        activation_dropout=0.0, rel_pos=False, abs_pos=False, rotary=True,
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def make_fleet(lm, n=2, router_kw=None, **engine_kw):
    model, params = lm
    kw = dict(POOL)
    kw.update(engine_kw)
    engines = {f"r{i}": ServeEngine(model, params, **kw)
               for i in range(n)}
    return FleetRouter(engines, **(router_kw or {}))


def solo_tokens(lm, req):
    """Oracle: the same request alone on a roomy solo engine."""
    model, params = lm
    engine = ServeEngine(model, params, num_pages=64, page_size=4,
                         max_batch=1)
    [res] = engine.generate([dataclasses.replace(req)])
    return res.tokens


# -- consistent-hash ring --------------------------------------------------


def test_ring_balance_within_bound():
    ring = HashRing([f"r{i}" for i in range(4)], vnodes=64)
    counts = {rid: 0 for rid in ring.members()}
    for k in range(2000):
        counts[ring.lookup(f"user-{k}")] += 1
    mean = 2000 / 4
    assert max(counts.values()) < 2.0 * mean, counts
    assert min(counts.values()) > 0.35 * mean, counts


def test_ring_minimal_remap_on_leave_and_rejoin():
    replicas = [f"r{i}" for i in range(4)]
    ring = HashRing(replicas)
    keys = [f"sess-{k}" for k in range(512)]
    before = {k: ring.lookup(k) for k in keys}
    ring.remove("r2")
    after = {k: ring.lookup(k) for k in keys}
    # ONLY the departed replica's keys move, and they spread over the
    # survivors — nobody else's mapping is disturbed
    moved = [k for k in keys if before[k] != after[k]]
    assert moved == [k for k in keys if before[k] == "r2"]
    assert all(after[k] != "r2" for k in keys)
    bound = math.ceil(len(keys) / 4) + 32  # expected n/replicas + slack
    assert len(moved) <= bound, (len(moved), bound)
    # rejoin restores the ORIGINAL mapping exactly
    ring.add("r2")
    assert {k: ring.lookup(k) for k in keys} == before


def test_ring_stability_across_instances():
    # affinity must survive a router restart: a FRESH ring with the
    # same membership maps every key identically (stable_hash, not the
    # per-process salted hash())
    a = HashRing(["r0", "r1", "r2"])
    b = HashRing(["r2", "r0", "r1"])  # join order must not matter
    for k in range(200):
        assert a.lookup(f"u{k}") == b.lookup(f"u{k}")
    # pin one concrete digest so an accidental hash-function change
    # (which would silently remap EVERY session) is loud
    assert stable_hash("fixed-key") == 0xC3164720616CB4D1


def test_ring_membership_errors():
    ring = HashRing(["r0"])
    with pytest.raises(ValueError):
        ring.add("r0")
    with pytest.raises(KeyError):
        ring.remove("r9")
    ring.remove("r0")
    with pytest.raises(LookupError):
        ring.lookup("anything")


# -- trace generator -------------------------------------------------------


def trace_fields(events):
    return [(e.at_ms, e.session, e.request.prompt,
             e.request.max_new_tokens, e.request.seed,
             e.request.request_id) for e in events]


def test_trace_seeded_determinism():
    a = generate_trace(1106, num_requests=40, vocab=V)
    b = generate_trace(1106, num_requests=40, vocab=V)
    assert trace_fields(a) == trace_fields(b)
    c = generate_trace(1107, num_requests=40, vocab=V)
    assert trace_fields(a) != trace_fields(c)


def test_trace_shape_sessions_share_prefixes():
    events = generate_trace(3, num_requests=64, sessions=6,
                            prefix_pool=2, vocab=V)
    by_session = {}
    for e in events:
        by_session.setdefault(e.session, []).append(e.request.prompt)
    # every request of one session opens with the SAME prefix tokens
    prefixes = {}
    for s, prompts in by_session.items():
        n = min(len(p) for p in prompts)
        shared = 0
        while shared < n and len({tuple(p[: shared + 1])
                                  for p in prompts}) == 1:
            shared += 1
        prefixes[s] = tuple(prompts[0][:4])
        if len(prompts) > 1:
            assert shared >= 4, (s, shared)
    # a prefix pool of 2 over 6 sessions forces sharing ACROSS sessions
    assert len(set(prefixes.values())) <= 2
    # arrivals are bursty (ON/OFF): gaps span orders of magnitude
    gaps = [b.at_ms - a.at_ms for a, b in zip(events, events[1:])]
    assert max(gaps) > 10 * (sorted(gaps)[len(gaps) // 2] + 1e-9)
    # prompt lengths are heavy-tailed enough to spread
    lens = sorted(len(e.request.prompt) for e in events)
    assert lens[-1] >= lens[0] + 8


def test_trace_clip_drops_oversized():
    events = generate_trace(5, num_requests=32, vocab=V,
                            body_len_lognorm=(3.0, 1.0),
                            body_len_clip=(1, 200))
    kept = clip_trace(events, 64)
    assert all(len(e.request.prompt) <= 64 for e in kept)
    assert len(kept) < len(events)  # the clip actually engaged


# -- engine fleet surface --------------------------------------------------


def test_load_snapshot_is_stable_typed_dict(lm):
    model, params = lm
    eng = ServeEngine(model, params, max_waiting=3, **POOL)
    snap = eng.load_snapshot()
    want_types = {
        "free_pages": int, "total_pages": int, "waiting": int,
        "running": int, "free_slots": int, "max_waiting": int,
        "draining": bool, "step_ms": float,
        "prefix_hits": int, "prefix_tokens_saved": int,
        "prefix_hit_rate": float,
        # ISSUE 27: the cache was asked for and refused (a model with
        # recurrent layers), so the hit surface above stays at zero
        "prefix_cache_refused": bool,
        # ISSUE 14 health surface: the retired-token watermark the
        # router's wedge detector differences, and the host-fault
        # counter its fault-rate threshold windows
        "last_progress": int, "host_faults": int,
        # ISSUE 28: the fill of the mixed step program (dispatches at
        # the prefill width, tokens carried, tokens compiled for)
        "mixed_steps": int, "mixed_tokens_carried": int,
        "mixed_tokens_capacity": int,
        # ISSUE 31: a model with sparse experts counts its routing (zeros
        # for any other; the skew as moe_stats() last read it)
        "moe_assignments": int, "moe_experts_touched": int,
        "moe_load_max_over_mean": float,
        # ISSUE 35: the assignments that landed on experts held here, and
        # what one token holds in the pool over all layers
        "moe_assignments_held": int, "cache_bytes_per_token": int,
        # ISSUE 37: launches made with a step in flight (how often the
        # batch was full), sampled tokens dropped for a late end
        "steps_run_ahead": int, "tokens_overrun": int,
    }
    assert set(snap) == set(want_types), snap
    for k, t in want_types.items():
        assert isinstance(snap[k], t), (k, snap[k])
    assert snap["free_pages"] == POOL["num_pages"] - 1
    assert snap["free_slots"] == POOL["max_batch"]
    assert snap["max_waiting"] == 3 and not snap["draining"]
    assert snap["last_progress"] == 0 and snap["host_faults"] == 0
    eng2 = ServeEngine(model, params, **POOL)
    assert eng2.load_snapshot()["max_waiting"] is None


def test_submit_step_collect_matches_generate(lm):
    model, params = lm
    rng = np.random.RandomState(0)
    from unicore_tpu.serve.scheduler import Request

    def reqs():
        return [Request(prompt=[int(t) for t in
                                rng2.integers(1, V, size=(n,))],
                        max_new_tokens=6, seed=i, request_id=f"q{i}")
                for i, n in enumerate([3, 9, 14])]

    rng2 = np.random.default_rng(0)
    a = ServeEngine(model, params, **POOL).generate(reqs())
    rng2 = np.random.default_rng(0)
    eng = ServeEngine(model, params, **POOL)
    eng.submit(reqs())
    while eng.serve_step():
        pass
    b = {r.request_id: r for r in eng.collect_finished()}
    for res in a:
        assert b[res.request_id].tokens == res.tokens
        assert b[res.request_id].finish_reason == res.finish_reason
    del rng


def test_reclaim_and_reopen(lm):
    model, params = lm
    from unicore_tpu.serve.scheduler import Request

    eng = ServeEngine(model, params, **POOL)
    eng.submit([Request(prompt=[1, 2, 3], max_new_tokens=4, seed=i,
                        request_id=f"w{i}") for i in range(3)])
    with pytest.raises(RuntimeError):
        eng.reopen()  # busy: queued work must not be resurrected over
    reqs = eng.reclaim_waiting()
    assert [r.request_id for r in reqs] == ["w0", "w1", "w2"]
    assert not eng.has_work() and eng.pool.is_idle()
    eng.request_drain()
    eng.serve_step()
    eng.reopen()
    assert not eng.load_snapshot()["draining"]
    # the restart's drain record must not survive the reopen — a later
    # fleet-wide drain would re-report it as ITS outcome
    assert eng.drain_report is None
    # a reopened engine serves again
    [res] = eng.generate([Request(prompt=[1, 2, 3], max_new_tokens=2,
                                  seed=0)])
    assert res.finish_reason in ("eos", "length")


# -- router ----------------------------------------------------------------


def test_router_affinity_holds_without_membership_change(lm):
    router = make_fleet(lm, n=2)
    trace = clip_trace(
        generate_trace(1106, num_requests=24, vocab=V,
                       body_len_clip=(1, 20)),
        MAX_CONTEXT,
    )
    replay_trace(router, trace)
    results = router.results()
    assert len(results) == len(trace)
    for s, rids in router.session_replicas.items():
        assert len(set(rids)) == 1, (s, rids)
    # both replicas actually served (the trace spans enough sessions)
    used = {r[0] for r in router.session_replicas.values()}
    assert used == {"r0", "r1"}
    assert all(e.pool.is_idle() for e in router.engines.values())


def test_router_overflow_before_deadline(lm):
    from unicore_tpu.serve.scheduler import Request

    # service_floor 50ms: a home queue 4 deep projects 300ms of wait
    # (x1.5 safety), past the 200ms deadline — the router must override
    # affinity and route to the empty replica instead of queueing the
    # request into a deterministic expiry
    router = make_fleet(lm, n=2,
                        router_kw=dict(service_floor_ms=50.0))
    home = router.ring.lookup("hot")
    other = next(r for r in router.engines if r != home)
    filler = [Request(prompt=[1 + i, 2, 3], max_new_tokens=8, seed=i,
                      request_id=f"f{i}") for i in range(4)]
    for req in filler:
        assert router.submit(req, session_key="hot") == home
    probe = Request(prompt=[5, 6, 7], max_new_tokens=2, seed=9,
                    request_id="probe", deadline_ms=200.0)
    assert router.submit(probe, session_key="hot") == other
    assert router.stats["overflow_routed"] == 1
    # without a deadline the same pressure keeps affinity
    tail = Request(prompt=[8, 9], max_new_tokens=2, seed=10,
                   request_id="tail")
    assert router.submit(tail, session_key="hot") == home
    router.run_until_complete()
    assert all(e.pool.is_idle() for e in router.engines.values())


def test_router_routes_around_draining_replica(lm):
    from unicore_tpu.serve.scheduler import Request

    router = make_fleet(lm, n=2)
    home = router.ring.lookup("s-drain")
    other = next(r for r in router.engines if r != home)
    router.engines[home].request_drain()
    req = Request(prompt=[1, 2], max_new_tokens=2, seed=0,
                  request_id="d0")
    assert router.submit(req, session_key="s-drain") == other
    router.run_until_complete()
    assert router.results()["d0"].finish_reason in ("eos", "length")


def test_rolling_restart_drops_nothing(lm):
    model, params = lm

    def factory(rid):
        del rid
        return ServeEngine(model, params, **POOL)

    router = make_fleet(lm, n=2)
    trace = clip_trace(
        generate_trace(7, num_requests=16, vocab=V,
                       body_len_clip=(1, 20)),
        MAX_CONTEXT,
    )
    restarted = []

    def hook(step, r):
        if step == 2 and not restarted:
            restarted.append(r.rolling_restart(factory))

    replay_trace(router, trace, on_step=hook)
    assert restarted and router.stats["restarts"] == 2
    results = router.results()
    assert len(results) == len(trace)
    for ev in trace:
        res = results[ev.request.request_id]
        assert res.finish_reason in ("eos", "length", "capacity"), res
        assert res.tokens == solo_tokens(lm, ev.request), res.request_id
    for rep in restarted[0].values():
        if rep is not None:
            assert rep["shed"] == 0 and rep["expired"] == 0
            assert rep["signal"] == "SIGTERM"
    for eng in router.engines.values():
        eng.pool.check_invariants()
        assert eng.pool.is_idle()


def test_fleet_report_aggregates_and_drain(lm):
    from unicore_tpu.serve.scheduler import Request

    router = make_fleet(lm, n=2)
    for i in range(6):
        router.submit(Request(prompt=[1 + i, 2, 3], max_new_tokens=4,
                              seed=i, request_id=f"a{i}"),
                      session_key=f"s{i % 3}")
    router.run_until_complete()
    rep = router.fleet_report()
    assert rep["replicas"] == 2 and rep["sessions"] == 3
    assert rep["router"]["routed"] == 6
    agg = rep["aggregate"]
    per = [router.engines[r].stats for r in router.engines]
    assert agg["generated_tokens"] == sum(
        s["generated_tokens"] for s in per)
    assert agg["prefills"] == sum(s["prefills"] for s in per)
    assert agg["peak_waiting"] == max(s["peak_waiting"] for s in per)
    assert agg["peak_pool_occupancy"] == pytest.approx(
        max(s["peak_pool_occupancy"] for s in per))
    assert set(rep["per_replica"]) == {"r0", "r1"}
    drains = router.drain()
    assert set(drains) == {"r0", "r1"}
    for d in drains.values():
        assert d["requested"] and d["shed"] == 0 and d["pool_idle"]


def test_duplicate_request_id_rejected(lm):
    from unicore_tpu.serve.scheduler import Request

    router = make_fleet(lm, n=2)
    router.submit(Request(prompt=[1], max_new_tokens=1, seed=0,
                          request_id="dup"))
    with pytest.raises(ValueError):
        router.submit(Request(prompt=[2], max_new_tokens=1, seed=1,
                              request_id="dup"))
    router.run_until_complete()


# -- failover: health model, circuit breaker, re-dispatch (ISSUE 14) -------


def _kill(router, rid):
    """Make ``rid``'s next serve_step raise — the crash the router's
    guarded step loop must catch and turn into an eviction."""
    def boom():
        raise RuntimeError("chaos: replica killed mid-traffic")

    router.engines[rid].serve_step = boom


def _wedge(router, rid):
    """Make ``rid`` claim work forever while retiring nothing — the
    logic wedge only the progress watermark can see."""
    router.engines[rid].serve_step = lambda: True


def _health_snap(**kw):
    snap = {"last_progress": 0, "host_faults": 0, "waiting": 1,
            "running": 1, "free_pages": 10, "prefix_hits": 0}
    snap.update(kw)
    assert set(PROGRESS_KEYS) <= set(snap)
    return snap


def test_ring_discard_is_leave_without_drain():
    replicas = [f"r{i}" for i in range(4)]
    ring = HashRing(replicas)
    keys = [f"sess-{k}" for k in range(256)]
    before = {k: ring.lookup(k) for k in keys}
    # discard == remove semantics (only the dead replica's keys move)…
    assert ring.discard("r1") is True
    after = {k: ring.lookup(k) for k in keys}
    moved = [k for k in keys if before[k] != after[k]]
    assert moved == [k for k in keys if before[k] == "r1"]
    # …but idempotent: a failover racing a rolling restart that already
    # took the victim off the ring is a no-op, not a KeyError
    assert ring.discard("r1") is False
    assert {k: ring.lookup(k) for k in keys} == after
    ring.add("r1")
    assert {k: ring.lookup(k) for k in keys} == before


def test_health_wedge_suspect_then_dead():
    h = ReplicaHealth(suspect_steps=2, dead_steps=4)
    snap = _health_snap()
    assert h.observe("r0", snap, True, step=1) == "healthy"
    assert h.observe("r0", snap, True, step=2) == "healthy"  # stall 1
    assert h.observe("r0", snap, True, step=3) == "suspect"  # stall 2
    # progress (any signature key moving) resets the ladder
    assert h.observe("r0", _health_snap(last_progress=3), True,
                     step=4) == "healthy"
    for s in range(5, 8):
        h.observe("r0", _health_snap(last_progress=3), True, step=s)
    assert h.state("r0") == "suspect"
    assert h.observe("r0", _health_snap(last_progress=3), True,
                     step=8) == "dead"
    assert "wedged" in h.reason("r0")
    # dead is terminal until reset
    assert h.observe("r0", _health_snap(last_progress=9), True,
                     step=9) == "dead"
    h.reset("r0")
    assert h.state("r0") == "healthy"


def test_health_idle_replica_never_wedges():
    h = ReplicaHealth(suspect_steps=1, dead_steps=2)
    snap = _health_snap(waiting=0, running=0)
    for s in range(1, 10):
        assert h.observe("r0", snap, False, step=s) == "healthy"


def test_health_fault_rate_threshold():
    h = ReplicaHealth(fault_budget=2, fault_window=8)
    assert h.observe("r0", _health_snap(host_faults=0), True,
                     step=1) == "healthy"
    # one fault inside the window: not dead yet
    assert h.observe("r0", _health_snap(host_faults=1), True,
                     step=2) == "healthy"
    # a second inside the same window crosses the budget
    assert h.observe("r0", _health_snap(host_faults=2), True,
                     step=3) == "dead"
    assert "host-fault rate" in h.reason("r0")
    # the same delta spread WIDER than the window stays healthy
    h2 = ReplicaHealth(fault_budget=2, fault_window=8)
    faults = 0
    for s in range(1, 50, 12):  # one fault every 12 steps
        state = h2.observe("r0", _health_snap(host_faults=faults,
                                              last_progress=s),
                           True, step=s)
        assert state == "healthy", (s, faults)
        faults += 1


def test_health_crash_is_immediately_dead():
    h = ReplicaHealth()
    assert h.record_exception("r0", RuntimeError("boom"),
                              step=7) == "dead"
    assert "crash" in h.reason("r0") and "boom" in h.reason("r0")


def test_circuit_breaker_state_machine():
    br = CircuitBreaker(cooldown_steps=3, flap_limit=3, flap_window=50)
    assert br.state == "closed"
    with pytest.raises(RuntimeError):
        br.succeed(0)  # only a half-open probe can close it
    br.trip(10)
    assert br.state == "open"
    assert not br.ready(11) and not br.ready(12)  # cooling down
    assert br.ready(13)
    br.probe(13)
    assert br.state == "half_open" and br.attempts == 1
    br.succeed(14)
    assert br.state == "closed"
    assert br.describe() == {"state": "closed", "trips": 1,
                             "rejoin_attempts": 1}


def test_circuit_breaker_flap_stays_open():
    br = CircuitBreaker(cooldown_steps=1, flap_limit=3, flap_window=100)
    br.trip(0)
    br.probe(1)
    br.fail(2)      # trip #2
    br.probe(3)
    br.fail(4)      # trip #3 -> quarantined inside the window
    assert br.state == "open" and br.attempts == 2
    for step in range(5, 100):
        assert not br.ready(step), step  # flap hold: no more probes
    # the window eventually slides past the flap burst
    assert br.ready(105)


def test_child_shutdown_lost_is_permanent():
    from unicore_tpu.resilience.preemption import ChildShutdown

    child = ChildShutdown(name="r0")
    child.mark_lost()
    assert child.requested and child.lost
    child.clear()  # a zombie replica cannot re-open its own drain flag
    assert child.requested


def test_reclaim_include_running_salvages_generated(lm):
    model, params = lm
    from unicore_tpu.serve.scheduler import Request

    eng = ServeEngine(model, params, **POOL)
    eng.submit([Request(prompt=[1 + i, 2, 3], max_new_tokens=6, seed=i,
                        request_id=f"s{i}") for i in range(3)])
    for _ in range(3):
        eng.serve_step()
    assert eng.scheduler.running, "setup: nothing admitted"
    salvaged = eng.reclaim_waiting(include_running=True)
    ids = [req.request_id for req, _ in salvaged]
    assert sorted(ids) == ["s0", "s1", "s2"]
    # running sequences come first and carry their generated tokens
    assert salvaged[0][1], "running head salvaged without its tokens"
    assert not eng.has_work() and eng.pool.is_idle()
    # a healthy engine ADOPTS the salvage and continues the exact stream
    eng2 = ServeEngine(model, params, **POOL)
    for req, generated in salvaged:
        eng2.adopt(req, generated=generated)
    while eng2.serve_step():
        pass
    done = {r.request_id: r for r in eng2.collect_finished()}
    for req, _ in salvaged:
        assert done[req.request_id].tokens == solo_tokens(lm, req)


def test_failover_crash_reroutes_token_identical(lm):
    from unicore_tpu.serve.scheduler import Request

    router = make_fleet(lm, n=2)
    reqs = [Request(prompt=[1 + i, 2, 3, 4], max_new_tokens=8, seed=i,
                    request_id=f"q{i}") for i in range(10)]
    homes = {router.submit(req, session_key=f"s{i}")
             for i, req in enumerate(reqs)}
    assert homes == {"r0", "r1"}, "setup: both replicas must hold work"
    for _ in range(3):
        router.step()
        router.collect()
    _kill(router, "r0")
    router.run_until_complete()
    results = router.results()
    assert len(results) == len(reqs)
    assert router.stats["replicas_lost"] == 1
    assert router.stats["failovers"] >= 1
    assert "r0" not in router.engines and "r0" not in router.ring
    for req in reqs:
        res = results[req.request_id]
        assert res.finish_reason in ("eos", "length", "capacity"), res
        assert res.tokens == solo_tokens(lm, req), req.request_id
    survivor = router.engines["r1"]
    survivor.pool.check_invariants()
    assert survivor.pool.is_idle()
    rep = router.fleet_report()
    assert rep["lost"]["r0"]["reason"].startswith("crash")
    assert rep["breakers"]["r0"]["state"] == "open"
    assert rep["health"]["r1"]["state"] == "healthy"


def test_failover_salvage_not_stranded_on_already_stepped_replica(lm):
    """Regression: ALL work lives on the crashing replica while the
    survivor (which sorts FIRST, so it already stepped this fleet
    step) is idle.  The eviction step must still report progress, or
    run_until_complete() exits with the salvage adopted-but-never-
    decoded."""
    from unicore_tpu.serve.scheduler import Request

    router = make_fleet(lm, n=2)
    hot = [f"k{i}" for i in range(400)
           if router.ring.lookup(f"k{i}") == "r1"][:4]
    reqs = [Request(prompt=[1 + i, 2, 3], max_new_tokens=4, seed=i,
                    request_id=f"z{i}") for i in range(len(hot))]
    for req, sess in zip(reqs, hot):
        assert router.submit(req, session_key=sess) == "r1"
    router.step()
    _kill(router, "r1")
    router.run_until_complete()
    results = router.results()
    assert not router.has_work(), "salvage stranded on the survivor"
    assert len(results) == len(reqs)
    for req in reqs:
        assert results[req.request_id].finish_reason in ("eos", "length")
        assert results[req.request_id].tokens == solo_tokens(lm, req)


def test_adopt_rejects_prefix_outgrowing_pool(lm):
    """Heterogeneous-fleet guard: a salvaged prompt+generated that can
    never fit the adopter's pool is rejected at add() (typed), and the
    router turns that into a 'replica_lost' terminal instead of
    pinning waiting[0] forever."""
    model, params = lm
    from unicore_tpu.serve.scheduler import Request

    tiny = ServeEngine(model, params, num_pages=4, page_size=4,
                       max_batch=2)
    req = Request(prompt=[1, 2, 3, 4, 5, 6], max_new_tokens=12, seed=0,
                  request_id="big")
    with pytest.raises(ValueError):
        # 6 prompt + 8 generated = 14 tokens -> 4 pages > 3 usable
        tiny.adopt(req, generated=list(range(1, 9)))


def test_failover_wedge_detected_and_evicted(lm):
    from unicore_tpu.serve.scheduler import Request

    router = make_fleet(
        lm, n=2,
        router_kw=dict(health=ReplicaHealth(suspect_steps=2,
                                            dead_steps=4)),
    )
    reqs = [Request(prompt=[2 + i, 3, 4], max_new_tokens=6, seed=i,
                    request_id=f"w{i}") for i in range(8)]
    for i, req in enumerate(reqs):
        router.submit(req, session_key=f"s{i}")
    router.step()
    _wedge(router, "r0")
    steps = 0
    while router.step():
        router.collect()
        steps += 1
        assert steps < 500, "wedged replica never evicted — fleet hung"
    results = router.collect()
    assert "r0" not in router.engines
    assert "wedged" in router.fleet_report()["lost"]["r0"]["reason"]
    for req in reqs:
        res = results[req.request_id]
        assert res.finish_reason in ("eos", "length"), res
        assert res.tokens == solo_tokens(lm, req), req.request_id


def test_failover_budget_terminates_replica_lost(lm):
    from unicore_tpu.serve.scheduler import Request

    router = make_fleet(lm, n=2, router_kw=dict(max_failovers=0))
    reqs = [Request(prompt=[1 + i, 2, 3], max_new_tokens=8, seed=i,
                    request_id=f"m{i}") for i in range(8)]
    assigned = {req.request_id: router.submit(req, session_key=f"s{i}")
                for i, req in enumerate(reqs)}
    for _ in range(2):
        router.step()
        router.collect()
    _kill(router, "r0")
    router.run_until_complete()
    results = router.results()
    assert len(results) == len(reqs)
    lost = [r for r in results.values()
            if r.finish_reason == "replica_lost"]
    done_before_kill = sum(
        1 for req in reqs
        if assigned[req.request_id] == "r0"
        and results[req.request_id].finish_reason in ("eos", "length"))
    # every r0 request not already finished terminates typed — never
    # silently stranded, never rerouted past the budget
    assert len(lost) + done_before_kill == sum(
        1 for rid in assigned.values() if rid == "r0")
    assert lost, "setup: r0 held no unfinished work at the kill"
    assert router.stats["replica_lost"] == len(lost)
    for req in reqs:
        res = results[req.request_id]
        if res.finish_reason == "replica_lost":
            assert res.ttft_ms is None or res.tokens  # partial tokens kept
        else:
            assert res.tokens == solo_tokens(lm, req)


def test_breaker_rejoin_after_canary(lm):
    model, params = lm
    from unicore_tpu.serve.scheduler import Request

    def factory(rid):
        del rid
        return ServeEngine(model, params, **POOL)

    router = make_fleet(
        lm, n=2,
        router_kw=dict(
            factory=factory,
            breaker=lambda rid: CircuitBreaker(cooldown_steps=3),
        ),
    )
    for i in range(6):
        router.submit(Request(prompt=[1 + i, 2], max_new_tokens=4,
                              seed=i, request_id=f"j{i}"),
                      session_key=f"s{i}")
    for _ in range(2):
        router.step()
    _kill(router, "r0")
    for _ in range(40):
        router.step()
        router.collect()
    assert "r0" in router.engines, router.fleet_report()
    assert "r0" in router.ring
    assert router.stats["rejoins"] == 1
    rep = router.fleet_report()
    assert rep["breakers"]["r0"]["state"] == "closed"
    assert rep["breakers"]["r0"]["rejoin_attempts"] == 1
    # rejoin restores the ORIGINAL ring mapping (warm sessions return)
    fresh = HashRing(["r0", "r1"])
    for k in range(64):
        assert router.ring.lookup(f"u{k}") == fresh.lookup(f"u{k}")
    # and the rejoined replica actually serves
    sess = next(f"v{k}" for k in range(64)
                if router.ring.lookup(f"v{k}") == "r0")
    probe = Request(prompt=[5, 6], max_new_tokens=2, seed=9,
                    request_id="after-rejoin")
    assert router.submit(probe, session_key=sess) == "r0"
    router.run_until_complete()
    assert router.results()["after-rejoin"].finish_reason in (
        "eos", "length")


def test_breaker_flap_holds_replica_out(lm):
    model, params = lm
    from unicore_tpu.serve.scheduler import Request

    def flapping_factory(rid):
        del rid
        eng = ServeEngine(model, params, **POOL)

        def boom():
            raise RuntimeError("chaos: replacement dies on arrival")

        eng.serve_step = boom
        return eng

    router = make_fleet(
        lm, n=2,
        router_kw=dict(
            factory=flapping_factory,
            breaker=lambda rid: CircuitBreaker(
                cooldown_steps=2, flap_limit=3, flap_window=512),
        ),
    )
    for i in range(4):
        router.submit(Request(prompt=[1 + i, 2], max_new_tokens=4,
                              seed=i, request_id=f"f{i}"),
                      session_key=f"s{i}")
    router.step()
    _kill(router, "r0")
    for _ in range(80):
        router.step()
        router.collect()
    rep = router.fleet_report()
    # the flapping slot is HELD OUT: bounded rejoin attempts, breaker
    # open, replica off the ring — it cannot thrash the mapping
    assert "r0" not in router.engines and "r0" not in router.ring
    assert rep["breakers"]["r0"]["state"] == "open"
    assert rep["breakers"]["r0"]["rejoin_attempts"] <= 3
    assert rep["breakers"]["r0"]["rejoin_attempts"] >= 1
    assert not router.has_work()


# -- the full chaos leg (slow sibling of the fast test above) --------------


@pytest.mark.slow
def test_chaos_fleet_rolling_leg():
    out = os.path.join("/tmp", "chaos_fleet_test.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "unicore_chaos.py"),
         "--serve", "--fleet", "--rolling", "--json", out],
        cwd=REPO, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO},
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    import json

    with open(out) as f:
        r = json.load(f)
    leg = r["fleet_rolling"]
    assert leg["restarts"] == 2 and not leg["dropped"]
    assert leg["survivors_exact"] and leg["pools_idle"]
    assert not leg["affinity_split_sessions"]
    assert leg["remapped_on_leave"] <= leg["remap_bound"]


@pytest.mark.slow
def test_chaos_fleet_failover_legs():
    """The three ISSUE-14 legs end to end through the harness CLI —
    the slow siblings of the fast failover tests above."""
    import json

    for flag, key, checks in (
        ("--kill-replica", "fleet_kill",
         lambda f: (f["survivors_exact"] and not f["missing"]
                    and not f["typed"] and f["deterministic_replay"]
                    and f["replicas_lost"] == 1
                    and f["replica_lost_default"] == 0
                    and len(f["budget_zero_replica_lost"])
                    == f["budget_zero_salvaged"]
                    and f["survivor_pools_idle"])),
        ("--wedge-replica", "fleet_wedge",
         lambda f: ("wedged" in f["lost"]["reason"]
                    and f["detect_lag_steps"]
                    <= f["dead_steps_budget"] + 2
                    and not f["expired"] and f["survivors_exact"]
                    and f["survivor_pools_idle"])),
        ("--flap", "fleet_flap",
         lambda f: (f["breaker_state"] == "open" and f["held_out"]
                    and 1 <= f["rejoin_attempts"] <= f["flap_limit"]
                    and f["survivors_exact"]
                    and f["survivor_pools_idle"])),
    ):
        out = os.path.join("/tmp", f"chaos_fleet_{key}.json")
        proc = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "tools", "unicore_chaos.py"),
             "--serve", "--fleet", flag, "--json", out],
            cwd=REPO, capture_output=True, text=True, timeout=900,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "PYTHONPATH": REPO},
        )
        assert proc.returncode == 0, (
            flag, proc.stdout[-3000:] + proc.stderr[-3000:])
        with open(out) as f:
            leg = json.load(f)[key]
        assert checks(leg), (flag, leg)


# -- traffic-scenario suite (ISSUE 20) -------------------------------------


def test_scenario_suite_seeded_determinism():
    assert SCENARIOS == ("diurnal", "flash_crowd", "heavy_tail",
                         "session_churn")
    for name in SCENARIOS:
        a = scenario_trace(name, 11, num_requests=24, vocab=V)
        b = scenario_trace(name, 11, num_requests=24, vocab=V)
        assert trace_fields(a) == trace_fields(b), name
        c = scenario_trace(name, 12, num_requests=24, vocab=V)
        assert trace_fields(a) != trace_fields(c), name


def test_scenario_traces_merge_ordered_with_unique_ids():
    for name in SCENARIOS:
        events = scenario_trace(name, 7, num_requests=24, vocab=V)
        assert events, name
        ids = [e.request.request_id for e in events]
        assert len(set(ids)) == len(ids), name
        keys = [(e.at_ms, e.request.request_id) for e in events]
        assert keys == sorted(keys), name


def test_scenario_unknown_name_and_duplicate_merge_raise():
    from unicore_tpu.fleet.trace import merge_traces

    with pytest.raises(ValueError, match="unknown scenario"):
        scenario_trace("tsunami", 7)
    base = generate_trace(3, num_requests=4, vocab=V)
    with pytest.raises(ValueError, match="duplicate request id"):
        merge_traces(base, base)


# -- EWMA step-time smoothing (ISSUE 20 satellite) -------------------------


def test_step_ewma_single_spike_no_reroute(lm):
    from unicore_tpu.serve.scheduler import Request

    router = make_fleet(lm, n=2, router_kw=dict(service_floor_ms=1.0))
    home = router.ring.lookup("hot")
    other = next(r for r in router.engines if r != home)
    # steady 2ms service folds into the EWMA...
    for _ in range(6):
        router._observe_step_ms(home, 2.0)
    # ...then ONE 100ms hiccup (GC pause, page-cache miss)
    router._observe_step_ms(home, 100.0)
    assert router.smoothed_step_ms(home) == pytest.approx(
        0.75 * 2.0 + 0.25 * 100.0)
    for i in range(4):
        assert router.submit(
            Request(prompt=[1 + i, 2, 3], max_new_tokens=4, seed=i,
                    request_id=f"f{i}"),
            session_key="hot") == home
    # the INSTANTANEOUS projection would reroute (4 deep x 100ms x 1.5
    # = 600ms >> 200ms deadline); the EWMA's 26.5ms projects 159ms and
    # keeps affinity — one hiccup must not scatter the session
    probe = Request(prompt=[5, 6], max_new_tokens=2, seed=9,
                    request_id="p0", deadline_ms=200.0)
    assert router.submit(probe, session_key="hot") == home
    # a SUSTAINED spike is real pressure: the EWMA converges toward it
    # and the same deadline now deterministically reroutes
    router._observe_step_ms(home, 100.0)
    router._observe_step_ms(home, 100.0)
    probe2 = Request(prompt=[7, 8], max_new_tokens=2, seed=10,
                     request_id="p1", deadline_ms=200.0)
    assert router.submit(probe2, session_key="hot") == other
    assert router.stats["overflow_routed"] == 1
    router.run_until_complete()
    assert all(e.pool.is_idle() for e in router.engines.values())


def test_step_ewma_skips_unmeasured_steps(lm):
    router = make_fleet(lm, n=2)
    # before any observation: the instantaneous snapshot value rules
    assert router.smoothed_step_ms(
        "r0", {"step_ms": 7.0}) == pytest.approx(7.0)
    router._observe_step_ms("r0", 4.0)
    # zero-width (idle) steps must not drag the estimate toward 0
    router._observe_step_ms("r0", 0.0)
    router._observe_step_ms("r0", -1.0)
    assert router.smoothed_step_ms("r0") == pytest.approx(4.0)
    # and the floor clamps pathological small estimates
    router._step_ewma["r1"] = 0.01
    assert router.smoothed_step_ms("r1") == router.service_floor_ms


# -- elastic scaling (ISSUE 20) --------------------------------------------


def _engine_factory(lm):
    model, params = lm

    def factory(rid):
        del rid
        return ServeEngine(model, params, **POOL)

    return factory


def test_scale_up_boots_through_canary_off_ring(lm):
    router = make_fleet(lm, n=2,
                        router_kw=dict(factory=_engine_factory(lm)))
    assert router.scale_up("a0") is True
    # OFF-RING while probing: no traffic can route to the canary slot
    assert "a0" in router._probation and "a0" not in router.engines
    assert "a0" not in router.ring.members()
    for _ in range(router.probe_budget_steps + 2):
        router.step()
        if "a0" in router.engines:
            break
    assert "a0" in router.engines and "a0" in router.ring.members()
    assert router.stats["scale_ups"] == 1
    # a joined slot behaves like any other: the id is now taken
    with pytest.raises(ValueError):
        router.scale_up("a0")
    # no factory, no elasticity — loud, not silent
    with pytest.raises(RuntimeError, match="factory"):
        make_fleet(lm, n=1).scale_up("a1")


def test_retire_replica_zero_drop_under_load(lm):
    from unicore_tpu.serve.scheduler import Request

    router = make_fleet(lm, n=3)
    reqs = [Request(prompt=[1 + (i % 7), 2, 3], max_new_tokens=4,
                    seed=i, request_id=f"q{i}") for i in range(9)]
    for i, req in enumerate(reqs):
        router.submit(req, session_key=f"s{i % 4}")
    router.step()
    victim = sorted(router.engines)[0]
    router.retire_replica(victim)
    assert victim not in router.ring.members()
    assert victim in router.fleet_report()["retiring"]
    router.run_until_complete()
    # every request completed token-identical to a solo run — the
    # retirement dropped nothing
    results = router.results()
    assert len(results) == len(reqs)
    for req in reqs:
        res = results[req.request_id]
        assert res.finish_reason in ("eos", "length"), res
        assert res.tokens == solo_tokens(lm, req), req.request_id
    assert victim not in router.engines
    assert router.stats["retired"] == 1
    rec = router.fleet_report()["retired"][victim]
    assert rec["died"] is False and rec["pool_idle"] is True
    assert rec["drain"] is not None
    assert rec["drain"]["shed"] == 0 and rec["drain"]["expired"] == 0
    # the drained engine's pool ended idle and is kept auditable
    assert router._retired_engines[victim].pool.is_idle()


def test_fleet_report_pins_autoscale_and_retirement_keys(lm):
    router = make_fleet(lm, n=2)
    rep = router.fleet_report()
    assert rep["autoscale"] is None
    assert rep["retiring"] == [] and rep["retired"] == {}
    router.attach_autoscaler(FleetAutoscaler(router, min_replicas=1,
                                             max_replicas=3))
    auto = router.fleet_report()["autoscale"]
    want = {
        "min_replicas", "max_replicas", "serving", "booting",
        "retiring", "scale_ups", "scale_downs", "boot_failures",
        "boot_budget", "high_watermark_ms", "low_watermark_ms",
        "last_pressure_ms", "decisions",
    }
    assert set(auto) == want, auto
    assert auto["serving"] == 2 and auto["booting"] == []
    assert auto["scale_ups"] == 0 and auto["decisions"] == []


def test_autoscaler_envelope_validation(lm):
    router = make_fleet(lm, n=2)
    with pytest.raises(ValueError):
        FleetAutoscaler(router, min_replicas=0)
    with pytest.raises(ValueError):
        FleetAutoscaler(router, min_replicas=3, max_replicas=2)
    with pytest.raises(ValueError):
        FleetAutoscaler(router, high_watermark_ms=4.0,
                        low_watermark_ms=4.0)
    with pytest.raises(ValueError):
        FleetAutoscaler(router, hysteresis_steps=0)


def _autoscale_run(lm, trace):
    router = make_fleet(lm, n=2,
                        router_kw=dict(factory=_engine_factory(lm)))
    scaler = router.attach_autoscaler(FleetAutoscaler(
        router, min_replicas=1, max_replicas=3,
        high_watermark_ms=12.0, low_watermark_ms=1.0,
        hysteresis_steps=2, cooldown_steps=4, step_time_ms=2.0))
    replay_trace(router, trace)
    router.run_until_complete()
    return router, scaler


def test_autoscaler_decisions_replay_identically(lm):
    trace = clip_trace(
        scenario_trace("flash_crowd", 5, num_requests=18, vocab=V,
                       body_len_clip=(1, 16)),
        MAX_CONTEXT,
    )
    ra, sa = _autoscale_run(lm, trace)
    rb, sb = _autoscale_run(lm, trace)
    assert sa.decisions, "the flash crowd should provoke a decision"
    assert sa.decisions == sb.decisions
    assert {r: res.tokens for r, res in ra.results().items()} \
        == {r: res.tokens for r, res in rb.results().items()}
    assert ra.fleet_report()["autoscale"] == rb.fleet_report()["autoscale"]
    assert len(ra.results()) == len(trace)


def test_serve_cli_autoscale_flag_validation():
    from unicore_tpu.serve.cli import main

    with pytest.raises(SystemExit, match="needs --fleet"):
        main(["--demo", "--autoscale", "--num-requests", "2"])
    with pytest.raises(SystemExit, match="envelope is empty"):
        main(["--demo", "--fleet", "--autoscale",
              "--min-replicas", "3", "--max-replicas", "2",
              "--num-requests", "2"])
