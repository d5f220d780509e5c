"""``unicore-serve``: offline batch generation from a trained checkpoint
through the continuous-batching engine.

Two sources of model + prompts:

- ``--checkpoint ckpt.pt --dict dict.txt`` — serve a trained
  ``transformer_lm`` checkpoint (the framework's pickled-numpy format;
  convert torch checkpoints first, see checkpoint_utils).  Prompts come
  from ``--prompts FILE``: one request per line, whitespace-separated
  token ids (tokenization is a data-pipeline concern, not a serving
  one).
- ``--demo`` — a tiny randomly-initialized model + random prompts of
  mixed lengths: the zero-setup smoke path CI drives (at least 3
  concurrent mixed-length requests through the full
  admit/prefill/decode/evict machinery on CPU).

Output: one JSON object (``--json FILE`` or stdout) with per-request
generated ids, finish reasons, TTFT, the engine's aggregate stats and
``"steps"``, a summary of its step log (``serve/step_log.py``: the rows
the ring holds, by width class the median and 95th percentile of the
time the device had a step, the fill of the mixed program, the share of
steps launched ahead, the CPU a step by the two clocks).

``--fleet`` routes the same requests through a
:class:`~unicore_tpu.fleet.router.FleetRouter` over ``--replicas``
in-process engines instead (consistent-hash session affinity +
SLO-aware overflow, docs/serving.md#fleet); the report then carries
per-replica stats and drain records plus the fleet aggregate, and the
CI smoke asserts a clean end-of-run drain with zero leaked pages on
every pool.
"""

import argparse
import json
import logging
import os
import sys

import numpy as np

logger = logging.getLogger("unicore_tpu.serve.cli")


def make_parser():
    p = argparse.ArgumentParser(
        "unicore-serve",
        description="offline batch generation via the paged-KV "
                    "continuous-batching engine (docs/serving.md)",
    )
    src = p.add_argument_group("model source")
    src.add_argument("--checkpoint", help="framework checkpoint (.pt)")
    src.add_argument("--dict", dest="dict_path",
                     help="dict.txt the model was trained with")
    src.add_argument("--demo", action="store_true",
                     help="tiny random model + random prompts (smoke)")
    req = p.add_argument_group("requests")
    req.add_argument("--prompts",
                     help="file of whitespace-separated token-id lines")
    req.add_argument("--num-requests", type=int, default=4,
                     help="demo mode: how many random requests")
    req.add_argument("--prompt-len-range", default="3,17",
                     help="demo mode: 'lo,hi' prompt lengths")
    req.add_argument("--max-new-tokens", type=int, default=16)
    req.add_argument("--temperature", type=float, default=0.0)
    req.add_argument("--top-k", type=int, default=0)
    req.add_argument("--seed", type=int, default=1)
    eng = p.add_argument_group("engine")
    eng.add_argument("--page-size", type=int, default=16)
    eng.add_argument("--num-pages", type=int, default=64)
    eng.add_argument("--max-batch", type=int, default=8)
    eng.add_argument("--prefill-token-budget", type=int, default=512)
    eng.add_argument("--prefill-chunk", type=int, default=0,
                     help="ragged-step prefill chunk width: a prompt is "
                          "admitted in slices of at most this many "
                          "tokens per step (bounded TTFT under heavy "
                          "admission; 0 = the engine's default, 32)")
    eng.add_argument("--prefix-cache", choices=("on", "off"),
                     default="on",
                     help="shared-prefix KV page dedup: a repeat of a "
                          "warm system prompt becomes a page-table "
                          "lookup instead of a prefill (default: on)")
    flt = p.add_argument_group("fleet (docs/serving.md#fleet)")
    flt.add_argument("--fleet", action="store_true",
                     help="route through a FleetRouter over --replicas "
                          "in-process engines (consistent-hash session "
                          "affinity + SLO-aware overflow) instead of "
                          "one engine; the report carries per-replica "
                          "stats, drain records, and the fleet "
                          "aggregate")
    flt.add_argument("--replicas", type=int, default=2,
                     help="fleet mode: replica count (default: 2)")
    flt.add_argument("--sessions", type=int, default=4,
                     help="fleet mode: demo requests are spread over "
                          "this many session keys (affinity groups)")
    flt.add_argument("--max-failovers", type=int, default=2,
                     help="fleet failover: how many replica deaths one "
                          "request may survive (rerouted with its "
                          "generated tokens carried) before it "
                          "terminates with the typed reason "
                          "'replica_lost' (default: 2)")
    flt.add_argument("--suspect-steps", type=int, default=4,
                     help="fleet health: fleet steps of frozen "
                          "progress (replica holds work, retires "
                          "nothing) before a replica is marked "
                          "suspect (default: 4)")
    flt.add_argument("--progress-budget-steps", type=int, default=8,
                     help="fleet health: fleet steps of frozen "
                          "progress before a wedged replica is "
                          "declared DEAD and evicted without a drain "
                          "(default: 8)")
    flt.add_argument("--breaker-cooldown", type=int, default=8,
                     help="circuit breaker: fleet steps after an "
                          "eviction before a replacement replica may "
                          "probe for rejoin via one canary request "
                          "(default: 8)")
    flt.add_argument("--flap-limit", type=int, default=3,
                     help="circuit breaker: this many trips inside the "
                          "flap window hold the replica slot "
                          "quarantined — a flapping replica cannot "
                          "thrash the ring (default: 3)")
    flt.add_argument("--autoscale", action="store_true",
                     help="fleet mode: attach the deterministic "
                          "elastic scaling policy (docs/serving.md"
                          "#autoscaling) — scale-up boots replicas "
                          "off-ring through the breaker canary path, "
                          "scale-down retires the least-loaded "
                          "replica via the zero-drop drain")
    flt.add_argument("--min-replicas", type=int, default=1,
                     help="autoscale: never retire below this many "
                          "serving replicas (default: 1)")
    flt.add_argument("--max-replicas", type=int, default=4,
                     help="autoscale: never boot above this many "
                          "serving+booting replicas — at saturation "
                          "the engines shed deterministically instead "
                          "of growing (default: 4)")
    flt.add_argument("--scale-cooldown-steps", type=int, default=16,
                     help="autoscale: per-direction refractory period "
                          "between scaling decisions, in fleet steps "
                          "(default: 16)")
    flt.add_argument("--publish-dir", default=None,
                     help="deploy: watch this directory for published "
                          "weight manifests and roll them out live via "
                          "the canary-gated hot-swap pipeline "
                          "(docs/deployment.md)")
    flt.add_argument("--canary-steps", type=int, default=24,
                     help="deploy: fleet steps the canary replica "
                          "serves new weights off-ring before the SLO "
                          "gates decide promote vs rollback "
                          "(default: 24)")
    rob = p.add_argument_group(
        "robustness (docs/serving.md#robustness)")
    rob.add_argument("--max-waiting", type=int, default=None,
                     help="bound on the waiting queue (free decode "
                          "slots count as headroom); overflow is SHED "
                          "deterministically (reject-newest) instead of "
                          "growing without bound (default: unbounded)")
    rob.add_argument("--deadline-ms", type=float, default=None,
                     help="TTL applied to every request: blown requests "
                          "finish 'expired' and free their pages at the "
                          "next step boundary")
    from unicore_tpu.serve.scheduler import DEFAULT_REQUEST_RETRIES

    rob.add_argument("--request-retries", type=int,
                     default=DEFAULT_REQUEST_RETRIES,
                     help="per-request re-prefill budget: after this many "
                          "evictions a sequence is promoted and no longer "
                          "preempted (starvation protection) (default: "
                          f"{DEFAULT_REQUEST_RETRIES})")
    rob.add_argument("--drain-timeout", type=float, default=30.0,
                     help="seconds in-flight work gets to finish after "
                          "SIGTERM before it is shed (graceful drain)")
    rob.add_argument("--step-timeout", type=float, default=0.0,
                     help="arm a StepWatchdog around every prefill/decode "
                          "dispatch; a hung step dumps stacks + queue "
                          "depths and exits 87 (0 = off)")
    rob.add_argument("--progress-file", default=None,
                     help="append one line per decode step (the chaos "
                          "harness's mid-stream SIGTERM trigger)")
    p.add_argument("--json", dest="json_out",
                   help="write the report here instead of stdout")
    p.add_argument("--profile", metavar="DIR", default=None,
                   help="capture a jax profiler trace of the run (xplane "
                        "format) under DIR/jax_trace: the engine's "
                        "serve/* spans on the device operations' clock "
                        "(docs/serving.md#tracing-a-serving-process)")
    return p


def _demo_model(seed):
    import jax
    import jax.numpy as jnp

    from examples.lm.model import TransformerLMModel

    model = TransformerLMModel(
        vocab_size=97, padding_idx=0, decoder_layers=2,
        decoder_embed_dim=64, decoder_ffn_embed_dim=128,
        decoder_attention_heads=4, max_seq_len=256,
        emb_dropout=0.0, dropout=0.0, attention_dropout=0.0,
        activation_dropout=0.0, rel_pos=False, abs_pos=False, rotary=True,
    )
    proto = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(seed), proto)["params"]
    return model, params


def _checkpoint_model(path, dict_path):
    # the checkpoint->serve-params logic lives in deploy.loader (the
    # hot-swap path shares it); the CLI's only job is turning typed
    # deploy faults into an operator-facing exit
    from unicore_tpu.deploy import DeployError, load_serve_model

    try:
        return load_serve_model(path, dict_path)
    except DeployError as e:
        raise SystemExit(str(e)) from e


def _demo_requests(args, vocab, rng):
    from unicore_tpu.serve.scheduler import Request

    lo, hi = (int(x) for x in args.prompt_len_range.split(","))
    reqs = []
    for i in range(args.num_requests):
        n = int(rng.integers(lo, hi))
        prompt = rng.integers(1, vocab, size=(n,)).tolist()
        reqs.append(Request(
            prompt=[int(t) for t in prompt],
            max_new_tokens=args.max_new_tokens,
            temperature=args.temperature, top_k=args.top_k,
            seed=args.seed + i, request_id=f"demo-{i}",
            deadline_ms=args.deadline_ms,
        ))
    return reqs


def _file_requests(args, path):
    from unicore_tpu.serve.scheduler import Request

    reqs = []
    with open(path) as f:
        for i, line in enumerate(f):
            toks = [int(t) for t in line.split()]
            if not toks:
                continue
            reqs.append(Request(
                prompt=toks, max_new_tokens=args.max_new_tokens,
                temperature=args.temperature, top_k=args.top_k,
                seed=args.seed + i, request_id=f"req-{i}",
                deadline_ms=args.deadline_ms,
            ))
    return reqs


def _result_record(r):
    return {
        "request_id": r.request_id,
        "prompt": r.prompt,
        "tokens": r.tokens,
        "finish_reason": r.finish_reason,
        "queue_ms": None if r.queue_ms is None else round(r.queue_ms, 2),
        "ttft_ms": None if r.ttft_ms is None else round(r.ttft_ms, 2),
        "evictions": r.evictions,
    }


def _fleet_main(args, model, params, requests, shutdown):
    """``--fleet``: route the requests through a FleetRouter over
    ``--replicas`` in-process engines (session keys ``s{i mod
    --sessions}``), drive the fleet to completion, then drain every
    replica cleanly — the report must show zero leaked pages on EVERY
    pool and one drain record per replica (the CI smoke asserts it)."""
    from unicore_tpu.fleet.health import CircuitBreaker, ReplicaHealth
    from unicore_tpu.fleet.router import FleetRouter
    from unicore_tpu.serve import step_log
    from unicore_tpu.serve.engine import ServeEngine

    def make_engine(rid):
        del rid
        return ServeEngine(
            model, params, num_pages=args.num_pages,
            page_size=args.page_size, max_batch=args.max_batch,
            prefill_token_budget=args.prefill_token_budget,
            prefill_chunk=args.prefill_chunk,
            prefix_cache=args.prefix_cache == "on",
            max_waiting=args.max_waiting,
            request_retries=args.request_retries,
            drain_timeout=args.drain_timeout,
            step_timeout=args.step_timeout,
            progress_path=args.progress_file,
        )

    engines = {f"r{i}": make_engine(f"r{i}")
               for i in range(max(1, args.replicas))}
    router = FleetRouter(
        engines, shutdown=shutdown,
        # failover (docs/serving.md#failover-runbook): dead replicas
        # are evicted + replaced through the circuit breaker's canary
        # probe; the same engine recipe serves as the replacement
        factory=make_engine,
        max_failovers=args.max_failovers,
        health=ReplicaHealth(
            suspect_steps=args.suspect_steps,
            dead_steps=args.progress_budget_steps,
        ),
        breaker=lambda rid: CircuitBreaker(
            cooldown_steps=args.breaker_cooldown,
            flap_limit=args.flap_limit,
        ),
    )
    if args.autoscale:
        from unicore_tpu.fleet.autoscaler import FleetAutoscaler

        # the policy attaches itself via the router hook; its
        # describe() rides out through fleet_report()["autoscale"]
        router.attach_autoscaler(FleetAutoscaler(
            router,
            min_replicas=args.min_replicas,
            max_replicas=args.max_replicas,
            cooldown_steps=args.scale_cooldown_steps,
        ))
    if args.publish_dir:
        from unicore_tpu.deploy import DeploySubscriber, RolloutController

        # the controller attaches itself to the router; its describe()
        # rides out through fleet_report()["deploy"]
        RolloutController(
            router, DeploySubscriber(args.publish_dir),
            canary_steps=args.canary_steps,
        )
    logger.info(
        "fleet: %d request(s) over %d session(s) into %d replica(s) "
        "(pool %d pages x %d slots each, max batch %d)",
        len(requests), args.sessions, len(engines),
        args.num_pages, args.page_size, args.max_batch,
    )
    for i, req in enumerate(requests):
        router.submit(req, session_key=f"s{i % max(1, args.sessions)}")
    router.run_until_complete()
    # end-of-run drain: every replica closes admission and reports —
    # on a finished workload this is a clean zero-shed drain, and it
    # proves the pools end idle exactly like the solo path's report
    drains = router.drain()
    results = router.results()
    # audit every pool the run ever touched: the originals, anything
    # the autoscaler booted (still serving), and anything it retired
    audited = dict(engines)
    audited.update(router.engines)
    audited.update(router._retired_engines)
    pool_clean = all(e.pool.is_idle() for e in audited.values())
    for eng in audited.values():
        eng.pool.check_invariants()
        eng.moe_stats()  # the routing counters, read into stats once
    report = {
        "results": [_result_record(results[r.request_id])
                    for r in requests],
        "replicas": {
            rid: {
                "stats": {k: (round(v, 4) if isinstance(v, float) else v)
                          for k, v in engines[rid].stats.items()},
                "steps": step_log.summary(engines[rid].step_log.rows()),
                # a replica evicted by failover has no drain record —
                # the fleet report's "lost" section carries its story
                "drain": drains.get(rid),
                "pool_clean": engines[rid].pool.is_idle(),
            }
            for rid in sorted(engines)
        },
        "fleet": router.fleet_report(),
        "sessions": {s: rids
                     for s, rids in sorted(
                         router.session_replicas.items())},
        "pool_clean": pool_clean,
    }
    text = json.dumps(report, indent=2)
    if args.json_out:
        with open(args.json_out, "w") as f:
            f.write(text + "\n")
        logger.info("wrote %s", args.json_out)
    else:
        print(text)
    return 0


def main(argv=None):
    logging.basicConfig(
        format="%(asctime)s | %(levelname)s | %(name)s | %(message)s",
        level="INFO", stream=sys.stderr,
    )
    args = make_parser().parse_args(argv)
    if not args.demo and not args.checkpoint:
        raise SystemExit("need --checkpoint (with --dict) or --demo")
    # fail fast on an impossible autoscale envelope — a policy that
    # could neither boot nor retire must die at the parser, not
    # mid-flood (ISSUE 20 satellite)
    if args.autoscale and not args.fleet:
        raise SystemExit("--autoscale needs --fleet (the scaling "
                         "policy steps with the fleet router)")
    if args.min_replicas > args.max_replicas:
        raise SystemExit(
            f"--min-replicas {args.min_replicas} > --max-replicas "
            f"{args.max_replicas}: the autoscale envelope is empty"
        )

    from unicore_tpu.utils import configure_compile_cache

    configure_compile_cache()
    if not args.profile:
        return _serve(args)
    import jax

    # host spans on, the Python tracer off: it would record every
    # Python call and slow the host path the trace is taken to time
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    with jax.profiler.trace(os.path.join(args.profile, "jax_trace"),
                            profiler_options=options):
        return _serve(args)


def _serve(args):
    """Build the model, the requests and the engine (or the fleet),
    run to completion, write the report."""
    from unicore_tpu.ops.backend import dispatch_report
    from unicore_tpu.serve import step_log
    from unicore_tpu.serve.engine import ServeEngine

    if args.demo:
        model, params = _demo_model(args.seed)
        rng = np.random.default_rng(args.seed)
        requests = (_file_requests(args, args.prompts) if args.prompts
                    else _demo_requests(args, model.vocab_size, rng))
    else:
        if not args.dict_path:
            raise SystemExit("--checkpoint needs --dict")
        if not args.prompts:
            raise SystemExit("--checkpoint needs --prompts")
        model, params = _checkpoint_model(args.checkpoint, args.dict_path)
        requests = _file_requests(args, args.prompts)

    for req in requests:
        bad = [t for t in req.prompt if not 0 <= t < model.vocab_size]
        if bad:
            raise SystemExit(
                f"{req.request_id}: prompt ids {bad[:5]} outside the "
                f"model's vocab [0, {model.vocab_size}) — wrong "
                "dictionary for this checkpoint?"
            )

    from unicore_tpu.resilience.preemption import GracefulShutdown

    # SIGTERM/SIGINT -> graceful drain: admission closes at the next
    # step boundary, in-flight work gets --drain-timeout to finish or
    # is shed, and the process still writes its report and exits 0
    shutdown = GracefulShutdown().install()
    if args.fleet:
        try:
            return _fleet_main(args, model, params, requests, shutdown)
        finally:
            shutdown.uninstall()
    engine = ServeEngine(
        model, params, num_pages=args.num_pages, page_size=args.page_size,
        max_batch=args.max_batch,
        prefill_token_budget=args.prefill_token_budget,
        prefill_chunk=args.prefill_chunk,
        prefix_cache=args.prefix_cache == "on",
        max_waiting=args.max_waiting,
        request_retries=args.request_retries,
        drain_timeout=args.drain_timeout, shutdown=shutdown,
        step_timeout=args.step_timeout,
        progress_path=args.progress_file,
    )
    logger.info(
        "serving %d request(s): pool %d pages x %d slots, max batch %d",
        len(requests), args.num_pages, args.page_size, args.max_batch,
    )
    try:
        results = engine.generate(requests)
    finally:
        shutdown.uninstall()
    pool_clean = engine.pool.is_idle()
    engine.pool.check_invariants()
    engine.moe_stats()  # the routing counters, read into stats once
    report = {
        "results": [_result_record(r) for r in results],
        "stats": {k: (round(v, 4) if isinstance(v, float) else v)
                  for k, v in engine.stats.items()},
        "steps": step_log.summary(engine.step_log.rows()),
        "drain": engine.drain_report,
        "pool_clean": pool_clean,
        # which path each compiled width's attention took
        "kernel_dispatch": dispatch_report(),
    }
    if shutdown.requested and engine.drain_report is None:
        # the signal landed after the last step boundary: nothing was
        # in flight, but the operator still gets a drain record with
        # the same shape (and signal) a mid-stream drain reports
        import signal as _signal

        report["drain"] = {
            "requested": True,
            "signal": (None if shutdown.signum is None
                       else _signal.Signals(shutdown.signum).name),
            "drain_ms": 0.0,
            "drain_timeout_s": args.drain_timeout,
            "shed": 0, "expired": 0,
            "deadline_exceeded": False,
            "pool_idle": pool_clean,
        }
    text = json.dumps(report, indent=2)
    if args.json_out:
        with open(args.json_out, "w") as f:
            f.write(text + "\n")
        logger.info("wrote %s", args.json_out)
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
