"""From the facts of a run to its result object."""

import math
import statistics
import time

from . import check, spec, stats, trace as trace_lib
from .serve_cell import OK_REASONS
from .device import log
from .tracing import WINDOW_SPAN


def _metric(value, unit):
    return {"value": value, "unit": unit}


def _trace_ctx(trace_dir, devices):
    """The traced window reduced: per-device operations, host spans, the
    window's bounds in the trace's clock, ``busy_s`` and ``window_s``."""
    path = trace_lib.find_xplane(trace_dir)
    tr = trace_lib.read_xplane(path)
    for row in trace_lib.describe_xplane(path):
        log("trace plane", *row)
    win = tr.span(WINDOW_SPAN)
    planes = sorted(tr.devices)[:len(devices)] or []
    if not planes:
        raise RuntimeError(f"no device plane in the trace ({path})")
    if win is None:
        raise RuntimeError(f"no {WINDOW_SPAN} span in the trace ({path})")
    t0, t1 = win[1], win[1] + win[2]
    busy = [trace_lib.busy_ns(tr.devices[p], t0, t1) for p in planes]
    log("top device operations (name, seconds):",
        trace_lib.top_ops(tr.devices[planes[0]], t0, t1, 30))
    host_names = {}
    for name, _, dur in tr.host:
        host_names[name] = host_names.get(name, 0) + dur
    log("host spans by total seconds:",
        sorted(((n, d / 1e9) for n, d in host_names.items()),
               key=lambda kv: -kv[1])[:25])
    return {
        "trace": tr, "planes": planes, "t0": t0, "t1": t1,
        "busy_s": statistics.mean(busy) / 1e9,
        "window_s": (t1 - t0) / 1e9,
    }


def _breakdown(tctx):
    tr, t0, t1 = tctx["trace"], tctx["t0"], tctx["t1"]
    ops = tr.devices[tctx["planes"][0]]
    gaps = trace_lib.idle_gaps(ops, t0, t1)
    inside = [s for s in tr.host if s[0] != WINDOW_SPAN]
    return {
        "device_ops": trace_lib.top_ops(ops, t0, t1, 10),
        "idle_gaps": trace_lib.attribute_gaps(gaps, inside, 10),
    }


def _per_layer(cell, ctx):
    """Each of the cell's per-layer metrics through its own reader; a
    reader that finds nothing to read returns None and the metric is
    left out of the line."""
    out = {}
    for m in cell["per_layer"]:
        value = spec.load_reader(m["name"], cell["base"])(ctx)
        if value is None:
            log(f"per-layer {m['name']}: nothing to read")
            continue
        out[m["name"]] = _metric(float(value), m["unit"])
    return out


def _finish(cell, verdict, attempted, failed, e2e_values, ctx, trace,
            memory_peak):
    dev = {"memory_peak_bytes": int(memory_peak)}
    result = {"correct": verdict.correct, "attempted": int(attempted),
              "failed": int(failed)}
    if trace:
        result["metrics"] = _per_layer(cell, ctx)
        dev["busy_s"] = ctx["busy_s"]
        dev["window_s"] = ctx["window_s"]
        result["breakdown"] = _breakdown(ctx)
    else:
        units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
        missing = sorted(set(units) - set(e2e_values))
        if missing:
            raise RuntimeError(f"the cell reports no value for {missing}")
        result["metrics"] = {k: _metric(float(e2e_values[k]), units[k])
                             for k in units}
    result["device"] = dev
    return result


# -- training -----------------------------------------------------------

def train_result(cell, seed, facts, verdict, trace, devices, peaks):
    probe = facts["probe"]
    window_s = facts["window_s"]
    limits = cell["workload"]["limits"]
    log(f"window {window_s:.4f} s, {probe.window_updates} updates, "
        f"{probe.window_tokens} non-pad tokens; mesh {facts['mesh']}; "
        f"host timers {facts['host_timers']}")
    log("losses (bits/token, every update) "
        f"{[round(x, 4) for x in probe.losses]}")
    log(f"kernel dispatch {facts['dispatch']}")
    gaps = [b - a for a, b in zip(probe.step_clock, probe.step_clock[1:])]
    if gaps:
        worst = sorted(range(len(gaps)), key=lambda i: -gaps[i])[:5]
        log(f"dispatch-to-dispatch ms: median "
            f"{statistics.median(gaps) * 1e3:.3f} over {len(gaps)}; the five "
            f"longest (update, ms) "
            f"{[(i, round(gaps[i] * 1e3, 1)) for i in worst]}")
        inside = [i for i in range(len(gaps))
                  if probe.step_clock[i] >= probe.t0]
        probe.watch.report(log, probe.t0, [
            (probe.step_clock[i], gaps[i], f"update {i} to the next dispatch")
            for i in sorted(inside, key=lambda i: -gaps[i])[:2]])

    verdict.fact("compiles_in_window == 0", probe.compiles_in_window == 0,
                 f"{probe.compiles_in_window} compiled")
    check.dispatch_as_expected(verdict, facts["dispatch"],
                               cell["workload"].get("expect_dispatch", {}))
    losses = probe.losses
    verdict.fact("every loss finite",
                 bool(losses) and all(math.isfinite(x) for x in losses))
    tail = losses[-5:]
    verdict.fact("loss falls over the run",
                 len(losses) > 5 and statistics.median(tail) < losses[0],
                 f"first {losses[:1]} last five {tail}")
    program = {"losses": losses[:probe.CHECK_STEPS],
               "first_grad_norms": facts["first_grad_norms"],
               "param_change_norms": facts["param_change_norms"]}
    t = time.perf_counter()
    reference = check.train_numbers(facts, cell, seed, devices=devices)
    log(f"reference: three updates followed in {time.perf_counter() - t:.2f} s")
    check.compare_train(verdict, program, reference, limits)

    attempted = probe.window_updates
    failed = attempted - probe.updates_done
    e2e = {"train_tokens_per_s": probe.window_tokens / window_s,
           "setup_s": probe.setup_s}
    ctx = None
    if trace:
        ctx = _trace_ctx(probe.trace_dir, devices)
        ctx.update({"cell": cell, "peaks": peaks, "chips": len(devices),
                    "updates": probe.window_updates,
                    "tokens": probe.window_tokens, "host_window_s": window_s,
                    "rows_per_update": cell["traffic"]["batch_per_chip"]
                    * len(devices)})
    return _finish(cell, verdict, attempted, failed, e2e, ctx, trace,
                   probe.memory_peak)


# -- serving ------------------------------------------------------------

def serve_latencies(requests):
    """``(ttft_ms, itl_ms)`` of tracked requests: time to first token
    from when each was DUE (not from when it was sent), and the gaps
    between consecutive tokens of one request, pooled."""
    ttft = [(tr.token_times[0] - tr.due) * 1e3 for tr in requests
            if tr.token_times]
    itl = [(b - a) * 1e3 for tr in requests
           for a, b in zip(tr.token_times, tr.token_times[1:])]
    return ttft, itl


def serve_result(cell, seed, facts, verdict, trace, devices, peaks):
    sent, kind = facts["sent"], facts["kind"]
    window_s = facts["window_s"]
    limits = cell["workload"]["limits"]
    t_open, t_close = facts["t_open"], facts["t_close"]
    in_window = [tr for tr in sent if tr.in_window]
    ok = [tr for tr in in_window if tr.finished_at is not None
          and tr.seq.finish_reason in OK_REASONS]
    reasons = {}
    for tr in in_window:
        r = tr.seq.finish_reason or "unfinished"
        reasons[r] = reasons.get(r, 0) + 1
    late = [tr.sent - tr.due for tr in sent if tr.sent is not None]
    log(f"window {window_s:.3f} s; sent {len(sent)}, in window "
        f"{len(in_window)}, finished ok {len(ok)}; reasons {reasons}; "
        f"pool {facts['pool_dtype']}; widths warmed {facts['widths_warmed']}")
    if late:
        log(f"generator lateness ms: median {statistics.median(late) * 1e3:.3f} "
            f"max {max(late) * 1e3:.3f} over {len(late)}")
    delta = {k: facts["stats_close"][k] - facts["stats_open"].get(k, 0)
             for k in ("prefills", "decode_steps", "generated_tokens",
                       "host_faults", "quarantined", "shed",
                       "pool_exhausted_recoveries")
             if k in facts["stats_close"]}
    log(f"engine counters over the window {delta}; peak pool occupancy "
        f"{facts['stats_close'].get('peak_pool_occupancy')}; prefix "
        f"{facts['prefix_open']} -> {facts['prefix_close']}")
    log(f"kernel dispatch {facts['dispatch']}")

    steps = [(t, dt, w) for t, dt, w in facts["steps"]
             if t_open <= t < t_close and w]
    # the main loop's longest stretches: a serve_step, or what lay between
    # the end of one and the start of the next
    loop = [(t, dt, f"serve_step width {w}") for t, dt, w in steps]
    loop += [(a[0] + a[1], b[0] - a[0] - a[1], "between two serve_steps")
             for a, b in zip(steps, steps[1:])]
    facts["watch"].report(log, t_open, sorted(loop, key=lambda x: -x[1])[:2])
    ttft, itl = serve_latencies(in_window)
    e2e = {"setup_s": facts["setup_s"]}
    if ttft:
        e2e["serve_ttft_p90_ms"] = stats.percentile(ttft, 90)
        log(f"ttft ms: median {statistics.median(ttft):.3f} p90 "
            f"{e2e['serve_ttft_p90_ms']:.3f} over {len(ttft)} "
            f"({stats.samples_beyond(ttft, 90)} beyond)")
    if itl:
        e2e["serve_itl_p95_ms"] = stats.percentile(itl, 95)
        log(f"inter-token ms: median {statistics.median(itl):.3f} p95 "
            f"{e2e['serve_itl_p95_ms']:.3f} over {len(itl)} "
            f"({stats.samples_beyond(itl, 95)} beyond)")
    answered = sum(len(tr.seq.generated) for tr in ok)
    e2e["serve_tokens_per_s"] = answered / window_s
    log(f"answer tokens of requests completed in the window {answered}, "
        f"{e2e['serve_tokens_per_s']:.3f} per s; steps in window "
        f"{len(steps)}")

    verdict.fact("compiles_in_window == 0", facts["compiles_in_window"] == 0,
                 f"{facts['compiles_in_window']} compiled")
    check.dispatch_as_expected(verdict, facts["dispatch"],
                               cell["workload"].get("expect_dispatch", {}))
    sample = check.serve_sample(sent, seed,
                                cell["workload"].get("reference_requests", 6))
    served = sum(len(tr.seq.generated) for tr in sample)
    shared = sum(1 for tr in sample if tr.spec.get("shared_tokens"))
    log(f"reference sample: {len(sample)} requests, {served} served tokens, "
        f"{shared} served over a shared document, longest "
        f"{max((len(tr.spec['prompt']) + len(tr.seq.generated) for tr in sample), default=0)}")
    t = time.perf_counter()
    numbers = check.serve_numbers(
        *check.serve_gaps(facts["params"], cell, sample))
    log(f"reference: {len(sample)} sequences in {time.perf_counter() - t:.2f} s")
    check.compare_serve(verdict, numbers, limits)

    attempted = len(in_window)
    failed = attempted - len(ok)
    ctx = None
    if trace:
        ctx = _trace_ctx(facts["trace_dir"], devices)
        ctx.update({
            "cell": cell, "peaks": peaks, "chips": len(devices),
            "steps": steps, "host_window_s": window_s,
            "rows": [r for (t, _, w), r in zip(facts["steps"], facts["rows"])
                     if t_open <= t < t_close and w],
            "pool_itemsize": 2 if "16" in facts["pool_dtype"] else 4,
            "prefix_tokens_saved": facts["prefix_close"]["tokens_saved"]
            - facts["prefix_open"]["tokens_saved"],
            "prompt_tokens": sum(len(tr.spec["prompt"]) for tr in sent
                                 if tr.sent is not None
                                 and t_open <= tr.sent < t_close),
        })
    return _finish(cell, verdict, attempted, failed, e2e, ctx, trace,
                   facts["memory_peak"])
