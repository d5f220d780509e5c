#!/usr/bin/env python3
"""Find the knee of an open-loop serve cell, once, on the chip.

    python benchmarks/sweep.py --workload opt13b_chat --rates 2,3,4,5,6 --seconds 20

One engine, one set-up; for each rate a ramp, a window and a drain of the
cell's own traffic mix at that rate.  Prints one JSON line per rate: the
tails, the tokens completed per second, and the backlog left when the
window closed (a backlog that grows with the rate says the knee is
passed).  The rate written into the traffic file is 0.8 of the knee; the
benchmark never searches for one.
"""

import argparse
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

from benchmarks.lib import spec, stats  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)

    from benchmarks.lib import device, serve_cell, traffic

    cell = spec.load_cell(args.workload, CHECKOUT)
    device.configure_compile_cache()
    try:
        device.require_tpu(cell["entry"]["chips"])
    except device.NoChip as e:
        sys.stderr.write(f"benchmarks/sweep.py: {e}\n")
        return 1
    engine, _, _ = serve_cell.build(cell, args.seed)
    tr = dict(cell["traffic"])
    for rate in (float(r) for r in args.rates.split(",")):
        tr["rate_per_s"] = rate
        schedule = traffic.open_loop_schedule(
            tr, args.seed, tr["ramp_s"] + args.seconds,
            cell["config"]["vocab_size"])
        backlog = {}
        sent, driver, (t_open, t_close) = serve_cell.run_open_loop(
            engine, schedule, tr["ramp_s"], args.seconds, tr["drain_s"],
            on_close=lambda: backlog.update(
                waiting=len(engine.scheduler.waiting),
                running=len(engine.scheduler.running)))
        inw = [t for t in sent if t.in_window]
        ok = [t for t in inw if t.finished_at is not None]
        ttft = [(t.token_times[0] - t.due) * 1e3 for t in inw if t.token_times]
        itl = [(b - a) * 1e3 for t in inw
               for a, b in zip(t.token_times, t.token_times[1:])]
        done_in = [t for t in sent if t.finished_at is not None
                   and t_open <= t.finished_at < t_close]
        steps = [dt for s, dt, w in driver.steps if t_open <= s < t_close and w]
        print(json.dumps({
            "rate_per_s": rate, "in_window": len(inw), "finished": len(ok),
            "ttft_p50_ms": statistics.median(ttft),
            "ttft_p90_ms": stats.percentile(ttft, 90),
            "itl_p50_ms": statistics.median(itl),
            "itl_p95_ms": stats.percentile(itl, 95),
            "tokens_per_s_completed_in_window":
                sum(len(t.seq.generated) for t in done_in) / args.seconds,
            "backlog_at_close": backlog,
            "drain_s": max((t.finished_at for t in ok), default=t_close)
            - t_close,
            "step_ms_p50": statistics.median(steps) * 1e3,
            "steps": len(steps),
            "recoveries": engine.stats["pool_exhausted_recoveries"],
            "evictions": engine.scheduler.num_evictions,
        }), flush=True)
        while engine.has_work():   # whatever the drain limit left behind
            engine.serve_step()
        engine.collect_finished()
    return 0


if __name__ == "__main__":
    sys.exit(main())
