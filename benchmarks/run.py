#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds weights and data on the device from ``--seed``, warms exactly the
shapes of the cell through the calls the window will make, measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints as its LAST line one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``.  Everything else worth reading is on earlier lines.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a short traced window of its own.

No TPU, or fewer chips than the cell asks for, is a failure: exit code 1
and no result line, never a fall-back to the CPU.
"""

import time

PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

from benchmarks.lib import spec  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(cell, seed, seconds, trace, devices, *, peaks, workdir,
             process_t0=PROCESS_T0):
    """Everything after the look for a chip: run, compare, reduce.
    Returns the result object (the last line's content)."""
    from benchmarks.lib import check, device, report

    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    cell["root_program"] = CHECKOUT
    runner = cell["workload"]["runner"]
    verdict = check.Verdict()
    try:
        if runner == "train":
            from benchmarks.lib import train_cell

            facts = train_cell.run(cell, seed, seconds, trace, devices,
                                   workdir, process_t0)
            result = report.train_result(cell, seed, facts, verdict, trace,
                                         devices, peaks)
        elif runner == "serve":
            from benchmarks.lib import serve_cell

            facts = serve_cell.run(cell, seed, seconds, trace, devices,
                                   workdir, process_t0)
            result = report.serve_result(cell, seed, facts, verdict, trace,
                                         devices, peaks)
        else:
            raise spec.SpecError(f"unknown runner {runner!r} in the "
                                 f"workload file of {cell['name']}")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    result["device"].update(device.describe(devices))
    return result


def main(argv=None):
    args = parse(argv)
    try:
        cell = spec.load_cell(args.workload, CHECKOUT)
        import unicore_tpu  # noqa: F401  (the system under test)
    except (spec.SpecError, ImportError) as e:
        sys.stderr.write(f"benchmarks/run.py: {e}\n")
        return 1

    import jax

    from benchmarks.lib import device

    cache_dir = device.configure_compile_cache()
    try:
        devices = device.require_tpu(cell["entry"]["chips"])
        peaks = spec.load_peaks(devices[0].device_kind, cell["base"])
    except (device.NoChip, spec.SpecError) as e:
        sys.stderr.write(f"benchmarks/run.py: {e}\n")
        return 1
    device.log(f"cell {cell['name']} seed {args.seed} seconds {args.seconds} "
               f"trace {args.trace}; {device.describe(devices)}; "
               f"compile cache {cache_dir}; jax {jax.__version__}")
    workdir = os.path.join(CHECKOUT, ".bench_work", cell["name"])
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, peaks=peaks, workdir=workdir)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
