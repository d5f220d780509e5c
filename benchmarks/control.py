#!/usr/bin/env python3
"""Read, on the chip, the numbers a cell's limits are set from.

    python benchmarks/control.py --workload <name> --seeds 1,2,3 \
        [--seconds 8] [--side program|control]

For each seed, in this one process: run the cell for a short window, then
compare with the plain reference (a) what the program produced and (b)
the CONTROL, one precision below what the configuration states.  For a
train cell (bf16) the control is the reference itself, put in the
program's place with float8 matmul operands.  For a serve cell (float32)
``--side control`` is the ENGINE handed the same weights rounded to
bfloat16, and ``--side program`` prints beside the program's numbers
those of the reference in bfloat16 put in its place.  A sound limit lies
above the largest of (a) over many seeds and below the smallest of (b).
Prints one JSON line per seed.  The benchmark's own runs never run this.
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

from benchmarks.lib import check, spec  # noqa: E402
from benchmarks.lib.device import log  # noqa: E402

TRAIN_CONTROL = "fp8"  # the precision below a bf16 train cell's


def readings(cell, seed, seconds, devices, workdir, engine_box, side):
    """The numbers of one seed: a train cell's ``{program, control}``,
    a serve cell's :func:`serve_readings`.  ``engine_box`` is a list the
    serve path keeps its one engine in between seeds."""
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    cell["root_program"] = CHECKOUT
    if cell["workload"]["runner"] == "train":
        from benchmarks.lib import train_cell

        facts = train_cell.run(cell, seed, seconds, False, devices, workdir,
                               time.perf_counter())
        probe = facts["probe"]
        program = {"losses": probe.losses[:probe.CHECK_STEPS],
                   "first_grad_norms": facts["first_grad_norms"],
                   "param_change_norms": facts["param_change_norms"]}
        reference = check.train_numbers(facts, cell, seed, devices=devices)
        control = check.train_numbers(facts, cell, seed, precision=TRAIN_CONTROL,
                                      devices=devices)

        def numbers(got):
            return {
                "loss_rel_gap": max(abs(a - b) / abs(b) for a, b in
                                    zip(got["losses"], reference["losses"])),
                "first_grad_leaf_gap": check.worst_leaf_gap(
                    got["first_grad_norms"],
                    reference["first_grad_norms"])[0],
                "param_change_leaf_gap": check.worst_leaf_gap(
                    got["param_change_norms"],
                    reference["param_change_norms"])[0],
            }
        return {"program": numbers(program), "control": numbers(control)}
    return serve_readings(cell, seed, seconds, devices, workdir, engine_box,
                          side)


def serve_readings(cell, seed, seconds, devices, workdir, engine_box, side):
    """One seed of a serve cell on one ``side``.  ``program``: the engine
    as the cell runs it; beside its numbers, those of the reference put
    in its place one precision down (``reference_lower``: what it would
    serve is what bfloat16 puts first, so its share is 1 by definition).
    ``control``: the ENGINE one precision down, handed the same weights
    rounded to bfloat16, its served tokens judged like the program's."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import serve_cell, weights

    scales = cell["config"].get("weight_scales")
    dtype = jnp.bfloat16 if side == "control" else None
    # set-up is long: one engine serves every seed, each with weights of
    # its own (the old ones freed first: two sets do not fit beside the
    # pool)
    if engine_box:
        engine, abstract, widths = engine_box
        engine.params = None
        params = weights.make(abstract, seed, scales=scales)
        engine.params = params
    else:
        engine, params, widths = serve_cell.build(cell, seed, dtype)
        engine_box.extend([engine, weights.abstract_of(params), widths])
        log(f"side {side}: weights "
            f"{jax.tree_util.tree_leaves(params)[0].dtype}, pool "
            f"{jax.tree_util.tree_leaves(engine.pages)[0].dtype}")
    facts = serve_cell.drive(cell, engine, params, widths, seed, seconds,
                             False, devices, workdir, time.perf_counter())
    while engine.has_work():   # what the window left in flight
        engine.serve_step()
    engine.collect_finished()
    sample = check.serve_sample(
        facts["sent"], seed, cell["workload"].get("reference_requests", 6))
    served = [t for tr in sample for t in tr.seq.generated]
    log(f"seed {seed}: {len(set(served))} distinct tokens among "
        f"{len(served)} served in the sample")
    if dtype is not None:
        # the reference reads the float32 weights the engine's were
        # rounded from; both do not fit beside the pool
        engine.params = None
        facts["params"] = params = None
        params = weights.make(
            jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32),
                engine_box[1]), seed, scales=scales)
    served_gaps, lower_gaps = check.serve_gaps(params, cell, sample)
    out = {"side": side,
           "engine": check.serve_numbers(served_gaps, lower_gaps)}
    if side == "program":
        out["reference_lower"] = check.serve_numbers(lower_gaps, lower_gaps)
    del facts, params
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--side", choices=("program", "control"),
                    default="program", help="serve cells: which engine")
    args = ap.parse_args(argv)

    from benchmarks.lib import device

    cell = spec.load_cell(args.workload, CHECKOUT)
    device.configure_compile_cache()
    try:
        devices = device.require_tpu(cell["entry"]["chips"])
    except device.NoChip as e:
        sys.stderr.write(f"benchmarks/control.py: {e}\n")
        return 1
    workdir = os.path.join(CHECKOUT, ".bench_work", "control_" + cell["name"])
    engine_box = []
    for seed in (int(s) for s in args.seeds.split(",")):
        out = readings(spec.load_cell(args.workload, CHECKOUT), seed,
                       args.seconds, devices, workdir, engine_box, args.side)
        print(json.dumps({"workload": args.workload, "seed": seed, **out}),
              flush=True)
    shutil.rmtree(workdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
