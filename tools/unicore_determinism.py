#!/usr/bin/env python
"""Runtime determinism harness — the dynamic half of unicore-lint Pass 5.

Static analysis (UL401-UL403) certifies that the compiled programs and
the host planning code CONTAIN no nondeterministic construct; this tool
certifies that the programs BEHAVE deterministically: it captures the
exact argument tuple of a real dispatch (via the ``_input_capture``
hooks in ``Trainer._dispatch_train_step`` and ``ServeEngine._dispatch``,
copied to host BEFORE the donating call invalidates the buffers), then
replays the jitted step on those identical inputs twice and bit-compares
every output leaf via its raw bytes (NaN-safe — two NaNs with the same
payload compare equal, which is exactly the replay contract).

On divergence it does better than "the bit-compare went red": the jaxpr
is re-executed primitive by primitive, eagerly, recording a sha1 digest
of every equation's outputs; two passes over the same inputs then name
the FIRST equation whose digests differ.  This is prefix bisection
collapsed into one linear pass per run — re-running prefixes of length
1..N and diffing would identify the same equation at O(N^2) eager cost;
digest streams pay O(N) twice.

The XLA:CPU caveat (same honesty as Pass 4/5 static docs): on CPU, XLA
executes scatters and reductions serialized, so a green double-run here
does not certify a GPU's atomics.  What it DOES certify — that the step
is free of embedded run-to-run state (host callbacks smuggling
wall-clock or iteration order into the program, stateful RNG, capture
bugs in the replay plumbing itself) — is backend-independent, and it is
the property every chaos/failover replay oracle in this repo stands on.

Usage:
  python tools/unicore_determinism.py --train --serve --json out.json
  # exit 0 iff every requested surface double-ran bit-exact
"""

import argparse
import hashlib
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

# the shrunk 2x64 trainer every host-side bench micro uses: small
# enough that the double compile is cheap, real enough that the step
# carries the full update (adam, clip, guard, scan)
TRAIN_CFG = dict(batch=8, warmup=2, seq=128, layers=2, dim=64,
                 ffn=128, heads=2)


def _provision(cpu_devices):
    """Pin the CPU platform (and an optional virtual device count)
    BEFORE jax initializes."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if cpu_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={cpu_devices}"
            ).strip()


# ----------------------------------------------------------------------
# core primitives
# ----------------------------------------------------------------------

def bitwise_compare(tree_a, tree_b):
    """Compare two pytrees leaf-by-leaf on raw bytes.  Returns
    ``(mismatches, bytes_compared, n_leaves)`` where mismatches is
    ``[(leaf_path, reason), ...]``."""
    import jax
    import numpy as np

    la = jax.tree_util.tree_flatten_with_path(tree_a)[0]
    lb = jax.tree_util.tree_flatten_with_path(tree_b)[0]
    mismatches = []
    bytes_compared = 0
    if len(la) != len(lb):
        return ([("<tree>", f"{len(la)} vs {len(lb)} leaves")], 0,
                max(len(la), len(lb)))
    for (pa, a), (_, b) in zip(la, lb):
        name = jax.tree_util.keystr(pa)
        a, b = np.asarray(a), np.asarray(b)
        if a.shape != b.shape or a.dtype != b.dtype:
            mismatches.append(
                (name, f"{a.dtype}{a.shape} vs {b.dtype}{b.shape}")
            )
            continue
        bytes_compared += a.nbytes
        if a.tobytes() != b.tobytes():
            n = int(np.sum(
                np.frombuffer(a.tobytes(), np.uint8)
                != np.frombuffer(b.tobytes(), np.uint8)
            ))
            mismatches.append((name, f"{n} differing byte(s)"))
    return mismatches, bytes_compared, len(la)


def double_run(fn, host_args, runs=2):
    """Call ``fn`` ``runs`` times on the SAME host-side argument tuple
    and fetch every output to host.  Each call transfers the host
    arrays to device afresh, so a donating jit consumes a private copy
    every run — the host originals are never invalidated.  Returns
    ``(outputs, ms_per_run)``; the first run may include a compile."""
    import jax

    outs, ms = [], []
    for _ in range(runs):
        t0 = time.perf_counter()
        out = jax.device_get(fn(*host_args))
        ms.append((time.perf_counter() - t0) * 1e3)
        outs.append(out)
    return outs, ms


def digest_stream(closed, flat_args):
    """Eagerly re-execute a ClosedJaxpr equation by equation (the
    ``eval_jaxpr`` recipe: ``get_bind_params`` + ``bind``), returning a
    sha1 digest of every equation's outputs in order."""
    import jax
    import numpy as np

    from jax.extend import core

    jaxpr = closed.jaxpr
    env = {}

    def read(v):
        return v.val if isinstance(v, core.Literal) else env[v]

    for v, c in zip(jaxpr.constvars, closed.consts):
        env[v] = c
    if len(flat_args) != len(jaxpr.invars):
        raise ValueError(
            f"flat_args has {len(flat_args)} leaves, jaxpr expects "
            f"{len(jaxpr.invars)}"
        )
    for v, a in zip(jaxpr.invars, flat_args):
        env[v] = a
    stream = []
    for eqn in jaxpr.eqns:
        subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
        invals = [read(v) for v in eqn.invars]
        ans = eqn.primitive.bind(*subfuns, *invals, **bind_params)
        outs = ans if eqn.primitive.multiple_results else [ans]
        h = hashlib.sha1()
        for o in outs:
            h.update(np.asarray(jax.device_get(o)).tobytes())
        stream.append(h.hexdigest())
        for v, o in zip(eqn.outvars, outs):
            env[v] = o  # DropVars are distinct objects; harmless
    return stream


def first_divergence(closed, flat_args):
    """Two digest-stream passes over identical inputs; the first
    equation whose digests differ names the diverging primitive.
    Returns ``None`` when the streams agree, else
    ``{"eqn_index", "primitive", "eqn"}``."""
    s1 = digest_stream(closed, flat_args)
    s2 = digest_stream(closed, flat_args)
    for i, (a, b) in enumerate(zip(s1, s2)):
        if a != b:
            eqn = closed.jaxpr.eqns[i]
            return {
                "eqn_index": i,
                "primitive": eqn.primitive.name,
                "eqn": str(eqn)[:200],
            }
    return None


def _verdict(outs, ms, *, bisect=None):
    """Shared report shape for one surface."""
    mismatches, nbytes, leaves = bitwise_compare(outs[0], outs[-1])
    report = {
        "deterministic": not mismatches,
        "leaves": leaves,
        "bytes_compared": nbytes,
        "replay_ms": [round(m, 2) for m in ms],
        "mismatches": [
            {"leaf": p, "reason": r} for p, r in mismatches[:16]
        ],
    }
    if mismatches and bisect is not None:
        report["first_divergence"] = bisect()
    return report


# ----------------------------------------------------------------------
# train surface
# ----------------------------------------------------------------------

def capture_train_inputs(trainer, batch, warmup=2):
    """Warm the compiled step, then capture the next dispatch's exact
    argument tuple as host copies (state, batches, weights, lr, rng,
    inject)."""
    import jax

    from unicore_tpu import metrics

    box = {}

    def _cap(args):
        if "args" not in box:
            box["args"] = jax.device_get(args)

    with metrics.aggregate("train"):
        for _ in range(warmup):
            trainer.train_step([batch])
        trainer.flush_stats()
        trainer._input_capture = _cap
        try:
            trainer.train_step([batch])
            trainer.flush_stats()
        finally:
            trainer._input_capture = None
    return box["args"]


def run_train(runs=2, cfg=None, trainer=None, batch=None):
    """Double-run the jitted train step on one captured dispatch.
    Builds the shrunk 2x64 bench trainer unless one is injected."""
    import numpy as np

    if trainer is None:
        import bench  # lazy: bench imports this repo, not vice versa
        from unicore_tpu.distributed import utils as dist_utils

        dist_utils.reset_mesh()
        cfg = dict(TRAIN_CFG, **(cfg or {}))
        trainer, d, mask_idx = bench._build_trainer(dict(cfg, fp16=False))
        rng = np.random.RandomState(0)
        batch = bench._make_batch(
            rng, d, mask_idx, cfg["batch"], cfg["seq"]
        )
    captured = capture_train_inputs(
        trainer, batch, warmup=(cfg or TRAIN_CFG).get("warmup", 2)
    )
    fn = trainer._jit_train_step
    outs, ms = double_run(fn, captured, runs=runs)

    def bisect():
        import jax

        closed = fn.trace(*captured).jaxpr
        return first_divergence(
            closed, jax.tree_util.tree_leaves(captured)
        )

    return _verdict(outs, ms, bisect=bisect)


# ----------------------------------------------------------------------
# serve surface
# ----------------------------------------------------------------------

def run_serve(runs=2, engine=None):
    """Double-run the unified ragged serve step on one captured
    dispatch of the --demo engine."""
    import jax

    from unicore_tpu.serve.scheduler import Request

    if engine is None:
        from unicore_tpu.analysis.scenarios import build_demo_serve_engine

        engine = build_demo_serve_engine()
    requests = [
        Request(prompt=[5 + i, 7, 11, 13 + i, 17], max_new_tokens=8,
                seed=i, request_id=f"det-{i}")
        for i in range(3)
    ]
    box = {}

    def _cap(key, args):
        if "args" not in box:
            box["key"] = key
            box["args"] = jax.device_get(args)

    engine._input_capture = _cap
    try:
        engine.generate(requests)
    finally:
        engine._input_capture = None
    w, sampling = box["key"]
    fn = engine._ragged_step_fn(w, sampling)
    outs, ms = double_run(fn, box["args"], runs=runs)

    def bisect():
        closed = fn.trace(*box["args"]).jaxpr
        return first_divergence(
            closed, jax.tree_util.tree_leaves(box["args"])
        )

    report = _verdict(outs, ms, bisect=bisect)
    report["step"] = {"width": int(w), "sampling": sampling}
    return report


# ----------------------------------------------------------------------
# CLI
# ----------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="unicore-determinism",
        description="double-run bit-exactness harness (Pass 5 dynamic)",
    )
    ap.add_argument("--train", action="store_true",
                    help="capture + double-run the shrunk 2x64 jitted "
                         "train step")
    ap.add_argument("--serve", action="store_true",
                    help="capture + double-run the --demo ServeEngine's "
                         "unified ragged step")
    ap.add_argument("--runs", type=int, default=2, metavar="N",
                    help="replays per surface (default 2; the first "
                         "may include a compile)")
    ap.add_argument("--cpu-devices", type=int, default=0, metavar="N",
                    help="force a virtual N-device CPU platform")
    ap.add_argument("--json", default=None, metavar="FILE",
                    help="write the report as JSON")
    args = ap.parse_args(argv)
    if not (args.train or args.serve):
        ap.error("nothing to do: pass --train and/or --serve")
    _provision(args.cpu_devices)

    report = {}
    if args.train:
        t0 = time.perf_counter()
        report["train"] = run_train(runs=args.runs)
        report["train"]["wall_s"] = round(time.perf_counter() - t0, 2)
    if args.serve:
        t0 = time.perf_counter()
        report["serve"] = run_serve(runs=args.runs)
        report["serve"]["wall_s"] = round(time.perf_counter() - t0, 2)

    ok = all(r["deterministic"] for r in report.values())
    report["deterministic"] = ok
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(report, fh, indent=2)
            fh.write("\n")
    for name in ("train", "serve"):
        if name in report:
            r = report[name]
            print(
                f"unicore-determinism: {name}: "
                f"{'bit-exact' if r['deterministic'] else 'DIVERGED'} "
                f"({r['leaves']} leaves, {r['bytes_compared']} bytes, "
                f"replay {r['replay_ms'][-1]:.1f} ms)"
            )
            if not r["deterministic"] and r.get("first_divergence"):
                fd = r["first_divergence"]
                print(
                    f"unicore-determinism: {name}: first diverging "
                    f"primitive: {fd['primitive']} (eqn "
                    f"{fd['eqn_index']})"
                )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
