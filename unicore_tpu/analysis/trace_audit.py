"""Pass 1: jaxpr / lowered-module audit of a jitted step.

Every check here is static — the program is TRACED (``jit.trace``) and
LOWERED (``.lower()``), never executed, so the audit runs on a CPU box
against the same jaxpr a TPU would compile.  The rules encode the bug
classes rounds 3-5 paid for at bench time (see docs/static_analysis.md):

- UL001 upcast-leak: bf16/f16 values promoted to fp32 arithmetic by
  dtype promotion (a mixed-dtype ``dot_general`` runs off the bf16 MXU
  lanes; an elementwise chain seeded by an implicit convert drags every
  consumer to fp32).
- UL002 giant-intermediate: single buffers over an absolute byte budget,
  and O(T^2) buffers (two sequence-length dims) over a smaller budget —
  the "flash path expected, materialized path traced" tripwire.
- UL003 donation-miss: no argument donated while the arguments carry
  real state — the doubled-HBM failure mode.
- UL004 host-callback: callback and debug-print primitives inside the
  step (each one is a device->host round trip per step).
- UL005 sharding-hole: big train-state leaves left fully replicated on a
  mesh whose fsdp/tensor axes are real (the r4 involuntary-full-remat
  precursor).
- UL006 fp64-leak: float64/complex128 values in the step (an x64 leak
  silently halves MXU/VPU throughput on TPU).
"""

from unicore_tpu.analysis.findings import Finding

# thresholds are deliberately module-level defaults the CLI can override
DEFAULT_BIG_BYTES = 256 << 20          # UL002 absolute buffer budget
DEFAULT_QUAD_BYTES = 32 << 20          # UL002 budget for [.., T, T] buffers
DEFAULT_UPCAST_MIN_ELEMS = 4096        # UL001 ignores scalar/stat noise
DEFAULT_SHARD_MIN_ELEMS = 4096         # UL005 ignores scalars/tiny biases
DEFAULT_DONATE_MIN_BYTES = 1 << 20     # UL003 ignores tiny closures

_LOW_PRECISION = {"bfloat16", "float16"}

# elementwise arithmetic primitives that should stay in the compute dtype
_ELEMENTWISE_ARITH = {
    "add", "sub", "mul", "div", "max", "min", "pow", "rem", "atan2",
    "select_n", "nextafter",
}

# every host-callback primitive the installed jax can trace
# (jax.debug.print is its own primitive, not a debug_callback)
_CALLBACK_PRIMS = {
    "pure_callback", "io_callback", "debug_callback", "debug_print",
}


def _iter_eqns(jaxpr):
    """All equations, recursing into sub-jaxprs (scan/while/cond/pjit/
    custom_vjp carry inner jaxprs in their params)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _iter_eqns(sub)


def _sub_jaxprs(eqn):
    for val in eqn.params.values():
        for item in (val if isinstance(val, (tuple, list)) else (val,)):
            inner = getattr(item, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                yield inner          # ClosedJaxpr
            elif hasattr(item, "eqns"):
                yield item           # raw Jaxpr


def _closed(jaxpr):
    """Accept ClosedJaxpr or Jaxpr."""
    return jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr


def _aval(var):
    return getattr(var, "aval", None)


def _nbytes(aval):
    try:
        return int(aval.size) * aval.dtype.itemsize
    except Exception:
        return 0


def _dtype_name(aval):
    # extended dtypes (PRNG keys) have no kind/name surface worth auditing
    return getattr(getattr(aval, "dtype", None), "name", "")


def _is_float(aval):
    name = _dtype_name(aval)
    return name.startswith("float") or name in _LOW_PRECISION


def _shape_str(aval):
    return (f"{_dtype_name(aval)}"
            f"[{','.join(str(d) for d in aval.shape)}]")


def audit_jaxpr(jaxpr, *, context="trace", seq_len=None,
                big_bytes=DEFAULT_BIG_BYTES, quad_bytes=DEFAULT_QUAD_BYTES,
                upcast_min_elems=DEFAULT_UPCAST_MIN_ELEMS, pedantic=False):
    """UL001 / UL002 / UL004 / UL006 over one (closed) jaxpr.

    ``pedantic`` additionally flags fp32 ELEMENTWISE chains seeded by a
    bf16->f32 convert.  Off by default: a jaxpr cannot distinguish a
    promotion-inserted convert from a deliberate one, and the repo's
    correct fp32 islands (LayerNorm stats, softmax, fp32 grad
    accumulation, optimizer math) all match the pattern.  The
    default-on half of UL001 — a mixed bf16/f32 ``dot_general`` — has
    no such legitimate instance: matmul operands must share the
    compute dtype to stay on the low-precision MXU lanes."""
    findings = []
    location = f"trace:{context}"
    seen = set()  # dedup identical messages (scan bodies repeat shapes)

    def emit(rule, name, severity, message):
        f = Finding(rule, name, severity, location, message)
        if (rule, message) not in seen:
            seen.add((rule, message))
            findings.append(f)

    # producer map for the convert-seeded elementwise chain half of UL001
    convert_from_low = set()  # ids of vars produced by bf16/f16 -> f32 casts

    for eqn in _iter_eqns(_closed(jaxpr)):
        prim = eqn.primitive.name
        in_avals = [a for a in (_aval(v) for v in eqn.invars) if a is not None]
        out_avals = [a for a in (_aval(v) for v in eqn.outvars)
                     if a is not None]
        float_in = [a for a in in_avals if _is_float(a)]

        # -- UL006 fp64 leak ------------------------------------------
        for a in out_avals:
            if _dtype_name(a) in ("float64", "complex128"):
                emit(
                    "UL006", "fp64-leak", "error",
                    f"{prim} produces {_shape_str(a)} — float64 in the "
                    f"compiled step (x64 leak; TPUs emulate fp64 at a "
                    f"fraction of bf16/fp32 throughput)",
                )

        # -- UL004 host callback --------------------------------------
        if prim in _CALLBACK_PRIMS or prim.endswith("_callback"):
            emit(
                "UL004", "host-callback", "error",
                f"'{prim}' primitive inside the compiled step — each "
                f"invocation is a device->host round trip per step "
                f"(debug prints / pure_callback left in a hot path?)",
            )

        # -- UL001 upcast leak ----------------------------------------
        if prim == "convert_element_type":
            src = in_avals[0] if in_avals else None
            dst = out_avals[0] if out_avals else None
            if (src is not None and dst is not None
                    and _dtype_name(src) in _LOW_PRECISION
                    and _dtype_name(dst) == "float32"):
                for v in eqn.outvars:
                    convert_from_low.add(id(v))
        elif prim == "dot_general":
            names = {_dtype_name(a) for a in float_in}
            if names & _LOW_PRECISION and "float32" in names:
                emit(
                    "UL001", "upcast-leak", "error",
                    f"dot_general with mixed {sorted(names)} operands "
                    f"(output {_shape_str(out_avals[0])}) — dtype "
                    f"promotion moved this matmul off the low-precision "
                    f"MXU lanes; cast both operands to the compute dtype",
                )
        elif prim in _ELEMENTWISE_ARITH and pedantic:
            out = out_avals[0] if out_avals else None
            if (out is not None and _dtype_name(out) == "float32"
                    and out.size >= upcast_min_elems
                    and any(id(v) in convert_from_low for v in eqn.invars)
                    and any(_dtype_name(a) == "float32" for a in in_avals)):
                emit(
                    "UL001", "upcast-leak", "warning",
                    f"'{prim}' runs in float32 on a value implicitly "
                    f"converted from bf16/f16 (output {_shape_str(out)}) "
                    f"— a weak-type/promotion leak upcasting an "
                    f"elementwise chain",
                )

        # -- UL002 giant intermediates --------------------------------
        for a in out_avals:
            nb = _nbytes(a)
            if nb >= big_bytes:
                emit(
                    "UL002", "giant-intermediate", "error",
                    f"{prim} materializes {_shape_str(a)} "
                    f"({nb / (1 << 20):.0f} MiB) in one buffer — above "
                    f"the {big_bytes / (1 << 20):.0f} MiB audit budget",
                )
            elif (seq_len is not None and seq_len > 1 and nb >= quad_bytes
                    and sum(1 for d in a.shape if d == seq_len) >= 2):
                emit(
                    "UL002", "giant-intermediate", "error",
                    f"{prim} materializes {_shape_str(a)} "
                    f"({nb / (1 << 20):.0f} MiB) with two T={seq_len} "
                    f"dims — an O(T^2) buffer where a flash/chunked "
                    f"path was expected",
                )
    return findings


def audit_donation(lowered, *, context="trace",
                   min_bytes=DEFAULT_DONATE_MIN_BYTES):
    """UL003: no donated argument on a step whose args carry real state."""
    import jax

    try:
        args_info = lowered.args_info
    except Exception:
        return []  # backend/stage without args_info: nothing provable
    leaves = jax.tree_util.tree_leaves(
        args_info, is_leaf=lambda x: hasattr(x, "donated")
    )
    total = 0
    donated = False
    for leaf in leaves:
        aval = getattr(leaf, "_aval", None) or getattr(leaf, "aval", None)
        if aval is not None:
            total += _nbytes(aval)
        donated = donated or bool(getattr(leaf, "donated", False))
    if donated or total < min_bytes:
        return []
    return [Finding(
        "UL003", "donation-miss", "error", f"trace:{context}",
        f"no argument is donated but the step takes "
        f"{total / (1 << 20):.1f} MiB of arguments — without "
        f"donate_argnums the old and new train state coexist in HBM "
        f"(doubled state footprint)",
    )]


def audit_sharding_coverage(mesh, shardings, shapes, *, context="trace",
                            min_elems=DEFAULT_SHARD_MIN_ELEMS):
    """UL005: state leaves the mesh's parallel axes should have split
    but didn't.

    ``shardings``: pytree of NamedSharding; ``shapes``: matching pytree
    of array-likes (or ShapeDtypeStructs).  Two sub-checks:

    - **fsdp** (ZeRO semantics: EVERY big leaf shards): a leaf >=
      ``min_elems`` with some fsdp-divisible dim but no dim on the fsdp
      axis is a hole — its optimizer state replicates, wasting
      world_size x HBM.
    - **tensor** (named-layer semantics): only leaves the Megatron name
      map (``distributed.utils.tensor_spec``) DESIGNATES should shard;
      a designated leaf whose installed sharding skips the tensor axis
      is the r4/r5 silent-disengage bug — error when the dim divides
      the axis (the spec should have applied), warning when it does not
      (the layer legally falls back to replication, but capacity is
      silently lost — the r5 vocab-not-divisible-by-tp lesson)."""
    import numpy as np

    import jax

    from unicore_tpu.distributed.utils import tensor_spec

    extent = dict(zip(mesh.axis_names, mesh.devices.shape))
    fsdp = extent.get("fsdp", 1)
    tp = extent.get("tensor", 1)
    if fsdp <= 1 and tp <= 1:
        return []

    findings = []
    location = f"trace:{context}"
    flat_sh, _ = jax.tree_util.tree_flatten_with_path(shardings)
    flat_shape = jax.tree_util.tree_leaves(shapes)
    for (path, sharding), arr in zip(flat_sh, flat_shape):
        shape = tuple(getattr(arr, "shape", ()))
        size = int(np.prod(shape, dtype=np.int64)) if shape else 1
        spec = tuple(getattr(sharding, "spec", ()) or ())
        used = set()
        for entry in spec:
            for ax in (entry if isinstance(entry, tuple) else (entry,)):
                if ax is not None:
                    used.add(ax)
        key = jax.tree_util.keystr(path)
        names = [
            str(getattr(k, "key", getattr(k, "name", k))) for k in path
        ]

        if fsdp > 1 and size >= min_elems and "fsdp" not in used:
            divisible = any(d % fsdp == 0 and d >= fsdp for d in shape)
            if divisible:
                findings.append(Finding(
                    "UL005", "sharding-hole", "error", location,
                    f"state leaf {key} {list(shape)} is not sharded "
                    f"over the fsdp axis (size {fsdp}) despite a "
                    f"divisible dim — under ZeRO every such leaf "
                    f"should split; replicating it costs fsdp x HBM",
                ))

        if tp > 1 and "tensor" not in used:
            intended = tensor_spec(names, shape)
            if intended is None:
                continue
            tdims = [d for d, ax in enumerate(intended)
                     if ax == "tensor"]
            if not tdims:
                continue
            if any(shape[d] % tp == 0 for d in tdims):
                findings.append(Finding(
                    "UL005", "sharding-hole", "error", location,
                    f"state leaf {key} {list(shape)} is designated "
                    f"tensor-parallel (dims {tdims}) and divisible by "
                    f"the tensor axis (size {tp}) but the installed "
                    f"sharding leaves it replicated — the TP spec "
                    f"silently failed to engage (the r4 TP bug)",
                ))
            else:
                findings.append(Finding(
                    "UL005", "sharding-hole", "warning", location,
                    f"state leaf {key} {list(shape)} is designated "
                    f"tensor-parallel but dims {tdims} do not divide "
                    f"the tensor axis (size {tp}) — the layer silently "
                    f"replicates instead of sharding (size the dim to "
                    f"a multiple of tp, as the 8-device dryrun sizes "
                    f"its vocab)",
                ))
    return findings


def audit_trainer(trainer, samples, *, context, seq_len=None,
                  thresholds=None):
    """Full Pass-1 audit of a Trainer's jitted train step: trace + lower
    (no execution), then run every jaxpr/lowered/sharding rule."""
    th = dict(thresholds or {})
    art = trainer.trace_train_step(samples)
    findings = list(audit_jaxpr(
        art["jaxpr"], context=context, seq_len=seq_len,
        big_bytes=th.get("big_bytes", DEFAULT_BIG_BYTES),
        quad_bytes=th.get("quad_bytes", DEFAULT_QUAD_BYTES),
        upcast_min_elems=th.get(
            "upcast_min_elems", DEFAULT_UPCAST_MIN_ELEMS
        ),
        pedantic=th.get("pedantic", False),
    ))
    findings += audit_donation(
        art["lowered"], context=context,
        min_bytes=th.get("donate_min_bytes", DEFAULT_DONATE_MIN_BYTES),
    )
    findings += audit_sharding_coverage(
        trainer.mesh, art["state_shardings"], art["state"], context=context,
        min_elems=th.get("shard_min_elems", DEFAULT_SHARD_MIN_ELEMS),
    )
    return findings, art
