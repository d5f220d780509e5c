"""unicore-lint: every rule must fire on a seeded violation and stay
silent on clean code (ISSUE 1 acceptance).

Trace rules (UL001-UL006) get tiny fixture programs audited through
``jax.make_jaxpr`` / ``jit.lower``; source rules (UL101-UL105) get
fixture files written to tmp_path.  The flagship-config integration
audit (the CI gate) runs at the end; the multi-variant mesh sweep is
the only trace-heavy case and stays seconds-fast at audit shapes.
"""

import json
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unicore_tpu.analysis.findings import (
    Finding,
    load_baseline,
    split_baselined,
    write_baseline,
)
from unicore_tpu.analysis.source_lint import lint_paths
from unicore_tpu.analysis.trace_audit import (
    audit_donation,
    audit_jaxpr,
    audit_sharding_coverage,
)


def rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------
# UL001 upcast-leak
# ---------------------------------------------------------------------

def test_upcast_leak_fires_on_mixed_dot():
    def leaky(x, w, bias):
        h = x + bias           # bf16 + f32 -> promotes h to f32
        return h @ w           # f32 @ bf16 -> mixed-dtype dot_general

    x = jnp.ones((256, 128), jnp.bfloat16)
    w = jnp.ones((128, 64), jnp.bfloat16)
    bias = jnp.ones((256, 128), jnp.float32)
    found = audit_jaxpr(jax.make_jaxpr(leaky)(x, w, bias))
    assert "UL001" in rules_of(found)


def test_upcast_leak_silent_on_clean_bf16_matmul():
    def clean(x, w):
        # bf16 operands with fp32 MXU accumulation: the correct idiom
        return jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    x = jnp.ones((256, 128), jnp.bfloat16)
    w = jnp.ones((128, 64), jnp.bfloat16)
    assert audit_jaxpr(jax.make_jaxpr(clean)(x, w)) == []


def test_upcast_leak_pedantic_flags_elementwise_chain():
    def leaky(x, bias):
        return x + bias        # convert(x)->f32 feeds f32 add

    x = jnp.ones((256, 128), jnp.bfloat16)
    bias = jnp.ones((256, 128), jnp.float32)
    jaxpr = jax.make_jaxpr(leaky)(x, bias)
    assert "UL001" in rules_of(audit_jaxpr(jaxpr, pedantic=True))
    # default mode: elementwise-only promotion is not reported (the
    # repo's deliberate fp32 islands match the same jaxpr pattern)
    assert audit_jaxpr(jaxpr) == []


# ---------------------------------------------------------------------
# UL002 giant-intermediate
# ---------------------------------------------------------------------

def test_giant_intermediate_fires_on_materialized_scores():
    T = 2048

    def attn_scores(q, k):  # [B,H,T,D] x 2 -> [B,H,T,T] fp32 scores
        return jnp.einsum("bhtd,bhsd->bhts", q, k)

    q = jnp.ones((2, 4, T, 64), jnp.float32)
    found = audit_jaxpr(jax.make_jaxpr(attn_scores)(q, q), seq_len=T)
    assert "UL002" in rules_of(found)
    assert any("O(T^2)" in f.message for f in found)


def test_giant_intermediate_fires_on_absolute_budget():
    def blow_up(x):
        return jnp.broadcast_to(x, (512, 1024, 1024))  # 2 GiB fp32

    x = jnp.ones((1024, 1024), jnp.float32)
    found = audit_jaxpr(jax.make_jaxpr(blow_up)(x))
    assert "UL002" in rules_of(found)


def test_giant_intermediate_silent_on_flash_sized_buffers():
    def small(q, k):
        return jnp.einsum("bhtd,bhsd->bhts", q, k)  # tiny T

    q = jnp.ones((2, 4, 64, 16), jnp.float32)
    assert audit_jaxpr(jax.make_jaxpr(small)(q, q), seq_len=64) == []


# ---------------------------------------------------------------------
# UL003 donation-miss
# ---------------------------------------------------------------------

def _state_step(state, x):
    return {"p": state["p"] + x.sum()}, (x * 2).sum()


def test_donation_miss_fires_without_donate_argnums():
    state = {"p": jnp.zeros((512, 1024))}  # 2 MiB > the 1 MiB threshold
    x = jnp.ones((8, 8))
    lowered = jax.jit(_state_step).lower(state, x)
    assert rules_of(audit_donation(lowered)) == {"UL003"}


def test_donation_silent_with_donate_argnums():
    state = {"p": jnp.zeros((512, 1024))}
    x = jnp.ones((8, 8))
    lowered = jax.jit(_state_step, donate_argnums=(0,)).lower(state, x)
    assert audit_donation(lowered) == []


def test_donation_silent_below_min_bytes():
    lowered = jax.jit(_state_step).lower(
        {"p": jnp.zeros((4, 4))}, jnp.ones((4, 4))
    )
    assert audit_donation(lowered) == []


# ---------------------------------------------------------------------
# UL004 host-callback
# ---------------------------------------------------------------------

def test_host_callback_fires_on_debug_print():
    def noisy(x):
        jax.debug.print("x={x}", x=x)
        return x + 1

    found = audit_jaxpr(jax.make_jaxpr(noisy)(1.0))
    assert "UL004" in rules_of(found)


def test_host_callback_fires_on_pure_callback():
    def hostcall(x):
        return jax.pure_callback(
            lambda v: np.asarray(v) * 2,
            jax.ShapeDtypeStruct(x.shape, x.dtype), x,
        )

    found = audit_jaxpr(jax.make_jaxpr(hostcall)(jnp.ones((4,))))
    assert "UL004" in rules_of(found)


def test_host_callback_silent_on_pure_step():
    found = audit_jaxpr(jax.make_jaxpr(lambda x: x * 2 + 1)(jnp.ones((4,))))
    assert found == []


# ---------------------------------------------------------------------
# UL005 sharding-hole (needs the virtual 8-device CPU mesh)
# ---------------------------------------------------------------------

def _mesh(fsdp=1, tensor=1):
    devs = np.asarray(jax.devices()[:8]).reshape(
        8 // (fsdp * tensor), fsdp, 1, tensor
    )
    return jax.sharding.Mesh(devs, ("data", "fsdp", "seq", "tensor"))


def _named(mesh, *spec):
    return jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(*spec)
    )


def test_sharding_hole_fires_on_replicated_leaf_under_fsdp():
    mesh = _mesh(fsdp=2)
    shapes = {"params": {"w": jax.ShapeDtypeStruct((256, 64), jnp.float32)}}
    shardings = {"params": {"w": _named(mesh)}}  # fully replicated
    found = audit_sharding_coverage(mesh, shardings, shapes)
    assert rules_of(found) == {"UL005"}
    assert "fsdp" in found[0].message


def test_sharding_hole_fires_on_disengaged_tensor_spec():
    mesh = _mesh(tensor=2)
    # embed_tokens/embedding is DESIGNATED tensor-parallel (vocab dim)
    shapes = {"params": {"embed_tokens": {
        "embedding": jax.ShapeDtypeStruct((64, 64), jnp.float32)}}}
    shardings = {"params": {"embed_tokens": {"embedding": _named(mesh)}}}
    found = audit_sharding_coverage(mesh, shardings, shapes)
    assert [f.severity for f in found] == ["error"]
    assert "failed to engage" in found[0].message


def test_sharding_hole_warns_on_indivisible_tensor_dim():
    mesh = _mesh(tensor=2)
    shapes = {"params": {"embed_tokens": {
        "embedding": jax.ShapeDtypeStruct((63, 64), jnp.float32)}}}
    shardings = {"params": {"embed_tokens": {"embedding": _named(mesh)}}}
    found = audit_sharding_coverage(mesh, shardings, shapes)
    assert [f.severity for f in found] == ["warning"]


def test_sharding_hole_silent_when_sharded_or_undesignated():
    mesh = _mesh(fsdp=2, tensor=2)
    shapes = {
        "params": {
            "embed_tokens": {
                "embedding": jax.ShapeDtypeStruct((64, 64), jnp.float32)},
            "w": jax.ShapeDtypeStruct((256, 64), jnp.float32),
            "tiny": jax.ShapeDtypeStruct((8,), jnp.float32),
        }
    }
    shardings = {
        "params": {
            "embed_tokens": {
                "embedding": _named(mesh, ("tensor", "fsdp"), None)},
            "w": _named(mesh, "fsdp", None),
            "tiny": _named(mesh),  # small leaves legally replicate
        }
    }
    assert audit_sharding_coverage(mesh, shardings, shapes) == []


# ---------------------------------------------------------------------
# UL006 fp64-leak
# ---------------------------------------------------------------------

def test_fp64_leak_fires_under_x64():
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(
            lambda x: x * np.float64(2.0)
        )(jnp.ones((4,), jnp.float64))
    assert "UL006" in rules_of(audit_jaxpr(jaxpr))


def test_fp64_leak_silent_on_fp32():
    jaxpr = jax.make_jaxpr(lambda x: x * 2.0)(jnp.ones((4,), jnp.float32))
    assert audit_jaxpr(jaxpr) == []


# ---------------------------------------------------------------------
# source lint fixtures (UL101-UL105)
# ---------------------------------------------------------------------

def _lint_snippet(tmp_path, name, code):
    f = tmp_path / name
    f.write_text(textwrap.dedent(code))
    return lint_paths([str(f)])


def test_jit_missing_donation_fires(tmp_path):
    found = _lint_snippet(tmp_path, "step.py", """
        import jax
        def train_step(state, batch):
            return state, batch
        step = jax.jit(train_step)
    """)
    assert "UL101" in rules_of(found)


def test_jit_missing_donation_fires_on_decorator_forms(tmp_path):
    found = _lint_snippet(tmp_path, "step.py", """
        import functools
        import jax
        @jax.jit
        def train_step(state, batch):
            return state, batch
        @functools.partial(jax.jit, static_argnums=(2,))
        def train_step_accum(state, batch, n):
            return state, batch
    """)
    assert sum(1 for f in found if f.rule == "UL101") == 2


def test_jit_missing_donation_silent_on_donating_decorator(tmp_path):
    found = _lint_snippet(tmp_path, "step.py", """
        import functools
        import jax
        @functools.partial(jax.jit, donate_argnums=(0,))
        def train_step(state, batch):
            return state, batch
        @jax.jit
        def eval_step(state, batch):  # not a train step: no rule
            return batch
    """)
    assert "UL101" not in rules_of(found)


def test_jit_missing_donation_silent_with_donation(tmp_path):
    found = _lint_snippet(tmp_path, "step.py", """
        import jax
        def train_step(state, batch):
            return state, batch
        step = jax.jit(train_step, donate_argnums=(0,))
        evaluate = jax.jit(lambda s, b: s)  # not a train step: no rule
    """)
    assert "UL101" not in rules_of(found)


def test_numpy_in_jit_fires(tmp_path):
    found = _lint_snippet(tmp_path, "step.py", """
        import jax
        import numpy as np
        @jax.jit
        def train_step(state, batch):
            return state, np.asarray(batch)
    """)
    assert "UL102" in rules_of(found)


def test_numpy_in_jit_silent_on_metadata_and_unjitted(tmp_path):
    found = _lint_snippet(tmp_path, "step.py", """
        import jax
        import numpy as np
        @jax.jit
        def train_step(state, batch):
            n = np.prod(batch.shape)  # metadata-only: allowed
            return state, batch / n
        def host_helper(x):
            return np.asarray(x)  # not jitted: allowed
    """)
    assert "UL102" not in rules_of(found)


def test_unseeded_dataset_rng_fires(tmp_path):
    found = _lint_snippet(tmp_path, "my_dataset.py", """
        import random
        import numpy as np
        def __getitem__(self, index):
            a = np.random.rand(4)
            b = random.randint(0, 3)
            g = np.random.RandomState()
            return a, b, g
    """)
    assert sum(1 for f in found if f.rule == "UL103") == 3


def test_unseeded_dataset_rng_silent_inside_numpy_seed(tmp_path):
    found = _lint_snippet(tmp_path, "my_dataset.py", """
        import numpy as np
        from unicore_tpu.data import data_utils
        def __getitem__(self, index):
            with data_utils.numpy_seed(self.seed, self.epoch, index):
                a = np.random.rand(4)
            gen = np.random.RandomState(42)
            return a, gen
    """)
    assert "UL103" not in rules_of(found)


def test_blocking_fetch_fires_and_suppression_works(tmp_path):
    found = _lint_snippet(tmp_path, "lib.py", """
        def run(x, y):
            x.block_until_ready()
            v = y.item()
            ok = y.item()  # unicore-lint: disable=UL104
            return v, ok
    """)
    assert sum(1 for f in found if f.rule == "UL104") == 2


def test_blocking_fetch_silent_in_stats_slow_path(tmp_path):
    d = tmp_path / "logging"
    d.mkdir()
    f = d / "meters.py"
    f.write_text("def fmt(v):\n    return v.item()\n")
    assert lint_paths([str(f)]) == []


def test_dropout_dead_rate_fires(tmp_path):
    found = _lint_snippet(tmp_path, "model.py", """
        from unicore_tpu.ops.dropout import dropout
        def f(x, rng):
            return dropout(x, 0.001, rng)
    """)
    assert "UL105" in rules_of(found)


def test_dropout_dead_rate_matches_op_at_boundary(tmp_path):
    # r = 1/512 rounds to q = 256 (identity) in ops/dropout.py — the
    # lint must agree with the op's quantization, not a re-derived band
    found = _lint_snippet(tmp_path, "model.py", """
        from unicore_tpu.ops.dropout import dropout
        def f(x, rng):
            return dropout(x, 0.001953125, rng)
    """)
    assert "UL105" in rules_of(found)


def test_dropout_dead_rate_silent_on_representable_rates(tmp_path):
    found = _lint_snippet(tmp_path, "model.py", """
        from unicore_tpu.ops.dropout import dropout
        def f(x, rng):
            return dropout(x, 0.1, rng), dropout(x, 0.0, rng)
    """)
    assert "UL105" not in rules_of(found)


# ---------------------------------------------------------------------
# baseline / suppression mechanics
# ---------------------------------------------------------------------

def test_baseline_roundtrip_suppresses_known_findings(tmp_path):
    f1 = Finding("UL104", "blocking-fetch", "error", "a.py:10", "msg one")
    f2 = Finding("UL104", "blocking-fetch", "error", "b.py:20", "msg two")
    path = tmp_path / "baseline.json"
    write_baseline(str(path), [f1])
    fps = load_baseline(str(path))
    # line numbers must not churn the baseline
    moved = Finding("UL104", "blocking-fetch", "error", "a.py:99", "msg one")
    new, suppressed = split_baselined([moved, f2], fps)
    assert [f.location for f in suppressed] == ["a.py:99"]
    assert [f.location for f in new] == ["b.py:20"]


def test_baseline_missing_file_is_empty():
    assert load_baseline("/nonexistent/baseline.json") == set()


# ---------------------------------------------------------------------
# integration: the repo itself must be clean, and the flagship config
# must trace-audit clean over the dryrun meshes (the CI gate)
# ---------------------------------------------------------------------

def _repo_root():
    import os

    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_repo_source_lint_clean_within_baseline():
    import os

    from unicore_tpu.analysis.cli import DEFAULT_LINT_ROOTS

    # the default file set must cover the tool entry points, not just
    # the library (ISSUE 4 satellite: examples/ + serve/cli.py + tools/)
    assert set(DEFAULT_LINT_ROOTS) >= {
        "unicore_tpu", "unicore_tpu_cli", "examples", "tools", "bench.py"
    }
    root = _repo_root()
    roots = [os.path.join(root, d) for d in DEFAULT_LINT_ROOTS]
    findings = lint_paths(roots, rel_to=root)
    fps = load_baseline(os.path.join(root, "tools", "lint_baseline.json"))
    new, _ = split_baselined(findings, fps)
    assert new == [], "\n".join(f.render() for f in new)


def test_flagship_bert_trace_audit_clean():
    import os

    from unicore_tpu.analysis.scenarios import audit_bert_config

    findings, reports = audit_bert_config(
        os.path.join(_repo_root(), "examples", "bert"), n_devices=8
    )
    assert findings == [], "\n".join(f.render() for f in findings)
    ran = [r["variant"] for r in reports if "mesh" in r]
    assert ran == ["dp", "fsdp2", "tp2", "seq2", "tp2_fsdp2"], reports


def test_fused_head_audit_silent_fused_fires_materialized():
    """ISSUE 10 acceptance: with UL002's budget pinned to the head's
    full-logits byte size (rows * vocab * 4), the DEFAULT (fused
    chunked) train step must be silent on every pass-3 mesh variant —
    no intermediate that large exists in forward or backward — while
    the materialized head (--fused-lm-head off) must fire on each, the
    tripwire proving the budget bites at audit shapes."""
    import os

    from unicore_tpu.analysis.scenarios import (
        MESH_VARIANTS,
        PASS3_VARIANTS,
        ZERO1_VARIANTS,
        audit_fused_head_memory,
    )

    variants = [v for v in MESH_VARIANTS + ZERO1_VARIANTS
                if v[0] in PASS3_VARIANTS]
    results = audit_fused_head_memory(
        os.path.join(_repo_root(), "examples", "bert"),
        variants=variants, n_devices=8,
    )
    assert sorted(results) == sorted(PASS3_VARIANTS), results
    for name, per in results.items():
        assert per["fused"] == [], (
            name, "\n".join(f.render() for f in per["fused"]))
        assert any(f.rule == "UL002" for f in per["naive"]), (
            name, "materialized head did not trip the logits budget")


def test_trainer_trace_audit_catches_seeded_sharding_hole():
    """End-to-end negative control: force a hole through the REAL
    trainer artifacts and assert the audit sees it (guards against the
    audit silently auditing the wrong tree)."""
    import os

    from unicore_tpu.analysis.scenarios import (
        build_bert_scenario,
        restore_globals,
        snapshot_globals,
    )
    from unicore_tpu.analysis.trace_audit import audit_sharding_coverage

    snap = snapshot_globals()
    try:
        trainer, samples, _ = build_bert_scenario(
            os.path.join(_repo_root(), "examples", "bert"),
            {"fsdp_size": 2}, jax.devices()[:8],
        )
        art = trainer.trace_train_step(samples)
        # sabotage: claim every leaf is replicated
        rep = jax.sharding.NamedSharding(
            trainer.mesh, jax.sharding.PartitionSpec()
        )
        broken = jax.tree_util.tree_map(lambda _: rep,
                                        art["state_shardings"])
        found = audit_sharding_coverage(trainer.mesh, broken, art["state"])
        assert "UL005" in rules_of(found)
    finally:
        restore_globals(snap)


def test_cli_module_runs_lint_only():
    proc = subprocess.run(
        [sys.executable, "-m", "unicore_tpu.analysis", "--no-trace", "-q"],
        cwd=_repo_root(), capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout


def test_cli_json_report_and_exit_code(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "def f(x):\n    return x.block_until_ready()\n"
    )
    out = tmp_path / "report.json"
    proc = subprocess.run(
        [sys.executable, "-m", "unicore_tpu.analysis", "--no-trace", "-q",
         "--no-baseline", "--lint-root", str(bad), "--json", str(out)],
        cwd=_repo_root(), capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 1
    report = json.loads(out.read_text())
    assert report["counts"]["new"] == 1
    assert report["new_findings"][0]["rule"] == "UL104"


# ---------------------------------------------------------------------
# UL106 where-nan-grad
# ---------------------------------------------------------------------

def test_where_nan_grad_fires_on_risky_branches(tmp_path):
    found = _lint_snippet(tmp_path, "model.py", """
        import jax.numpy as jnp
        def f(x, n, d):
            a = jnp.where(x > 0, jnp.sqrt(x), 0.0)
            b = jnp.where(d != 0, n / d, 0.0)
            c = jnp.where(x > 0, x ** 0.5, 0.0)
            return a, b, c
    """)
    assert sum(1 for f in found if f.rule == "UL106") == 3


def test_where_nan_grad_silent_on_clamped_and_plain(tmp_path):
    found = _lint_snippet(tmp_path, "model.py", """
        import jax.numpy as jnp
        def f(x, n, d, keep, keep_prob, mask):
            a = jnp.where(x > 0, jnp.sqrt(jnp.maximum(x, 1e-6)), 0.0)
            b = jnp.where(mask, x, -1e9)              # plain branches
            c = jnp.where(keep, n / keep_prob, 0.0)   # denom not guarded
            d2 = jnp.where(x > 0, x * 2.0, x / 4.0)   # constant denom
            return a, b, c, d2
    """)
    assert "UL106" not in rules_of(found)


def test_where_nan_grad_ignores_module_alias_overlap(tmp_path):
    # 'jnp' appearing in both the condition and a denominator is NOT a
    # shared value, and the documented clamp fix silences the div half
    found = _lint_snippet(tmp_path, "model.py", """
        import jax.numpy as jnp
        def f(self, x, m, w, n, d, eps):
            a = jnp.where(jnp.all(m), x / jnp.sum(w), 0.0)
            b = jnp.where(d > eps, n / jnp.maximum(d, eps), 0.0)
            # attribute ROOTS are not shared values: self.eps vs
            # self.temperature must not collide on 'self'
            c = jnp.where(m > self.eps, x / self.temperature, 0.0)
            # the sanctioned clamp fix silences the pow form too
            e = jnp.where(x > 0, jnp.maximum(x, eps) ** 0.5, 0.0)
            return a, b, c, e
    """)
    assert "UL106" not in rules_of(found)


def test_where_nan_grad_tracks_jnp_import_forms(tmp_path):
    found = _lint_snippet(tmp_path, "model.py", """
        from jax import numpy as jn
        def f(x):
            return jn.where(x > 0, jn.log(x), 0.0)
    """)
    assert "UL106" in rules_of(found)


# ---------------------------------------------------------------------
# UL107 swallowed-io-error
# ---------------------------------------------------------------------

def test_swallowed_io_error_fires(tmp_path):
    found = _lint_snippet(tmp_path, "ckpt.py", """
        import os, pickle
        def save(obj, fn):
            try:
                with open(fn, "wb") as fh:
                    pickle.dump(obj, fh)
            except Exception:
                pass
        def sweep(paths):
            for p in paths:
                try:
                    os.remove(p)
                except:
                    continue
    """)
    assert sum(1 for f in found if f.rule == "UL107") == 2


def test_swallowed_io_error_silent_on_sanctioned_forms(tmp_path):
    found = _lint_snippet(tmp_path, "ckpt.py", """
        import os, pickle, logging
        logger = logging.getLogger(__name__)
        def narrow(fn):
            try:
                os.remove(fn)
            except FileNotFoundError:
                pass  # deliberate: prune races are benign
        def logged(obj, fn):
            try:
                with open(fn, "wb") as fh:
                    pickle.dump(obj, fh)
            except Exception:
                logger.error("save failed", exc_info=True)
                raise
        def no_io(x):
            try:
                return float(x)
            except Exception:
                pass
    """)
    assert "UL107" not in rules_of(found)


def test_swallowed_io_error_inline_suppression(tmp_path):
    found = _lint_snippet(tmp_path, "ckpt.py", """
        import os
        def f(p):
            try:
                os.remove(p)
            except Exception:  # unicore-lint: disable=UL107
                pass
    """)
    assert "UL107" not in rules_of(found)


# ---------------------------------------------------------------------
# UL108 sync-in-step-loop
# ---------------------------------------------------------------------

def test_sync_in_step_loop_fires(tmp_path):
    found = _lint_snippet(tmp_path, "loop.py", """
        import jax
        def train(trainer, batches):
            for b in batches:
                out = trainer.train_step(b)
                stats = jax.device_get(out)           # per-step sync
                trainer.save_checkpoint("last.pt", {})  # sync save
            return stats
        def drive(trainer, stream):
            staged = next(stream, None)
            while staged is not None:
                out = trainer.train_step(staged)
                out.block_until_ready()
                staged = next(stream, None)
    """)
    assert sum(1 for f in found if f.rule == "UL108") == 3


def test_sync_in_step_loop_silent_outside_and_in_plain_loops(tmp_path):
    found = _lint_snippet(tmp_path, "loop.py", """
        import jax
        def train(trainer, batches):
            # the sanctioned shape: dispatch inside, fetch at the end
            for b in batches:
                out = trainer.train_step(b)
            trainer.flush_stats()
            return jax.device_get(out)
        def not_a_step_loop(xs):
            # device_get in a loop that never dispatches train steps
            return [jax.device_get(x) for x in xs]
        def eval_loop(model, batches):
            for b in batches:
                out = model.valid_step(b)
                host = jax.device_get(out)
            return host
        def epochs(trainer, loader):
            # the OUTER loop is not a step loop: train_step only runs
            # in the nested loop, so the per-epoch fetch is the
            # sanctioned real-boundary sync, not a per-step stall
            for epoch in range(3):
                for b in loader:
                    out = trainer.train_step(b)
                stats = jax.device_get(out)
                trainer.save_checkpoint(f"ck{epoch}.pt", stats)
    """)
    assert "UL108" not in rules_of(found)


def test_sync_in_step_loop_inline_suppression_and_closure(tmp_path):
    found = _lint_snippet(tmp_path, "loop.py", """
        import jax
        def train(trainer, batches):
            for b in batches:
                out = trainer.train_step(b)
                x = jax.device_get(out)  # unicore-lint: disable=UL108
        def builder(trainer):
            # a closure DEFINED in a step loop does not run per
            # iteration — its body must not be flagged
            hooks = []
            for phase in ("a", "b"):
                trainer.train_step(None)
                def done(out):
                    return jax.device_get(out)
                hooks.append(done)
            return hooks
    """)
    assert "UL108" not in rules_of(found)


# ---------------------------------------------------------------------
# UL112 sync-on-current-step
# ---------------------------------------------------------------------

def test_sync_on_current_step_fires(tmp_path):
    found = _lint_snippet(tmp_path, "pipeloop.py", """
        import jax
        def train(trainer, batches):
            for b in batches:
                out = trainer.train_step(b)
                loss = out["loss"].item()        # sync on THIS step
            return loss
        def drive(trainer, stream):
            staged = next(stream, None)
            while staged is not None:
                state, stats = trainer.train_step(staged)
                host = jax.device_get(stats)     # current-step fetch
                stats["gnorm"].block_until_ready()
                staged = next(stream, None)
    """)
    assert sum(1 for f in found if f.rule == "UL112") == 3


def test_sync_on_current_step_silent_on_drain_path(tmp_path):
    found = _lint_snippet(tmp_path, "pipeloop.py", """
        import jax
        def train(trainer, batches):
            # the sanctioned lag-K shape: train_step's return IS the
            # lagged host-side stats; flush_stats gives exact counts —
            # syncing on values from the DRAIN path must not fire
            for b in batches:
                out = trainer.train_step(b)
                exact = trainer.flush_stats()
                if exact is not None:
                    exact[0]["loss"].item()
            return jax.device_get(out)           # after the loop: fine
        def rebound_from_drain(trainer, batches):
            # rebinding the SAME name from the drain path launders it:
            # the nearest binding above the sync is flush_stats, not
            # the step call
            for b in batches:
                out = trainer.train_step(b)
                out = trainer.flush_stats()
                if out is not None:
                    out[0]["loss"].item()
            return out
        def manual_lag_one(trainer, batches):
            # reading the PREVIOUS iteration's output before this
            # iteration's dispatch is the manual lag-1 idiom — the
            # value is already on host, nothing stalls
            prev = None
            for b in batches:
                if prev is not None:
                    prev["loss"].item()
                prev = trainer.train_step(b)
            return prev
        def not_a_step_loop(model, xs):
            for x in xs:
                y = model.valid_step(x)
                y.block_until_ready()            # no train_step here
    """)
    assert "UL112" not in rules_of(found)


def test_sync_on_current_step_suppression_and_closure(tmp_path):
    found = _lint_snippet(tmp_path, "pipeloop.py", """
        import jax
        def train(trainer, batches):
            for b in batches:
                out = trainer.train_step(b)
                x = jax.device_get(out)  # unicore-lint: disable=UL112,UL108
        def builder(trainer):
            # a closure DEFINED in the loop does not run per iteration
            hooks = []
            for b in ("a", "b"):
                out = trainer.train_step(b)
                def done():
                    return jax.device_get(out)
                hooks.append(done)
            return hooks
    """)
    assert "UL112" not in rules_of(found)


# ---------------------------------------------------------------------
# UL109 unbounded-queue-growth
# ---------------------------------------------------------------------

def test_unbounded_queue_growth_fires(tmp_path):
    found = _lint_snippet(tmp_path, "server.py", """
        def serve_forever(sched, source, backlog):
            while True:
                req = source.get()
                sched.waiting.append(req)        # no bound, no shed
                backlog.insert(0, req)           # second offender
                sched.admit()
        def drive(sched, reqs):
            for r in reqs:
                retry_queue.appendleft(r)        # third offender
                sched.prepare_decode()
        def poll_then_drain(sched, source, k):
            # the scheduling marker lives in a NESTED loop: the outer
            # while still grows the queue once per serve cycle, so it
            # must classify as the serve loop (regression: the UL108
            # nested-loop exclusion must not apply here)
            while True:
                queue.append(source.get())       # fourth offender
                for _ in range(k):
                    sched.admit()
    """)
    assert sum(1 for f in found if f.rule == "UL109") == 4


def test_unbounded_queue_growth_silent_on_bounded_and_shed(tmp_path):
    found = _lint_snippet(tmp_path, "server.py", """
        def bounded(sched, source, max_waiting):
            while True:
                req = source.get()
                # bound check on the same collection sanctions growth
                if len(sched.waiting) < max_waiting:
                    sched.waiting.append(req)
                sched.admit()
        def drains(sched, source):
            while True:
                sched.waiting.append(source.get())
                sched.waiting.popleft()          # drain path
                sched.admit()
        def sheds(sched, source):
            while True:
                req = source.get()
                sched.waiting.append(req)
                shed_overflow(sched)             # a shed path in sight
                sched.admit()
        def not_a_serve_loop(out, items):
            for x in items:                      # no scheduling markers
                out.append(x)
        def closure_in_loop(sched, reqs):
            hooks = []
            while True:
                sched.admit()
                if len(hooks) > 4:
                    break
                def late(q, r):
                    q.append(r)                  # closure: fresh scope
                hooks.append(late)
    """)
    assert "UL109" not in rules_of(found)


def test_unbounded_queue_growth_inline_suppression(tmp_path):
    found = _lint_snippet(tmp_path, "server.py", """
        def serve_forever(sched, source):
            while True:
                req = source.get()
                sched.waiting.append(req)  # unicore-lint: disable=UL109
                sched.admit()
    """)
    assert "UL109" not in rules_of(found)


# ---------------------------------------------------------------------
# UL111 blocking-in-router-loop
# ---------------------------------------------------------------------

def test_blocking_in_router_loop_fires(tmp_path):
    found = _lint_snippet(tmp_path, "router.py", """
        import time
        def dispatch_loop(replicas, worker):
            while True:
                for eng in replicas:
                    eng.serve_step()
                time.sleep(0.01)                 # pacing stall
                worker.join()                    # parks behind one thread
        def drive(router, home, reqs):
            for req in reqs:
                router.route(req)
                home.generate([req])             # batch-blocking API
        def nested(router, engines):
            # fan-out in a NESTED for: the outer while still blocks
            # once per dispatch cycle, so it classifies (UL109-style
            # subtree semantics, not UL108's nested-loop exclusion)
            while True:
                for eng in engines:
                    eng.serve_step()
                time.sleep(1)                    # fourth offender
    """)
    assert sum(1 for f in found if f.rule == "UL111") == 4


def test_blocking_in_router_loop_silent_cases(tmp_path):
    found = _lint_snippet(tmp_path, "router.py", """
        import time
        def not_a_router_loop(items, worker):
            for x in items:                      # no dispatch markers
                time.sleep(0.01)
                worker.join()
        def str_join_is_fine(router, rows):
            while True:
                router.dispatch(rows)
                label = ",".join(r.id for r in rows)   # one arg: str.join
            return label
        def paced_outside(router, reqs):
            for req in reqs:
                router.route(req)
            time.sleep(0.5)                      # after the loop
        def closure_in_loop(router, hooks):
            while True:
                router.serve_step()
                def later():
                    time.sleep(1)                # fresh scope
                hooks.pop()
                hooks.append(later)
                if not hooks:
                    break
    """)
    assert "UL111" not in rules_of(found)


def test_blocking_in_router_loop_inline_suppression(tmp_path):
    found = _lint_snippet(tmp_path, "router.py", """
        import time
        def dispatch_loop(replicas):
            while True:
                for eng in replicas:
                    eng.serve_step()
                time.sleep(0.01)  # unicore-lint: disable=UL111
    """)
    assert "UL111" not in rules_of(found)


# ---------------------------------------------------------------------
# UL113 unguarded-replica-step
# ---------------------------------------------------------------------

def test_unguarded_replica_step_fires(tmp_path):
    found = _lint_snippet(tmp_path, "router.py", """
        def fleet_loop(engines):
            while True:
                for rid in sorted(engines):
                    engines[rid].serve_step()      # subscripted replica
        def fan_out(replicas):
            for eng in replicas:                   # replica-ish iterable
                eng.serve_step()
        def two_receivers(a, b, work):
            while work:
                a.serve_step()                     # two distinct replicas
                b.serve_step()
                work.pop()
    """)
    assert sum(1 for f in found if f.rule == "UL113") == 4


def test_unguarded_replica_step_silent_cases(tmp_path):
    found = _lint_snippet(tmp_path, "router.py", """
        def guarded_fleet_loop(engines, health, evict):
            # the sanctioned shape: typed fault handling around the step
            while True:
                for rid in sorted(engines):
                    try:
                        engines[rid].serve_step()
                    except Exception as exc:
                        health.record_exception(rid, exc)
                        evict(rid)
        def health_recorded(replicas, health):
            # health recording in the loop also sanctions a bare step
            for rid, eng in replicas.items():
                eng.serve_step()
                health.observe(rid, eng.load_snapshot(), eng.has_work())
        def self_driver(self):
            # an engine driving ITSELF is its own run loop, not a fleet
            while self.serve_step():
                pass
        def solo_harness(eng, n):
            # a bench/test harness driving ONE local engine: no fan-out
            for _ in range(n):
                eng.serve_step()
        def no_loop(eng2):
            eng2.serve_step()                      # not in a loop at all
    """)
    assert "UL113" not in rules_of(found)


def test_unguarded_replica_step_inline_suppression(tmp_path):
    found = _lint_snippet(tmp_path, "router.py", """
        def fleet_loop(engines):
            for rid in sorted(engines):
                engines[rid].serve_step()  # unicore-lint: disable=UL113
    """)
    assert "UL113" not in rules_of(found)


def test_unguarded_replica_step_fleet_package_clean():
    # the shipped fleet tier must BE the sanctioned shape: every
    # replica step routed through the guarded/health-recording helper
    import os

    import unicore_tpu.fleet as fleet_pkg

    root = os.path.dirname(fleet_pkg.__file__)
    found = lint_paths([root])
    assert "UL113" not in rules_of(found), [
        (f.location, f.message) for f in found if f.rule == "UL113"]


# ---------------------------------------------------------------------
# UL110 unguarded-dataset-io
# ---------------------------------------------------------------------

def test_unguarded_dataset_io_fires(tmp_path):
    # filename marks it a dataset file; raw IO in __getitem__ with no
    # typed re-raise = 3 findings (open+loads, lmdb get), and a broad
    # swallow in __iter__ = 1 more
    found = _lint_snippet(tmp_path, "raw_dataset.py", """
        import pickle
        class Raw:
            def __getitem__(self, idx):
                with open(self.paths[idx], "rb") as f:
                    return pickle.loads(f.read())
        class Db:
            def __getitem__(self, idx):
                return self._env.begin().get(self._keys[idx])
        class It:
            def __iter__(self):
                for p in self.paths:
                    try:
                        yield pickle.load(open(p, "rb"))
                    except Exception:
                        continue
    """)
    ul110 = [f for f in found if f.rule == "UL110"]
    # Raw: open + pickle.loads; Db: lmdb get; It: the swallow (the IO
    # inside the try is separately unguarded too — no re-raise)
    assert len(ul110) >= 4, found


def test_unguarded_dataset_io_silent_on_typed_reraise(tmp_path):
    found = _lint_snippet(tmp_path, "rec_dataset.py", """
        import pickle
        from unicore_tpu.data.resilient import DataIntegrityError
        class Store:
            def __getitem__(self, idx):
                try:
                    return pickle.loads(self._bytes(idx))
                except pickle.UnpicklingError as e:
                    raise DataIntegrityError(f"record {idx} torn") from e
            def helper_outside_fetch(self, p):
                return pickle.load(open(p, "rb"))  # not a fetch body
        class NoIo:
            def __getitem__(self, idx):
                return self.items[idx]
    """)
    assert "UL110" not in rules_of(found)


def test_unguarded_dataset_io_ignores_non_dataset_files(tmp_path):
    found = _lint_snippet(tmp_path, "container.py", """
        import pickle
        class Box:
            def __getitem__(self, idx):
                return pickle.loads(self.blobs[idx])
    """)
    assert "UL110" not in rules_of(found)


def test_unguarded_dataset_io_inline_suppression(tmp_path):
    found = _lint_snippet(tmp_path, "raw_dataset.py", """
        import pickle
        class Raw:
            def __getitem__(self, idx):
                return pickle.loads(self.blobs[idx])  # unicore-lint: disable=UL110
    """)
    assert "UL110" not in rules_of(found)


# ---------------------------------------------------------------------
# Pass 3: HLO parsing primitives (pure text, no compile)
# ---------------------------------------------------------------------

def test_parse_replica_groups_iota_and_explicit():
    from unicore_tpu.analysis.hlo_audit import parse_replica_groups

    assert parse_replica_groups("replica_groups=[4,2]<=[8],") == tuple(
        frozenset(p) for p in [(0, 1), (2, 3), (4, 5), (6, 7)]
    )
    # reshape+transpose iota: arange(8).reshape(4,2).T -> strided groups
    assert parse_replica_groups(
        "replica_groups=[2,4]<=[4,2]T(1,0),"
    ) == (frozenset({0, 2, 4, 6}), frozenset({1, 3, 5, 7}))
    assert parse_replica_groups(
        "replica_groups={{0,2,4,6},{1,3,5,7}}, use_global"
    ) == (frozenset({0, 2, 4, 6}), frozenset({1, 3, 5, 7}))
    assert parse_replica_groups("replica_groups={}", 4) == (
        frozenset({0, 1, 2, 3}),
    )
    assert parse_replica_groups("no groups here") is None


_HLO_SNIPPET = """
  %all-gather = f32[64,64]{1,0} all-gather(f32[32,64]{1,0} %p), \
channel_id=1, replica_groups=[4,2]<=[8], dimensions={0}, \
metadata={op_name="jit(step)/fwd/dot_general"}
  %all-reduce = f32[64,64]{1,0} all-reduce(f32[64,64]{1,0} %d), \
channel_id=2, replica_groups=[1,8]<=[8], to_apply=%add
  %ar-done = f32[8]{0} all-reduce-done(f32[8]{0} %x)
  %ags = (f32[32,64]{1,0}, f32[64,64]{1,0}) all-gather-start(\
f32[32,64]{1,0} %p), replica_groups=[4,2]<=[8], dimensions={0}
  %cp = u32[128]{0} collective-permute(u32[128]{0} %y), \
source_target_pairs={{0,1}}
"""


def test_extract_collectives_and_stats():
    from unicore_tpu.analysis.hlo_audit import (
        collective_stats,
        extract_collectives,
    )

    colls = extract_collectives(_HLO_SNIPPET, 8)
    assert [c.kind for c in colls] == [
        "all-gather", "all-reduce", "all-gather", "collective-permute"
    ]
    ag = colls[0]
    assert ag.bytes == 64 * 64 * 4 and ag.is_float
    assert ag.groups == tuple(
        frozenset(p) for p in [(0, 1), (2, 3), (4, 5), (6, 7)]
    )
    assert ag.op_name == "jit(step)/fwd/dot_general"
    # async -start: the result tuple aliases the operand next to the
    # output — count the transfer once (largest component), not summed
    assert colls[2].bytes == 64 * 64 * 4
    stats = collective_stats(colls)
    assert stats["collective_bytes"]["all-gather"] == 2 * 64 * 64 * 4
    assert stats["collective_count"]["collective-permute"] == 1
    assert not colls[3].is_float  # u32 permute


# ---------------------------------------------------------------------
# Pass 3: UL201 unit fixtures (synthetic collectives over a real mesh)
# ---------------------------------------------------------------------

def _coll(kind, nbytes, groups, *, is_float=True, shape="f32[x]"):
    from unicore_tpu.analysis.hlo_audit import Collective

    return Collective(kind=kind, shape=shape, bytes=nbytes,
                      is_float=is_float,
                      groups=tuple(frozenset(g) for g in groups),
                      op_name="test")


def test_ul201_unit_fires_and_stays_silent():
    from unicore_tpu.analysis.hlo_audit import audit_fsdp_collectives

    mesh = _mesh(fsdp=2)  # data=4, fsdp=2: fsdp pairs {0,1},{2,3},...
    params = {"w": jnp.zeros((64, 64), jnp.float32)}
    fsdp_pairs = [(0, 1), (2, 3), (4, 5), (6, 7)]
    healthy = [
        _coll("all-gather", 16384, fsdp_pairs),
        _coll("all-reduce", 16384, [(0, 2, 4, 6), (1, 3, 5, 7)]),
    ]
    assert audit_fsdp_collectives(mesh, healthy, params,
                                  context="t") == []
    # disengaged: only full-mesh all-reduces remain
    dead = [_coll("all-reduce", 16384, [range(8)])]
    found = audit_fsdp_collectives(mesh, dead, params, context="t")
    assert rules_of(found) == {"UL201"}
    assert "disengaged" in found[0].message
    # full-remat: weight-sized all-gather spanning the data axis
    remat = healthy + [_coll("all-gather", 20000, [range(8)])]
    found = audit_fsdp_collectives(mesh, remat, params, context="t")
    assert rules_of(found) == {"UL201"}
    assert "remat" in found[0].message
    # same gather below weight scale: budget territory, not UL201
    small = healthy + [_coll("all-gather", 1024, [range(8)])]
    assert audit_fsdp_collectives(mesh, small, params, context="t") == []
    # dp mesh: rule does not apply
    assert audit_fsdp_collectives(_mesh(), dead, params,
                                  context="t") == []


# ---------------------------------------------------------------------
# Pass 3: UL202/UL203 budget round-trip (unit)
# ---------------------------------------------------------------------

def test_budget_roundtrip_and_regressions(tmp_path):
    from unicore_tpu.analysis import hlo_audit

    path = str(tmp_path / "comms.json")
    fp = "test|fingerprint"
    stats = {"collective_bytes": {"all-gather": 1000, "all-reduce": 500},
             "peak_bytes": 10000}
    hlo_audit.update_budget_entries(path, fp, {"s1": stats})
    budgets = hlo_audit.load_budgets(path)
    entry = hlo_audit.budget_entry(budgets, fp, "s1")
    assert hlo_audit.audit_comms_budget("s1", stats, entry) == []
    assert hlo_audit.audit_memory_budget("s1", 10000, entry) == []
    # within tolerance: 4% over passes, >5% fails
    ok = {"collective_bytes": {"all-gather": 1040, "all-reduce": 500}}
    assert hlo_audit.audit_comms_budget("s1", ok, entry) == []
    bad = {"collective_bytes": {"all-gather": 1100, "all-reduce": 500}}
    found = hlo_audit.audit_comms_budget("s1", bad, entry)
    assert rules_of(found) == {"UL202"}
    # a collective kind the budget never saw
    new_kind = {"collective_bytes": {"all-gather": 1000,
                                     "all-to-all": 64}}
    found = hlo_audit.audit_comms_budget("s1", new_kind, entry)
    assert any("all-to-all" in f.message for f in found)
    # a zero-byte committed kind must report, not ZeroDivisionError
    zero_entry = {"collective_bytes": {"all-gather": 0},
                  "peak_bytes": 10000}
    found = hlo_audit.audit_comms_budget(
        "s1", {"collective_bytes": {"all-gather": 64}}, zero_entry
    )
    assert rules_of(found) == {"UL202"}
    # full-surface updates prune scenarios that no longer exist
    hlo_audit.update_budget_entries(path, fp, {"gone": stats})
    assert hlo_audit.prune_budget_entries(path, fp, {"s1"}) == ["gone"]
    assert hlo_audit.budget_entry(
        hlo_audit.load_budgets(path), fp, "s1") is not None
    # memory regression + missing budget
    found = hlo_audit.audit_memory_budget("s1", 11000, entry)
    assert rules_of(found) == {"UL203"}
    found = hlo_audit.audit_memory_budget("s1", 11000, None)
    assert [f.severity for f in found] == ["warning"]
    # stale fingerprints self-invalidate: entries keyed elsewhere unread
    assert hlo_audit.budget_entry(budgets, "other|fp", "s1") is None
    # updating one scenario keeps other fingerprints' sections intact
    hlo_audit.update_budget_entries(path, "other|fp", {"s2": stats})
    budgets = hlo_audit.load_budgets(path)
    assert hlo_audit.budget_entry(budgets, fp, "s1") is not None


# ---------------------------------------------------------------------
# Pass 3: UL204 / UL205 units
# ---------------------------------------------------------------------

def test_ul204_collective_divergence():
    from unicore_tpu.analysis.hlo_audit import audit_sequence_match

    a = [_coll("all-gather", 64, [(0, 1)], shape="f32[64]"),
         _coll("all-reduce", 64, [(0, 1)], shape="f32[64]")]
    b = list(reversed(a))  # order must NOT matter
    assert audit_sequence_match("g", [("s1", a), ("s2", b)]) == []
    c = a + [_coll("all-gather", 64, [(0, 1)], shape="f32[128]")]
    found = audit_sequence_match("g", [("s1", a), ("s3", c)])
    assert rules_of(found) == {"UL204"}
    assert "f32[128]" in found[0].message


def test_ul205_serve_recompiles():
    from unicore_tpu.analysis.hlo_audit import audit_serve_recompiles

    # the unified ragged step's constant two-width surface is clean
    declared = (1, 32)
    width_fn = lambda m: 1 if m <= 1 else 32  # noqa: E731
    assert audit_serve_recompiles(width_fn, declared, 32) == []
    # a broken width fn: one lowering per chunk size
    found = audit_serve_recompiles(lambda m: max(m, 8), declared, 92)
    assert rules_of(found) == {"UL205"}
    # chunk sizes 1..92 through max(m, 8): 85 distinct lowerings
    assert "85 distinct" in found[0].message


# ---------------------------------------------------------------------
# Pass 3 integration: the real compiled fsdp2 step (one compile,
# shared) and the deliberately disengaged spec (ISSUE 4 acceptance)
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def fsdp2_compiled():
    import os

    from unicore_tpu.analysis.scenarios import (
        build_bert_scenario,
        restore_globals,
        snapshot_globals,
    )

    snap = snapshot_globals()
    try:
        trainer, samples, _ = build_bert_scenario(
            os.path.join(_repo_root(), "examples", "bert"),
            {"fsdp_size": 2}, jax.devices()[:8],
        )
        art = trainer.trace_train_step(samples)
        compiled = art["lowered"].compile()
        yield trainer, art, compiled
    finally:
        restore_globals(snap)


@pytest.mark.slow  # AOT-compiles the real step; CI's full pytest runs it
def test_ul201_silent_on_healthy_fsdp2(fsdp2_compiled):
    from unicore_tpu.analysis import hlo_audit

    trainer, art, compiled = fsdp2_compiled
    found, stats, colls = hlo_audit.audit_compiled(
        compiled, context="bert/fsdp2", mesh=trainer.mesh,
        params=art["state"]["params"], num_devices=8,
    )
    assert found == [], "\n".join(f.render() for f in found)
    # the compiled step's collectives are real and byte-counted
    assert stats["collective_bytes"].get("all-gather", 0) > 0
    assert stats["peak_bytes"] and stats["peak_bytes"] > 0
    assert any(c.kind == "all-gather" and c.is_float for c in colls)


@pytest.mark.slow  # AOT-compiles the real step; CI's full pytest runs it
def test_ul201_fires_on_disengaged_fsdp_spec():
    """ISSUE 4 acceptance: a deliberately disengaged fsdp spec (state
    installed replicated on an fsdp mesh) must trip UL201 through the
    REAL compile path."""
    import os

    from unicore_tpu.analysis import hlo_audit
    from unicore_tpu.analysis.scenarios import (
        build_bert_scenario,
        restore_globals,
        snapshot_globals,
    )

    snap = snapshot_globals()
    try:
        trainer, samples, _ = build_bert_scenario(
            os.path.join(_repo_root(), "examples", "bert"),
            {"fsdp_size": 2}, jax.devices()[:8],
        )
        trainer.init_state(samples[0])
        rep = jax.sharding.NamedSharding(
            trainer.mesh, jax.sharding.PartitionSpec()
        )
        trainer._state_shardings = jax.tree_util.tree_map(
            lambda _: rep, trainer._state_shardings
        )
        trainer.state = jax.device_put(
            jax.device_get(trainer.state), rep
        )
        art = trainer.trace_train_step(samples)
        compiled = art["lowered"].compile()
        found, _, _ = hlo_audit.audit_compiled(
            compiled, context="bert/fsdp2-disengaged",
            mesh=trainer.mesh, params=art["state"]["params"],
            num_devices=8,
        )
        assert "UL201" in rules_of(found), found
    finally:
        restore_globals(snap)


@pytest.mark.slow  # AOT-compiles the real step; CI's full pytest runs it
def test_real_budget_roundtrip_from_compiled_step(fsdp2_compiled,
                                                  tmp_path):
    """--pass3 budget semantics against the real compiled stats: update
    -> clean; shrink the committed budget -> UL202 + UL203 fail."""
    import json as _json

    from unicore_tpu.analysis import hlo_audit

    _, _, compiled = fsdp2_compiled
    _, stats, _ = hlo_audit.audit_compiled(compiled,
                                           context="bert/fsdp2")
    path = str(tmp_path / "comms.json")
    fp = hlo_audit.pass3_fingerprint()
    hlo_audit.update_budget_entries(path, fp, {"bert/fsdp2": stats})
    entry = hlo_audit.budget_entry(hlo_audit.load_budgets(path), fp,
                                   "bert/fsdp2")
    assert hlo_audit.audit_comms_budget("bert/fsdp2", stats,
                                        entry) == []
    assert hlo_audit.audit_memory_budget(
        "bert/fsdp2", stats["peak_bytes"], entry) == []
    # an exceeded committed budget must fail
    data = _json.load(open(path))
    e = data["budgets"][fp]["bert/fsdp2"]
    e["collective_bytes"] = {
        k: int(v * 0.5) for k, v in e["collective_bytes"].items()
    }
    e["peak_bytes"] = int(e["peak_bytes"] * 0.5)
    _json.dump(data, open(path, "w"))
    entry = hlo_audit.budget_entry(hlo_audit.load_budgets(path), fp,
                                   "bert/fsdp2")
    rules = rules_of(
        hlo_audit.audit_comms_budget("bert/fsdp2", stats, entry)
        + hlo_audit.audit_memory_budget("bert/fsdp2",
                                        stats["peak_bytes"], entry)
    )
    assert rules == {"UL202", "UL203"}


# ---------------------------------------------------------------------
# Pass 3: the serve engine's jits through Pass 1 + Pass 3 (no device
# execution)
# ---------------------------------------------------------------------

@pytest.mark.slow  # subprocess/compile latency; CI's full pytest runs it
def test_serve_jits_trace_clean_through_pass1_and_pass3(tmp_path):
    from unicore_tpu.analysis import hlo_audit
    from unicore_tpu.analysis.scenarios import build_demo_serve_engine
    from unicore_tpu.analysis.trace_audit import (
        audit_donation,
        audit_jaxpr,
    )

    engine = build_demo_serve_engine()
    # the ragged unification's whole point: the compile surface is a
    # CONSTANT two widths, independent of prompt length (the old
    # per-pow2-bucket family here was (8, 16, 32, 64, 128) + decode)
    assert engine.serve_step_widths() == (1, engine.prefill_chunk)
    assert hlo_audit.audit_serve_recompiles(
        engine.width_fn, engine.serve_step_widths(),
        engine.prefill_chunk,
    ) == []
    arts = engine.trace_step_fns()
    assert set(arts) == {"ragged-w1", f"ragged-w{engine.prefill_chunk}"}
    for name, art in arts.items():
        found = audit_jaxpr(art["jaxpr"], context=f"serve/{name}")
        found += audit_donation(art["lowered"], context=f"serve/{name}")
        assert found == [], (name,
                             "\n".join(f.render() for f in found))
        compiled = art["lowered"].compile()
        _, stats, _ = hlo_audit.audit_compiled(
            compiled, context=f"serve/{name}"
        )
        assert stats["peak_bytes"] is None or stats["peak_bytes"] > 0
    # a sabotaged width fn (one lowering per chunk size — the
    # recompile explosion) is caught statically before it can compile
    engine.width_fn = lambda m: max(m, 1)
    found = hlo_audit.audit_serve_recompiles(
        engine.width_fn, engine.serve_step_widths(),
        engine.prefill_chunk,
    )
    assert rules_of(found) == {"UL205"}


# ---------------------------------------------------------------------
# Pass 3 CLI contract: merged JSON schema, exit codes, budget
# round-trip through the real CLI (dp variant: the fastest compile)
# ---------------------------------------------------------------------

def _run_cli(args, timeout=420):
    return subprocess.run(
        [sys.executable, "-m", "unicore_tpu.analysis", "-q"] + args,
        cwd=_repo_root(), capture_output=True, text=True,
        timeout=timeout,
    )


@pytest.mark.slow  # three subprocess AOT compiles (~2 min) — CI's full
def test_cli_pass3_budget_roundtrip_and_schema(tmp_path):  # pytest runs it
    budget = str(tmp_path / "comms.json")
    report = str(tmp_path / "r1.json")
    base = ["--no-lint", "--no-trace", "--config", "examples/bert",
            "--cpu-devices", "8", "--pass3", "--pass3-variants", "dp",
            "--budget-file", budget]
    # 1) fresh budgets: --update-budgets writes and exits clean
    proc = _run_cli(base + ["--update-budgets", "--json", report])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    r = json.loads(open(report).read())
    assert set(r["counts"]) == {"new", "suppressed"}
    assert r["pass3"]["fingerprint"]
    scen = {s["scenario"]: s for s in r["pass3"]["scenarios"]}
    assert "bert/dp" in scen
    assert scen["bert/dp"]["collective_bytes"]["all-reduce"] > 0
    assert scen["bert/dp"]["peak_bytes"] > 0
    # 2) a committed budget exceeded by >5% fails the CLI
    data = json.loads(open(budget).read())
    fp = r["pass3"]["fingerprint"]
    entry = data["budgets"][fp]["bert/dp"]
    entry["collective_bytes"] = {
        k: int(v * 0.5) for k, v in entry["collective_bytes"].items()
    }
    entry["peak_bytes"] = int(entry["peak_bytes"] * 0.5)
    open(budget, "w").write(json.dumps(data))
    proc = _run_cli(base + ["--json", report])
    assert proc.returncode == 1, proc.stdout + proc.stderr
    rules = {f["rule"]
             for f in json.loads(open(report).read())["new_findings"]}
    assert {"UL202", "UL203"} <= rules
    # 3) --update-budgets accepts the change and the run passes again
    proc = _run_cli(base + ["--update-budgets"])
    assert proc.returncode == 0, proc.stdout + proc.stderr


@pytest.mark.slow  # subprocess/compile latency; CI's full pytest runs it
def test_cli_check_baseline_flags_rot(tmp_path):
    clean = tmp_path / "clean.py"
    clean.write_text("def f():\n    return 1\n")
    rotten = tmp_path / "baseline.json"
    rotten.write_text(json.dumps({"version": 1, "suppressions": [{
        "rule": "UL104", "name": "blocking-fetch",
        "location": "gone.py", "message": "was fixed long ago",
        "fingerprint": "deadbeefdeadbeef",
    }]}))
    proc = subprocess.run(
        [sys.executable, "-m", "unicore_tpu.analysis", "--no-trace",
         "-q", "--lint-root", str(clean), "--baseline", str(rotten),
         "--check-baseline"],
        cwd=_repo_root(), capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 1
    assert "stale" in proc.stdout
    # without --check-baseline the same rot passes silently
    proc = subprocess.run(
        [sys.executable, "-m", "unicore_tpu.analysis", "--no-trace",
         "-q", "--lint-root", str(clean), "--baseline", str(rotten)],
        cwd=_repo_root(), capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0


# ---------------------------------------------------------------------
# satellite: dropout identity/full-drop quantization warning
# ---------------------------------------------------------------------

def test_dropout_warns_once_on_identity_quantization(caplog):
    import importlib

    dropout_mod = importlib.import_module("unicore_tpu.ops.dropout")

    dropout_mod._warned_rates.clear()
    x = jnp.ones((8,))
    rng = jax.random.PRNGKey(0)
    with caplog.at_level("WARNING", logger=dropout_mod.__name__):
        out = dropout_mod.dropout(x, 0.001, rng)  # quantizes to identity
        dropout_mod.dropout(x, 0.001, rng)        # second call: no new warn
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
    warns = [r for r in caplog.records if "quantizes" in r.message]
    assert len(warns) == 1


def test_dropout_strict_raises_on_dead_rate():
    import importlib

    dropout_mod = importlib.import_module("unicore_tpu.ops.dropout")

    x = jnp.ones((8,))
    rng = jax.random.PRNGKey(0)
    with pytest.raises(ValueError, match="quantizes"):
        dropout_mod.dropout(x, 0.9995, rng, strict=True)
    # representable rates never warn or raise
    dropout_mod.dropout(x, 0.1, rng, strict=True)


def test_dropout_zero_and_one_rates_stay_silent(caplog):
    import importlib

    dropout_mod = importlib.import_module("unicore_tpu.ops.dropout")

    dropout_mod._warned_rates.clear()
    x = jnp.ones((8,))
    rng = jax.random.PRNGKey(0)
    with caplog.at_level("WARNING", logger=dropout_mod.__name__):
        dropout_mod.dropout(x, 0.0, rng)
        out = dropout_mod.dropout(x, 1.0, rng)
    np.testing.assert_array_equal(np.asarray(out), np.zeros_like(x))
    assert [r for r in caplog.records if "quantizes" in r.message] == []


# ---------------------------------------------------------------------
# UL201 zero1 certification (ISSUE 15): synthetic units + real compiles
# ---------------------------------------------------------------------

def test_ul201_zero1_unit_fires_and_stays_silent():
    from unicore_tpu.analysis.hlo_audit import audit_zero1_collectives

    mesh = _mesh()  # data=8
    params = {"w": jnp.zeros((64, 64), jnp.float32)}  # 16 KiB leaf
    data_slab = [range(8)]
    healthy = [
        _coll("all-reduce", 16384, data_slab),
        _coll("all-gather", 20000, data_slab),
    ]
    assert audit_zero1_collectives(mesh, healthy, params,
                                   context="t") == []
    # reduce-scatter proper (the TPU form) also satisfies the rule
    rs = [
        _coll("reduce-scatter", 2048, data_slab),
        _coll("all-gather", 20000, data_slab),
    ]
    assert audit_zero1_collectives(mesh, rs, params, context="t") == []
    # plain dp signature: data all-reduce but no param-sized gather
    dead = [_coll("all-reduce", 16384, data_slab)]
    found = audit_zero1_collectives(mesh, dead, params, context="t")
    assert rules_of(found) == {"UL201"}
    assert "zero1-disengaged" in found[0].name
    # no data reduction at all: both signatures missing
    none = [_coll("all-gather", 512, data_slab)]
    found = audit_zero1_collectives(mesh, none, params, context="t")
    assert len(found) == 2
    # a tensor-axis gather must not count toward the data signature
    mesh_tp = _mesh(tensor=2)  # data=4, tensor=2
    tp_pairs = [(0, 1), (2, 3), (4, 5), (6, 7)]  # vary along tensor
    tp_only = [
        _coll("all-reduce", 16384, [(0, 2, 4, 6), (1, 3, 5, 7)]),
        _coll("all-gather", 20000, tp_pairs),
    ]
    found = audit_zero1_collectives(mesh_tp, tp_only, params, context="t")
    assert rules_of(found) == {"UL201"}
    # 1-device data axis: --zero1 is a declared no-op, rule silent
    mesh_1 = jax.sharding.Mesh(
        np.asarray(jax.devices()[:8]).reshape(1, 8, 1, 1),
        ("data", "fsdp", "seq", "tensor"),
    )
    assert audit_zero1_collectives(mesh_1, dead, params, context="t") == []


@pytest.fixture(scope="module")
def zero1_compiled():
    import os

    from unicore_tpu.analysis.scenarios import (
        build_bert_scenario,
        restore_globals,
        snapshot_globals,
    )

    snap = snapshot_globals()
    try:
        trainer, samples, _ = build_bert_scenario(
            os.path.join(_repo_root(), "examples", "bert"),
            {"zero1": True, "optim_bf16_moments": True},
            jax.devices()[:8],
        )
        art = trainer.trace_train_step(samples)
        compiled = art["lowered"].compile()
        yield trainer, art, compiled
    finally:
        restore_globals(snap)


@pytest.mark.slow  # AOT-compiles the real step; CI's full pytest runs it
def test_ul201_zero1_silent_on_healthy_compile(zero1_compiled):
    """ISSUE 15 acceptance: the real --zero1 --optim-bf16-moments
    compile carries the sharded-update group signature (data-axis
    reduction + param-sized update all-gathers) and the certifier is
    silent; the moments really are data-sharded bf16."""
    from unicore_tpu.analysis import hlo_audit

    trainer, art, compiled = zero1_compiled
    colls = hlo_audit.extract_collectives(compiled.as_text(), 8)
    found = hlo_audit.audit_zero1_collectives(
        trainer.mesh, colls, art["state"]["params"], context="bert/zero1"
    )
    assert found == [], "\n".join(f.render() for f in found)
    for leaf in jax.tree_util.tree_leaves(
            trainer.state["opt_state"]["exp_avg"]):
        assert leaf.dtype == jnp.bfloat16
        if leaf.ndim >= 2:
            axes = {a for e in leaf.sharding.spec if e
                    for a in (e if isinstance(e, tuple) else (e,))}
            assert "data" in axes


@pytest.mark.slow  # AOT-compiles the real step; CI's full pytest runs it
def test_ul201_zero1_fires_on_disengaged_spec():
    """The disengaged fixture: a plain-dp compile (moments replicated)
    audited under a declared --zero1 must fire — the update gathers
    that prove per-replica sharding are absent."""
    import os

    from unicore_tpu.analysis import hlo_audit
    from unicore_tpu.analysis.scenarios import (
        build_bert_scenario,
        restore_globals,
        snapshot_globals,
    )

    snap = snapshot_globals()
    try:
        trainer, samples, _ = build_bert_scenario(
            os.path.join(_repo_root(), "examples", "bert"), {},
            jax.devices()[:8],
        )
        art = trainer.trace_train_step(samples)
        compiled = art["lowered"].compile()
        colls = hlo_audit.extract_collectives(compiled.as_text(), 8)
        found = hlo_audit.audit_zero1_collectives(
            trainer.mesh, colls, art["state"]["params"],
            context="bert/zero1-disengaged",
        )
        assert "UL201" in rules_of(found), found
        assert any("zero1-disengaged" in f.name for f in found)
    finally:
        restore_globals(snap)


def test_committed_zero1_budget_strictly_below_dp():
    """ISSUE 15 acceptance: the committed UL203 budget pins the zero1
    scenarios' peak HBM strictly below their replicated baselines for
    this environment's fingerprint."""
    import os

    from unicore_tpu.analysis import hlo_audit

    path = os.path.join(_repo_root(), "tools", "comms_baseline.json")
    budgets = hlo_audit.load_budgets(path)
    fp = hlo_audit.pass3_fingerprint()
    section = budgets.get("budgets", {}).get(fp)
    if not section or "bert/zero1" not in section:
        pytest.skip(f"no committed budgets for fingerprint {fp}")
    assert (section["bert/zero1"]["peak_bytes"]
            < section["bert/dp"]["peak_bytes"])
    assert (section["bert/zero1_tp2"]["peak_bytes"]
            < section["bert/tp2"]["peak_bytes"])


# ---------------------------------------------------------------------
# UL114 replicated-optim-state (ISSUE 15)
# ---------------------------------------------------------------------

def test_ul114_fires_on_bare_init_in_zero1_module(tmp_path):
    found = _lint_snippet(tmp_path, "tr.py", """
        import jax
        class T:
            def setup(self, args, params):
                self.zero1 = bool(args.zero1)
                self.opt_state = self.optimizer.init(params)
    """)
    assert "UL114" in rules_of(found)


def test_ul114_fires_on_init_allocations(tmp_path):
    found = _lint_snippet(tmp_path, "opt.py", """
        import jax
        import jax.numpy as jnp
        class Opt:
            def __init__(self, args):
                self.zero1 = args.zero1
            def init(self, params):
                return jax.tree_util.tree_map(jnp.zeros_like, params)
    """)
    assert "UL114" in rules_of(found)
    found = _lint_snippet(tmp_path, "opt2.py", """
        import jax
        import jax.numpy as jnp
        class Opt:
            def __init__(self, args):
                self.zero1 = args.zero1
            def init(self, params):
                zeros = lambda p: jnp.zeros(p.shape, dtype=jnp.float32)
                return jax.tree_util.tree_map(zeros, params)
    """)
    assert "UL114" in rules_of(found)


def test_ul114_silent_on_sanctioned_paths(tmp_path):
    # jit(init, out_shardings=...) — the Trainer._init_opt_state shape
    found = _lint_snippet(tmp_path, "ok1.py", """
        import jax
        class T:
            def setup(self, args, params, sh):
                self.zero1 = bool(args.zero1)
                self.opt_state = jax.jit(
                    self.optimizer.init, out_shardings=sh)(params)
    """)
    assert "UL114" not in rules_of(found)
    # result wrapped in a sharding constraint
    found = _lint_snippet(tmp_path, "ok2.py", """
        import jax
        class T:
            def setup(self, args, params, sh):
                self.zero1 = bool(args.zero1)
                self.opt_state = jax.lax.with_sharding_constraint(
                    self.optimizer.init(params), sh)
    """)
    assert "UL114" not in rules_of(found)
    # no zero1 plumbing: replicated moments are just the dp layout
    found = _lint_snippet(tmp_path, "ok3.py", """
        import jax
        import jax.numpy as jnp
        class Opt:
            def init(self, params):
                return jax.tree_util.tree_map(jnp.zeros_like, params)
        class T:
            def setup(self, params):
                self.opt_state = self.optimizer.init(params)
    """)
    assert "UL114" not in rules_of(found)


def test_ul114_inline_suppression(tmp_path):
    found = _lint_snippet(tmp_path, "sup.py", """
        import jax
        class T:
            def setup(self, args, params):
                self.zero1 = bool(args.zero1)
                self.opt_state = self.optimizer.init(params)  # unicore-lint: disable=UL114
    """)
    assert "UL114" not in rules_of(found)


def test_ul114_repo_sweep_clean():
    import os

    root = _repo_root()
    found = [
        f for f in lint_paths(
            [os.path.join(root, "unicore_tpu"),
             os.path.join(root, "bench.py"),
             os.path.join(root, "tools")],
            rel_to=root,
        )
        if f.rule == "UL114"
    ]
    assert found == [], "\n".join(f.render() for f in found)


# ---------------------------------------------------------------------
# Pass 4: compiled-schedule audit (UL301/UL302/UL303) —
# unicore_tpu/analysis/schedule_audit.py
# ---------------------------------------------------------------------

def _sched_module(body):
    """Synthetic scheduled-HLO module text in the exact dump format
    ``compiled.as_text()`` emits (two-space indent, ``%name = shape
    op(...)``) — the fixtures feed the SAME parser path a real
    compile's text does."""
    return (
        "HloModule fixture, is_scheduled=true\n\n"
        "ENTRY %main.1 (p0: f32[64,64]) -> f32[64,64] {\n"
        "  %p0 = f32[64,64]{1,0} parameter(0)\n"
        + body
        + "  ROOT %out.1 = f32[64,64]{1,0} add(f32[64,64]{1,0} %p0, "
          "f32[64,64]{1,0} %p0)\n}\n"
    )


_AG_START = (
    "  %ag-start = (f32[64,64]{1,0}, f32[128,64]{1,0}) "
    "all-gather-start(f32[64,64]{1,0} %p0), replica_groups={{0,1}}, "
    "dimensions={0}\n"
)
_AG_DONE = (
    "  %ag-done = f32[128,64]{1,0} all-gather-done((f32[64,64]{1,0}, "
    "f32[128,64]{1,0}) %ag-start)\n"
)
# 2 * 64*64 result elems * 128 contraction = 1048576 flops
_BIG_DOT = (
    "  %dot.1 = f32[64,64]{1,0} dot(f32[64,128]{1,0} %p0, "
    "f32[128,64]{1,0} %p0), lhs_contracting_dims={1}, "
    "rhs_contracting_dims={0}\n"
)


def test_schedule_parser_structure_and_pairs():
    from unicore_tpu.analysis import schedule_audit as sa

    comps = sa.parse_schedule(_sched_module(_AG_START + _BIG_DOT
                                            + _AG_DONE))
    assert len(comps) == 1 and comps[0].is_entry
    ops = [i.op for i in comps[0].instrs]
    assert ops == ["parameter", "all-gather-start", "dot",
                   "all-gather-done", "add"]
    pairs, unmatched, orphans, crossed = sa.match_async_pairs(comps[0])
    assert len(pairs) == 1 and not (unmatched or orphans or crossed)
    start, done = pairs[0]
    assert start.kind == "all-gather" and start.is_float
    # -start tuple result counts the LARGEST component only (the
    # operand alias must not double-count the transfer)
    assert start.bytes == 128 * 64 * 4


def test_schedule_parser_interleaved_pairs_match_by_operand():
    from unicore_tpu.analysis import schedule_audit as sa

    body = (
        _AG_START
        + "  %ar-start = f32[256]{0} all-reduce-start(f32[256]{0} %p0), "
          "replica_groups={{0,1}}, to_apply=%add\n"
        + _BIG_DOT
        + _AG_DONE
        + "  %ar-done = f32[256]{0} all-reduce-done(f32[256]{0} "
          "%ar-start)\n"
    )
    comps = sa.parse_schedule(_sched_module(body))
    pairs, unmatched, orphans, crossed = sa.match_async_pairs(comps[0])
    # healthy interleaving (s1 s2 d1 d2) pairs by OPERAND, not nesting
    assert {(s.name, d.name) for s, d in pairs} == {
        ("ag-start", "ag-done"), ("ar-start", "ar-done")}
    assert not (unmatched or orphans or crossed)
    found, stats = sa.audit_schedule_text(
        _sched_module(body), context="fix")
    assert [f for f in found if f.rule == "UL303"] == []
    assert stats["async_pairs"] == 2


def test_schedule_window_attribution_counts_dot_flops():
    from unicore_tpu.analysis import schedule_audit as sa

    _, stats = sa.audit_schedule_text(
        _sched_module(_AG_START + _BIG_DOT + _AG_DONE), context="fix")
    assert stats["window_flops"] == 2 * 64 * 64 * 128
    assert stats["async_collectives"] == 1
    assert stats["overlap_ratio"] == 1.0
    assert stats["exposed_collective_bytes"] == 0


def test_ul303_unmatched_start_and_orphan_done():
    from unicore_tpu.analysis import schedule_audit as sa

    found, _ = sa.audit_schedule_text(
        _sched_module(_AG_START + _BIG_DOT), context="fix")
    msgs = [f for f in found if f.rule == "UL303"]
    assert msgs and "no matching -done" in msgs[0].message

    found, _ = sa.audit_schedule_text(
        _sched_module(_BIG_DOT + _AG_DONE), context="fix")
    msgs = [f for f in found if f.rule == "UL303"]
    assert msgs and "no known -start" in msgs[0].message


def test_ul303_crossed_pair_is_corruption():
    from unicore_tpu.analysis import schedule_audit as sa

    found, _ = sa.audit_schedule_text(
        _sched_module(_AG_DONE + _BIG_DOT + _AG_START), context="fix")
    msgs = [f.message for f in found if f.rule == "UL303"]
    assert any("BEFORE its start" in m for m in msgs), found


def test_ul303_zero_width_window_warns():
    from unicore_tpu.analysis import schedule_audit as sa

    found, stats = sa.audit_schedule_text(
        _sched_module(_AG_START + _AG_DONE + _BIG_DOT), context="fix")
    assert stats["zero_width_pairs"] == 1
    assert any(f.rule == "UL303" and f.severity == "warning"
               for f in found)


def test_ul301_fires_on_serialized_schedule():
    """The deliberately serialized fixture: an empty start/done window
    with overlappable compute scheduled after it must fire UL301."""
    from unicore_tpu.analysis import schedule_audit as sa

    found, stats = sa.audit_schedule_text(
        _sched_module(_AG_START + _AG_DONE + _BIG_DOT), context="fix")
    fired = [f for f in found if f.rule == "UL301"]
    assert fired and "exposed" in fired[0].message
    assert stats["overlap_ratio"] == 0.0
    assert stats["exposed_collective_bytes"] == 128 * 64 * 4


def test_ul301_silent_when_overlapped():
    from unicore_tpu.analysis import schedule_audit as sa

    found, _ = sa.audit_schedule_text(
        _sched_module(_AG_START + _BIG_DOT + _AG_DONE), context="fix")
    assert [f for f in found if f.rule == "UL301"] == []


def test_ul301_whitelists_tail_positioned_collective():
    """Nothing above the compute floor after the done: there is no
    compute left to hide the collective behind — silent."""
    from unicore_tpu.analysis import schedule_audit as sa

    found, _ = sa.audit_schedule_text(
        _sched_module(_BIG_DOT + _AG_START + _AG_DONE), context="fix")
    assert [f for f in found if f.rule == "UL301"] == []


def test_ul301_whitelists_op_name_patterns():
    from unicore_tpu.analysis import schedule_audit as sa

    wl_start = _AG_START.replace(
        "dimensions={0}\n",
        'dimensions={0}, metadata={op_name="zero1_param_gather"}\n')
    found, _ = sa.audit_schedule_text(
        _sched_module(wl_start + _AG_DONE + _BIG_DOT), context="fix")
    assert [f for f in found if f.rule == "UL301"] == []


def test_ul301_ignores_int_collectives():
    from unicore_tpu.analysis import schedule_audit as sa

    body = (
        "  %rng-start = (u32[64]{0}, u32[128]{0}) all-gather-start("
        "u32[64]{0} %p0), replica_groups={{0,1}}, dimensions={0}\n"
        "  %rng-done = u32[128]{0} all-gather-done((u32[64]{0}, "
        "u32[128]{0}) %rng-start)\n" + _BIG_DOT
    )
    found, _ = sa.audit_schedule_text(_sched_module(body), context="fix")
    assert [f for f in found if f.rule == "UL301"] == []


def test_sync_collectives_count_as_exposed():
    """XLA:CPU lowers every collective synchronously — no async pairs;
    every byte exposed by construction (the documented CPU caveat)."""
    from unicore_tpu.analysis import schedule_audit as sa

    body = (
        "  %ar.1 = f32[256]{0} all-reduce(f32[256]{0} %p0), "
        "replica_groups={{0,1}}, to_apply=%add\n" + _BIG_DOT
    )
    found, stats = sa.audit_schedule_text(
        _sched_module(body), context="fix")
    assert found == []
    assert stats["sync_collectives"] == 1
    assert stats["async_pairs"] == 0
    assert stats["overlap_ratio"] == 0.0
    assert stats["exposed_collective_bytes"] == 256 * 4
    assert stats["exposed_collective_bytes"] == \
        stats["total_collective_bytes"]


def test_ul302_budget_semantics(tmp_path):
    from unicore_tpu.analysis import hlo_audit
    from unicore_tpu.analysis import schedule_audit as sa

    stats = {"total_collective_bytes": 1000,
             "overlapped_collective_bytes": 600,
             "exposed_collective_bytes": 400, "overlap_ratio": 0.6}
    # no committed entry -> warning nudge toward --update-budgets
    got = sa.audit_overlap_budget("bert/dp", stats, None)
    assert [f.severity for f in got] == ["warning"]
    # matching entry -> clean
    entry = {"exposed_collective_bytes": 400, "overlap_ratio": 0.6}
    assert sa.audit_overlap_budget("bert/dp", stats, entry) == []
    # exposed bytes regressed >5% -> error
    got = sa.audit_overlap_budget(
        "bert/dp", stats, {"exposed_collective_bytes": 300,
                           "overlap_ratio": 0.6})
    assert [f.rule for f in got] == ["UL302"]
    assert got[0].severity == "error"
    # overlap ratio regressed >5% -> error
    got = sa.audit_overlap_budget(
        "bert/dp", stats, {"exposed_collective_bytes": 400,
                           "overlap_ratio": 0.8})
    assert [f.rule for f in got] == ["UL302"]
    # budgeted fully-overlapped: ANY exposure fires
    got = sa.audit_overlap_budget(
        "bert/dp", stats, {"exposed_collective_bytes": 0,
                           "overlap_ratio": 1.0})
    assert {f.rule for f in got} == {"UL302"}
    # a scenario with no collectives has nothing to budget
    assert sa.audit_overlap_budget(
        "serve/ragged-w1", {"total_collective_bytes": 0}, None) == []


def test_budget_entries_merge_across_passes(tmp_path):
    """Pass-3 and Pass-4 keys share one scenario entry: refreshing
    either pass must not erase the other's keys."""
    from unicore_tpu.analysis import hlo_audit
    from unicore_tpu.analysis import schedule_audit as sa

    path = str(tmp_path / "comms.json")
    fp = "fmtX|test|n8|jax0"
    hlo_audit.update_budget_entries(path, fp, {"bert/dp": {
        "collective_bytes": {"all-reduce": 123}, "peak_bytes": 456}})
    sa.update_schedule_budget_entries(path, fp, {"bert/dp": {
        "overlap_ratio": 0.5, "exposed_collective_bytes": 789}})
    entry = hlo_audit.budget_entry(hlo_audit.load_budgets(path), fp,
                                   "bert/dp")
    assert entry == {"collective_bytes": {"all-reduce": 123},
                     "peak_bytes": 456, "overlap_ratio": 0.5,
                     "exposed_collective_bytes": 789}
    # pass3 refresh keeps pass4 keys; pass4 refresh keeps pass3 keys
    hlo_audit.update_budget_entries(path, fp, {"bert/dp": {
        "collective_bytes": {"all-reduce": 200}, "peak_bytes": 500}})
    sa.update_schedule_budget_entries(path, fp, {"bert/dp": {
        "overlap_ratio": 0.25, "exposed_collective_bytes": 1000}})
    entry = hlo_audit.budget_entry(hlo_audit.load_budgets(path), fp,
                                   "bert/dp")
    assert entry == {"collective_bytes": {"all-reduce": 200},
                     "peak_bytes": 500, "overlap_ratio": 0.25,
                     "exposed_collective_bytes": 1000}


def test_schedule_audit_deterministic_on_same_text():
    from unicore_tpu.analysis import schedule_audit as sa

    text = _sched_module(_AG_START + _AG_DONE + _BIG_DOT)
    f1, s1 = sa.audit_schedule_text(text, context="fix")
    f2, s2 = sa.audit_schedule_text(text, context="fix")
    assert s1 == s2
    assert [f.fingerprint for f in f1] == [f.fingerprint for f in f2]


@pytest.mark.slow  # AOT-compiles the real step; CI's full pytest runs it
def test_pass4_silent_on_healthy_zero1_compile(zero1_compiled):
    """Acceptance: the healthy real compile is UL301/UL303-silent, and
    its stats carry the documented CPU shape — sync collectives only,
    every byte exposed (the before-number the item-5 overlap campaign
    commits to push down)."""
    from unicore_tpu.analysis import schedule_audit as sa

    _, _, compiled = zero1_compiled
    found, stats = sa.audit_compiled_schedule(compiled,
                                              context="bert/zero1")
    assert found == [], "\n".join(f.render() for f in found)
    assert stats["sync_collectives"] > 0
    assert stats["async_pairs"] == 0
    assert stats["overlap_ratio"] == 0.0
    assert stats["total_collective_bytes"] > 0
    assert stats["exposed_collective_bytes"] == \
        stats["total_collective_bytes"]


@pytest.mark.slow  # AOT-compiles the real step; CI's full pytest runs it
def test_pass4_byte_totals_match_pass3(zero1_compiled):
    """The two passes count the same collectives: Pass 4's total bytes
    must equal the sum of Pass 3's per-kind byte budget."""
    from unicore_tpu.analysis import hlo_audit
    from unicore_tpu.analysis import schedule_audit as sa

    _, _, compiled = zero1_compiled
    text = compiled.as_text()
    colls = hlo_audit.extract_collectives(text, 8)
    _, stats = sa.audit_schedule_text(text, context="bert/zero1")
    assert stats["total_collective_bytes"] == sum(c.bytes for c in colls)


@pytest.mark.slow  # three subprocess AOT compiles (~2 min) — CI's full
def test_cli_pass4_budget_roundtrip_and_schema(tmp_path):  # pytest runs it
    budget = str(tmp_path / "comms.json")
    report = str(tmp_path / "r1.json")
    base = ["--no-lint", "--no-trace", "--config", "examples/bert",
            "--cpu-devices", "8", "--pass4", "--pass3-variants", "dp",
            "--budget-file", budget]
    # 1) fresh budgets: --update-budgets writes and exits clean
    proc = _run_cli(base + ["--update-budgets", "--json", report])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    r = json.loads(open(report).read())
    assert r["pass4"]["fingerprint"]
    assert "pass3" not in r  # --pass4 alone reports pass 4 only
    scen = {s["scenario"]: s for s in r["pass4"]["scenarios"]}
    assert scen["bert/dp"]["overlap_ratio"] == 0.0  # CPU: all exposed
    assert scen["bert/dp"]["exposed_collective_bytes"] > 0
    assert scen["bert/dp"]["sync_collectives"] > 0
    data = json.loads(open(budget).read())
    entry = data["budgets"][r["pass4"]["fingerprint"]]["bert/dp"]
    assert set(entry) == {"overlap_ratio", "exposed_collective_bytes"}
    # 2) a tightened budget (claims less exposure than reality) fails
    entry["exposed_collective_bytes"] = int(
        entry["exposed_collective_bytes"] * 0.5)
    open(budget, "w").write(json.dumps(data))
    proc = _run_cli(base + ["--json", report])
    assert proc.returncode == 1, proc.stdout + proc.stderr
    rules = {f["rule"]
             for f in json.loads(open(report).read())["new_findings"]}
    assert rules == {"UL302"}
    # 3) --update-budgets accepts the measurement; clean again
    proc = _run_cli(base + ["--update-budgets"])
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------
# Budget-scenario rot surface (--check-baseline over comms_baseline)
# ---------------------------------------------------------------------

def test_known_budget_scenarios_cover_committed_file():
    import os

    from unicore_tpu.analysis.scenarios import (
        known_budget_scenarios,
        stale_budget_scenarios,
    )

    known = known_budget_scenarios()
    assert "bert/zero1" in known and "bert/fsdp2-uf1" in known
    assert any(s.startswith("serve/ragged-w") for s in known)
    committed = os.path.join(_repo_root(), "tools",
                             "comms_baseline.json")
    assert stale_budget_scenarios(committed) == []


def test_stale_budget_scenarios_flags_rot(tmp_path):
    from unicore_tpu.analysis.scenarios import stale_budget_scenarios

    path = str(tmp_path / "comms.json")
    with open(path, "w") as fh:
        json.dump({"version": 1, "budgets": {
            "fp-a": {"bert/dp": {}, "serve/prefill-b8": {}},
            "fp-b": {"bert/gone2": {}},
        }}, fh)
    assert stale_budget_scenarios(path) == [
        ("fp-a", "serve/prefill-b8"), ("fp-b", "bert/gone2")]
    # absent file: nothing to check
    assert stale_budget_scenarios(str(tmp_path / "nope.json")) == []


@pytest.mark.slow  # subprocess + serve-engine build; CI runs it
def test_cli_check_baseline_flags_budget_rot(tmp_path):
    clean = tmp_path / "clean.py"
    clean.write_text("def f():\n    return 1\n")
    rotten = tmp_path / "comms.json"
    rotten.write_text(json.dumps({"version": 1, "budgets": {
        "fmt1|cpu|n8|jax0.4.37": {"serve/prefill-b8": {
            "peak_bytes": 1}}}}))
    base = [sys.executable, "-m", "unicore_tpu.analysis", "--no-trace",
            "-q", "--lint-root", str(clean), "--no-baseline",
            "--budget-file", str(rotten)]
    proc = subprocess.run(
        base + ["--check-baseline"], cwd=_repo_root(),
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "stale budget scenario" in proc.stdout
    # without --check-baseline the same rot passes silently
    proc = subprocess.run(
        base, cwd=_repo_root(), capture_output=True, text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------
# UL115 — unjoined daemon thread
# ---------------------------------------------------------------------

def test_ul115_fires_on_unstopped_daemon_worker(tmp_path):
    found = _lint_snippet(tmp_path, "w.py", """
        import threading
        class Worker:
            def go(self):
                self._thread = threading.Thread(
                    target=self._run, daemon=True)
                self._thread.start()
    """)
    assert "UL115" in rules_of(found)


def test_ul115_fires_on_chained_fire_and_forget(tmp_path):
    found = _lint_snippet(tmp_path, "w.py", """
        import threading
        def kick(fn):
            threading.Thread(target=fn, daemon=True).start()
    """)
    fired = [f for f in found if f.rule == "UL115"]
    assert fired and "drops the only reference" in fired[0].message


def test_ul115_silent_with_shutdown_method(tmp_path):
    # the watchdog shape: close() stops the worker with a flag, no join
    found = _lint_snippet(tmp_path, "w.py", """
        import threading
        class Worker:
            def go(self):
                self._thread = threading.Thread(
                    target=self._run, daemon=True)
                self._thread.start()
            def close(self):
                self._stop = True
    """)
    assert "UL115" not in rules_of(found)


def test_ul115_silent_with_join(tmp_path):
    found = _lint_snippet(tmp_path, "w.py", """
        from threading import Thread
        def run_briefly(fn):
            t = Thread(target=fn, daemon=True)
            t.start()
            t.join(timeout=1.0)
    """)
    assert "UL115" not in rules_of(found)


def test_ul115_silent_on_non_daemon_thread(tmp_path):
    # a non-daemon thread blocks exit visibly instead of losing work
    found = _lint_snippet(tmp_path, "w.py", """
        import threading
        def go(fn):
            t = threading.Thread(target=fn)
            t.start()
    """)
    assert "UL115" not in rules_of(found)


def test_ul115_inline_suppression(tmp_path):
    found = _lint_snippet(tmp_path, "w.py", """
        import threading
        def kick(fn):
            threading.Thread(target=fn, daemon=True).start()  # unicore-lint: disable=UL115
    """)
    assert "UL115" not in rules_of(found)


def test_ul115_repo_sweep_clean():
    """async_writer, prefetch pump, watchdog, and the fleet router are
    the intended-clean worker spawns — each owns a stop/close/drain
    shutdown path."""
    import os

    root = _repo_root()
    found = [
        f for f in lint_paths(
            [os.path.join(root, "unicore_tpu"),
             os.path.join(root, "bench.py"),
             os.path.join(root, "tools")],
            rel_to=root,
        )
        if f.rule == "UL115"
    ]
    assert found == [], "\n".join(f.render() for f in found)


# ---------------------------------------------------------------------
# UL116 unverified-checkpoint-read
# ---------------------------------------------------------------------

def _lint_deploy_snippet(tmp_path, code, name="sub.py"):
    """Write the snippet under a deploy/ dir so the UL116 path
    predicate (deploy/serve/fleet code) marks it in scope."""
    d = tmp_path / "deploy"
    d.mkdir(exist_ok=True)
    f = d / name
    f.write_text(textwrap.dedent(code))
    return lint_paths([str(f)])


def test_ul116_fires_on_raw_checkpoint_reads(tmp_path):
    # open(manifest_path), pickle.loads(ckpt_bytes), and both halves of
    # pickle.load(open("checkpoint_last.pt")) are raw checkpoint reads
    # with neither read_verified nor a typed re-raise around them
    found = _lint_deploy_snippet(tmp_path, """
        import pickle
        def read_manifest(manifest_path):
            with open(manifest_path, "rb") as fh:
                return pickle.loads(fh.read())
        def from_bytes(ckpt_bytes):
            return pickle.loads(ckpt_bytes)
        def from_literal():
            return pickle.load(open("checkpoint_last.pt", "rb"))
    """)
    ul116 = [f for f in found if f.rule == "UL116"]
    assert len(ul116) >= 3, found


def test_ul116_silent_on_read_verified_and_typed_reraise(tmp_path):
    # the two sanctioned shapes — bytes straight out of read_verified,
    # or a try whose handler re-raises typed — plus a read that never
    # names checkpoint bytes at all
    found = _lint_deploy_snippet(tmp_path, """
        import pickle
        from unicore_tpu.checkpoint_utils import (CheckpointIntegrityError,
                                                  read_verified)
        def read_manifest(manifest_path):
            return pickle.loads(read_verified(manifest_path))
        def read_guarded(ckpt_path):
            try:
                with open(ckpt_path, "rb") as fh:
                    return pickle.loads(fh.read())
            except OSError as e:
                raise CheckpointIntegrityError(str(e)) from e
        def read_prompts(prompts_path):
            with open(prompts_path) as fh:
                return fh.read()
    """)
    assert "UL116" not in rules_of(found)


def test_ul116_try_does_not_guard_nested_def(tmp_path):
    # a function DEFINED inside a re-raising try executes later,
    # outside the guard — its raw read still fires
    found = _lint_deploy_snippet(tmp_path, """
        import pickle
        def make_loader(manifest_path):
            try:
                def load():
                    return pickle.load(open(manifest_path, "rb"))
            except Exception as e:
                raise RuntimeError("never guards load()") from e
            return load
    """)
    assert "UL116" in rules_of(found)


def test_ul116_ignores_train_side_files(tmp_path):
    found = _lint_snippet(tmp_path, "train_utils.py", """
        import pickle
        def peek(ckpt_path):
            return pickle.load(open(ckpt_path, "rb"))
    """)
    assert "UL116" not in rules_of(found)


def test_ul116_inline_suppression(tmp_path):
    found = _lint_deploy_snippet(tmp_path, """
        import pickle
        def peek(ckpt_path):
            return pickle.load(open(ckpt_path, "rb"))  # unicore-lint: disable=UL116
    """)
    assert "UL116" not in rules_of(found)


def test_ul116_repo_sweep_clean():
    """Every checkpoint/manifest read in deploy/serve/fleet code goes
    through read_verified (deploy/loader.py, deploy/publish.py) or a
    typed re-raise."""
    import os

    root = _repo_root()
    found = [
        f for f in lint_paths(
            [os.path.join(root, "unicore_tpu"),
             os.path.join(root, "bench.py"),
             os.path.join(root, "tools")],
            rel_to=root,
        )
        if f.rule == "UL116"
    ]
    assert found == [], "\n".join(f.render() for f in found)


# ---------------------------------------------------------------------
# UL118 unbounded-replica-growth (elastic fleet satellite)
# ---------------------------------------------------------------------

def test_ul118_fires_on_unbounded_boot_shapes(tmp_path):
    # pressure-retry while loop appending fresh engines: no bound
    found = _lint_snippet(tmp_path, "grow1.py", """
        def grow(factory, engines, pressure):
            while pressure():
                engines.append(factory(len(engines)))
    """)
    assert "UL118" in rules_of(found)
    # subscript store keyed by a counter, not the loop variable
    found = _lint_snippet(tmp_path, "grow2.py", """
        def grow(factory, engines, events):
            n = 0
            for ev in events:
                if ev.hot:
                    n = n + 1
                    engines["a%d" % n] = factory(n)
    """)
    assert "UL118" in rules_of(found)
    # the boot laundered through a name before joining the fleet
    found = _lint_snippet(tmp_path, "grow3.py", """
        def grow(engine_factory, fleet, ticks):
            for t in ticks:
                eng = engine_factory(t.rid)
                fleet.add(eng)
    """)
    assert "UL118" in rules_of(found)


def test_ul118_silent_on_replacement_and_scale_gates(tmp_path):
    # rolling restart's replacement shape: same slot, no growth
    found = _lint_snippet(tmp_path, "roll.py", """
        def roll(factory, engines):
            for rid in sorted(engines):
                engines[rid] = factory(rid)
    """)
    assert "UL118" not in rules_of(found)
    # max-replicas bound in the loop
    found = _lint_snippet(tmp_path, "gated1.py", """
        def grow(factory, engines, pressure, max_replicas):
            while pressure():
                if len(engines) >= max_replicas:
                    break
                engines.append(factory(len(engines)))
    """)
    assert "UL118" not in rules_of(found)
    # a len() bound is a bound even when the cap name says nothing
    found = _lint_snippet(tmp_path, "gated1b.py", """
        def grow(factory, fleet, cap):
            while len(fleet) < cap:
                fleet.append(factory("r"))
    """)
    assert "UL118" not in rules_of(found)
    # cooldown gate in the loop
    found = _lint_snippet(tmp_path, "gated2.py", """
        def grow(factory, engines, pressure, cooldown_ok):
            while pressure():
                if not cooldown_ok():
                    continue
                engines.append(factory(len(engines)))
    """)
    assert "UL118" not in rules_of(found)
    # breaker-gated canary boot
    found = _lint_snippet(tmp_path, "gated3.py", """
        def grow(factory, engines, pressure, breaker):
            while pressure():
                if breaker.ready(0):
                    engines.append(factory(len(engines)))
    """)
    assert "UL118" not in rules_of(found)
    # a factory result that never joins a collection is a local probe
    found = _lint_snippet(tmp_path, "probe.py", """
        def probe(factory, ticks):
            for t in ticks:
                eng = factory(t)
                eng.close()
    """)
    assert "UL118" not in rules_of(found)


def test_ul118_inline_suppression(tmp_path):
    found = _lint_snippet(tmp_path, "sup.py", """
        def grow(factory, engines, pressure):
            while pressure():
                engines.append(factory(len(engines)))  # unicore-lint: disable=UL118
    """)
    assert "UL118" not in rules_of(found)


def test_ul118_repo_sweep_clean():
    """Every replica boot in the repo is gated — the autoscaler's
    envelope (max_replicas + cooldown + boot budget) and the router's
    breaker-gated canary keep fleet growth bounded."""
    import os

    root = _repo_root()
    found = [
        f for f in lint_paths(
            [os.path.join(root, "unicore_tpu"),
             os.path.join(root, "bench.py"),
             os.path.join(root, "tools")],
            rel_to=root,
        )
        if f.rule == "UL118"
    ]
    assert found == [], "\n".join(f.render() for f in found)
