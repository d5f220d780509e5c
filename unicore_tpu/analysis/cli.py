"""``python -m unicore_tpu.analysis`` — the unicore-lint entry point.

Runs all passes and reports machine-readable JSON plus human text:

  Pass 1 (trace audit)     --config examples/bert [--cpu-devices 8]
  Pass 2 (source lint)     on unicore_tpu/ unicore_tpu_cli/ examples/
                           tools/ bench.py
  Pass 3 (compiled audit)  --pass3 [--pass3-serve]: compile the real
                           jitted programs and audit the optimized
                           HLO's collectives + memory against
                           tools/comms_baseline.json
  Pass 4 (schedule audit)  --pass4 [--pass4-serve]: parse the same
                           compiled modules' SCHEDULED text and audit
                           collective/compute overlap (UL301-UL303)
                           against the same budget file
  Pass 5 (determinism)     --pass5 [--pass5-serve]: audit the same
                           compiled modules for nondeterministic
                           execution signatures (UL401), re-compile
                           each scenario and diff the program texts
                           byte-exactly (UL402), and AST-audit the
                           host planning modules that feed device
                           programs (UL403)

Exit code 0 when no findings outside the baseline, 1 otherwise.  CI
pins the baseline (``tools/lint_baseline.json``) so only NEW findings
fail; ``--write-baseline`` regenerates it after an accepted change and
``--check-baseline`` fails on baseline rot (suppressions that no longer
fire).  Pass-3 budgets regenerate via ``--update-budgets``.
"""

import argparse
import json
import os
import sys

DEFAULT_LINT_ROOTS = ("unicore_tpu", "unicore_tpu_cli", "examples",
                      "tools", "bench.py")
DEFAULT_BASELINE = os.path.join("tools", "lint_baseline.json")


def _anchor_dir():
    """Directory the cwd-relative defaults resolve against: the cwd when
    it looks like the repo checkout, else the checkout this package was
    imported from (two levels up).  Running the tool from elsewhere must
    not silently lint an empty set and report 'clean'."""
    if any(os.path.isdir(r) for r in DEFAULT_LINT_ROOTS
           if not r.endswith(".py")):
        return os.getcwd()
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))


def build_parser():
    p = argparse.ArgumentParser(
        prog="python -m unicore_tpu.analysis",
        description="unicore-lint: trace audit + source lint",
    )
    p.add_argument(
        "--config", metavar="DIR",
        help="example plugin dir to trace-audit (e.g. examples/bert); "
             "omit to skip the trace audit",
    )
    p.add_argument(
        "--cpu-devices", type=int, default=0, metavar="N",
        help="force a virtual N-device CPU platform (the 8-device dryrun "
             "mesh CI uses); must be set before jax initializes",
    )
    p.add_argument(
        "--lint-root", action="append", default=None, metavar="PATH",
        help=f"roots for the source lint (default: "
             f"{' '.join(DEFAULT_LINT_ROOTS)})",
    )
    p.add_argument("--no-lint", action="store_true",
                   help="skip Pass 2 (source lint)")
    p.add_argument("--no-trace", action="store_true",
                   help="skip Pass 1 (trace audit) even with --config")
    p.add_argument(
        "--baseline", default=None, metavar="FILE",
        help=f"baseline/suppression file (default: {DEFAULT_BASELINE} "
             f"when present)",
    )
    p.add_argument("--no-baseline", action="store_true",
                   help="ignore any baseline file")
    p.add_argument("--write-baseline", action="store_true",
                   help="accept all current findings into the baseline "
                        "file and exit 0")
    p.add_argument(
        "--check-baseline", action="store_true",
        help="fail when the baseline contains suppressions that no "
             "longer fire (baseline rot); scoped to the rule families "
             "this invocation runs (trace UL0xx, lint UL1xx, pass-3 "
             "UL2xx, pass-4 UL3xx, pass-5 UL4xx), so a partial run "
             "never false-flags "
             "entries it could not have re-fired; also fails on budget "
             "rot — comms_baseline.json entries for scenarios that no "
             "longer exist in scenarios.py",
    )
    p.add_argument(
        "--pass3", action="store_true",
        help="Pass 3: AOT-compile the --config train step per mesh "
             "variant and audit the optimized HLO's collectives and "
             "memory (UL201-UL204) against the budget file",
    )
    p.add_argument(
        "--pass3-serve", action="store_true",
        help="Pass 3 over the demo ServeEngine: trace/lower the "
             "unified ragged step at its constant two widths plus "
             "the sampling variants (Pass-1 rules included) "
             "and audit recompile surface + budgets (UL205, "
             "UL202/UL203)",
    )
    p.add_argument(
        "--pass4", action="store_true",
        help="Pass 4: parse the scheduled optimized-HLO text of the "
             "--config train step per mesh variant and audit "
             "collective/compute overlap (UL301/UL303) plus the "
             "per-scenario overlap budget (UL302); shares its "
             "compiles with --pass3 when both are requested",
    )
    p.add_argument(
        "--pass4-serve", action="store_true",
        help="Pass 4 over the demo ServeEngine's ragged-step "
             "executables (shares compiles with --pass3-serve)",
    )
    p.add_argument(
        "--pass5", action="store_true",
        help="Pass 5: audit the --config train step's optimized HLO "
             "per mesh variant for nondeterministic execution "
             "signatures (UL401), re-compile each variant and diff "
             "the program texts byte-exactly (UL402), and AST-audit "
             "the planning modules (UL403); shares its first compile "
             "with --pass3/--pass4, pays one extra compile per "
             "variant for the identity diff",
    )
    p.add_argument(
        "--pass5-serve", action="store_true",
        help="Pass 5 over the demo ServeEngine's ragged-step "
             "executables: UL401 + the UL402 re-trace/re-compile "
             "identity diff (shares compiles with --pass3-serve), "
             "plus the UL403 planning audit",
    )
    p.add_argument(
        "--pass3-variants", default=None, metavar="CSV",
        help="comma-separated mesh variants for --pass3 (default: "
             "dp,fsdp2,tp2,tp2_fsdp2)",
    )
    p.add_argument(
        "--budget-file", default=None, metavar="FILE",
        help="Pass-3 collective/HBM budget file (default: "
             "tools/comms_baseline.json; entries are keyed by an "
             "environment fingerprint, so stale entries self-invalidate)",
    )
    p.add_argument(
        "--update-budgets", action="store_true",
        help="replace the budget entries for the current environment "
             "fingerprint with this run's measurements before the "
             "budget rules evaluate (the accepted-change workflow)",
    )
    p.add_argument(
        "--fused-head-audit", action="store_true",
        help="certify the fused LM head's memory contract on --config: "
             "per mesh variant, re-trace the train step with UL002's "
             "budget set to the head's full-logits byte size — the "
             "fused default must be silent, the materialized head must "
             "fire (exit 1 otherwise)",
    )
    p.add_argument("--json", default=None, metavar="FILE",
                   help="also write the report as JSON")
    p.add_argument(
        "--big-mib", type=int, default=None, metavar="MIB",
        help="override the UL002 absolute buffer budget (MiB)",
    )
    p.add_argument(
        "--pedantic", action="store_true",
        help="UL001 also flags fp32 elementwise chains seeded by "
             "bf16->f32 converts (noisy: deliberate fp32 islands like "
             "LayerNorm stats and optimizer math match the pattern)",
    )
    p.add_argument("-q", "--quiet", action="store_true",
                   help="suppress progress logging")
    return p


def _provision_cpu_devices(n):
    """Force an n-device virtual CPU platform.  Must run before jax
    initializes a backend; the platform is pinned through the jax config
    as well as the environment (same recipe as tests/conftest.py)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")


def main(argv=None):
    args = build_parser().parse_args(argv)
    log = (lambda *a: None) if args.quiet else (
        lambda *a: print("unicore-lint:", *a, file=sys.stderr)
    )

    findings = []
    trace_reports = []
    pass3_report = None
    anchor = _anchor_dir()

    needs_jax = (
        (args.config and not args.no_trace) or args.pass3
        or args.pass3_serve or args.pass4 or args.pass4_serve
        or args.pass5 or args.pass5_serve
        or args.fused_head_audit
    )
    if needs_jax and args.cpu_devices:
        _provision_cpu_devices(args.cpu_devices)

    thresholds = {"pedantic": args.pedantic}
    if args.big_mib is not None:
        thresholds["big_bytes"] = args.big_mib << 20

    if args.config and not args.no_trace:
        from unicore_tpu.analysis.scenarios import audit_bert_config

        got, trace_reports = audit_bert_config(
            args.config, thresholds=thresholds, log=log,
            n_devices=args.cpu_devices or None,
        )
        findings.extend(got)
        for r in trace_reports:
            if "skipped" in r:
                log(f"variant {r['variant']}: SKIPPED ({r['skipped']})")

    fused_head_failed = False
    fused_head_report = None
    if args.fused_head_audit:
        if not args.config:
            print("unicore-lint: error: --fused-head-audit needs --config",
                  file=sys.stderr)
            return 2
        from unicore_tpu.analysis.scenarios import audit_fused_head_memory

        results = audit_fused_head_memory(
            args.config, log=log, n_devices=args.cpu_devices or None,
        )
        fused_head_report = []
        for name, per in sorted(results.items()):
            ok = not per["fused"] and bool(per["naive"])
            fused_head_failed = fused_head_failed or not ok
            fused_head_report.append({
                "variant": name, "rows": per["rows"],
                "budget_bytes": per["budget_bytes"], "ok": ok,
                "fused_findings": [f.message for f in per["fused"]],
                "naive_fires": len(per["naive"]),
            })
            print(
                f"fused-head audit bert/{name}: "
                f"{'PASS' if ok else 'FAIL'} (budget "
                f"{per['budget_bytes'] >> 10} KiB: fused "
                f"{len(per['fused'])} finding(s), materialized "
                f"{len(per['naive'])})"
            )

    pass4_report = None
    pass5_report = None
    budget_path = args.budget_file or os.path.join(
        anchor, os.path.join("tools", "comms_baseline.json")
    )
    if (args.pass3 or args.pass3_serve or args.pass4 or args.pass4_serve
            or args.pass5 or args.pass5_serve):
        from unicore_tpu.analysis import hlo_audit

        if args.pass3 or args.pass3_serve:
            pass3_report = {"budget_file": budget_path, "scenarios": []}
        if args.pass4 or args.pass4_serve:
            pass4_report = {"budget_file": budget_path, "scenarios": []}
        if args.pass5 or args.pass5_serve:
            pass5_report = {"scenarios": []}
        if args.pass3 or args.pass4 or args.pass5:
            if not args.config:
                print("unicore-lint: error: --pass3/--pass4/--pass5 "
                      "need --config", file=sys.stderr)
                return 2
            from unicore_tpu.analysis.scenarios import (
                audit_bert_config_pass3,
            )

            variants = (args.pass3_variants.split(",")
                        if args.pass3_variants else None)
            got, rep = audit_bert_config_pass3(
                args.config, variants=variants,
                n_devices=args.cpu_devices or None,
                budget_path=budget_path,
                update_budgets=args.update_budgets, log=log,
                pass3=args.pass3, schedule=args.pass4,
                determinism=args.pass5,
            )
            findings.extend(got)
            if args.pass3:
                pass3_report["fingerprint"] = rep["fingerprint"]
                pass3_report["scenarios"].extend(rep["scenarios"])
            if args.pass4:
                pass4_report["fingerprint"] = rep["fingerprint"]
                pass4_report["scenarios"].extend(
                    rep["schedule_scenarios"]
                )
            if args.pass5:
                pass5_report["scenarios"].extend(
                    rep["determinism_scenarios"]
                )
        if args.pass3_serve or args.pass4_serve or args.pass5_serve:
            from unicore_tpu.analysis.scenarios import audit_serve_demo

            got, rep = audit_serve_demo(
                budget_path=budget_path,
                update_budgets=args.update_budgets,
                thresholds=thresholds, log=log,
                pass3=args.pass3_serve, schedule=args.pass4_serve,
                determinism=args.pass5_serve,
            )
            findings.extend(got)
            if args.pass3_serve:
                pass3_report.setdefault("fingerprint",
                                        rep["fingerprint"])
                pass3_report["scenarios"].extend(rep["scenarios"])
            if args.pass4_serve:
                pass4_report.setdefault("fingerprint",
                                        rep["fingerprint"])
                pass4_report["scenarios"].extend(
                    rep["schedule_scenarios"]
                )
            if args.pass5_serve:
                pass5_report["scenarios"].extend(
                    rep["determinism_scenarios"]
                )
        if args.pass5 or args.pass5_serve:
            # UL403 runs once per invocation, not per scenario: the
            # planning modules are the same host code whichever device
            # programs they feed
            from unicore_tpu.analysis.determinism_audit import (
                audit_planning_modules,
            )

            got, planning = audit_planning_modules(anchor)
            findings.extend(got)
            pass5_report["planning"] = planning
            log(f"pass5: planning audit over "
                f"{len(planning['audited'])} module(s)")
        if (args.update_budgets and args.pass3 and args.pass3_serve
                and not args.pass3_variants
                and pass3_report.get("fingerprint")):
            # full measurement surface: scenarios absent from this run
            # no longer exist — drop their stale budget entries
            pruned = hlo_audit.prune_budget_entries(
                budget_path, pass3_report["fingerprint"],
                keep={s["scenario"] for s in pass3_report["scenarios"]
                      if "skipped" not in s},
            )
            for s in pruned:
                log(f"pass3: pruned stale budget entry {s}")
    if not args.no_lint:
        from unicore_tpu.analysis.source_lint import lint_paths

        roots = args.lint_root or [
            os.path.join(anchor, r) for r in DEFAULT_LINT_ROOTS
            if os.path.exists(os.path.join(anchor, r))
        ]
        if not roots:
            print(
                f"unicore-lint: error: no lint roots found under {anchor} "
                f"(pass --lint-root or run from the repo checkout)",
                file=sys.stderr,
            )
            return 2
        log("linting", ", ".join(roots))
        findings.extend(lint_paths(roots, rel_to=anchor))

    from unicore_tpu.analysis.findings import (
        load_baseline,
        render_report,
        report_json,
        split_baselined,
        stale_baseline_entries,
        write_baseline,
    )

    baseline_path = args.baseline or os.path.join(anchor, DEFAULT_BASELINE)
    if args.write_baseline:
        write_baseline(baseline_path, findings)
        print(f"unicore-lint: wrote {len(findings)} suppression(s) to "
              f"{baseline_path}")
        return 0

    fps = set() if args.no_baseline else load_baseline(baseline_path)
    new, suppressed = split_baselined(findings, fps)

    stale = []
    if args.check_baseline and not args.no_baseline:
        # only the rule families THIS invocation executed can prove an
        # entry stale: a lint-only run must not flag trace or pass-3
        # suppressions as rot (and vice versa) — otherwise accepting a
        # pass-3 finding into the baseline would deadlock against a CI
        # step that runs passes 1-2 only
        ran = set()
        if args.config and not args.no_trace:
            ran.add("UL0")
        if not args.no_lint:
            ran.add("UL1")
        if args.pass3 or args.pass3_serve:
            ran.add("UL2")
        if args.pass4 or args.pass4_serve:
            ran.add("UL3")
        if args.pass5 or args.pass5_serve:
            ran.add("UL4")
        stale = [
            e for e in stale_baseline_entries(baseline_path, findings)
            if str(e.get("rule", ""))[:3] in ran
        ]
        for e in stale:
            print(
                f"{baseline_path}: stale suppression {e['fingerprint']} "
                f"({e.get('rule', '?')} at {e.get('location', '?')}) — "
                f"the finding no longer fires; remove it or rerun "
                f"--write-baseline",
            )

    stale_budget = []
    if args.check_baseline and os.path.exists(budget_path):
        # the budget file rots the same way: a scenario renamed or
        # removed in scenarios.py leaves dead entries behind in every
        # fingerprint section — fail on them instead of letting a
        # reviewed file accumulate fiction
        from unicore_tpu.analysis.scenarios import stale_budget_scenarios

        stale_budget = stale_budget_scenarios(budget_path)
        for fp_key, scenario in stale_budget:
            print(
                f"{budget_path}: stale budget scenario '{scenario}' "
                f"(fingerprint {fp_key}) — no such scenario exists in "
                f"scenarios.py; remove the entry or restore the "
                f"scenario",
            )

    extra = {"trace": trace_reports}
    if pass3_report is not None:
        extra["pass3"] = pass3_report
    if pass4_report is not None:
        extra["pass4"] = pass4_report
    if pass5_report is not None:
        extra["pass5"] = pass5_report
    if fused_head_report is not None:
        extra["fused_head_audit"] = fused_head_report
    if stale:
        extra["stale_baseline"] = stale
    if stale_budget:
        extra["stale_budget_scenarios"] = [
            {"fingerprint": fp_key, "scenario": s}
            for fp_key, s in stale_budget
        ]
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(report_json(new, suppressed, extra=extra),
                      fh, indent=2)
            fh.write("\n")
    print(render_report(new, suppressed))
    if stale:
        print(f"unicore-lint: {len(stale)} stale baseline "
              f"suppression(s) (baseline rot)")
    if stale_budget:
        print(f"unicore-lint: {len(stale_budget)} stale budget "
              f"scenario entr(ies) (budget rot)")
    if fused_head_failed:
        print("unicore-lint: fused-head memory audit FAILED")
    return 1 if (new or stale or stale_budget or fused_head_failed) \
        else 0


if __name__ == "__main__":
    sys.exit(main())
