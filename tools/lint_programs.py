#!/usr/bin/env python
"""Static-lint the paddle_tpu program corpus against the committed baseline.

CPU-only and trace-only (``jax.make_jaxpr`` — nothing executes), so this
runs on any CI host in well under a minute. The corpus covers the real
entry points: the sharded train step (with and without gradient-reduction
collectives), serving prefill/decode, the GradReducer shard_map schedule,
a resharding executor body, and an ir-pipeline-optimized program.

Two tiers share one exit status:

- tier 1 (always): trace-level rules against the suppression baseline,
  plus a stale-suppression check — a suppression whose finding is gone
  FAILS the gate until pruned (``--update-baseline`` prunes).
- tier 2 (``--hlo``): compile every corpus entry with its declared
  ShardingContract, parse the partitioned HLO for actual collectives and
  the executable memory peak, and diff against the committed
  ``tools/hlo_baseline.json`` — any collective-count / wire-byte / HBM-peak
  drift fails, naming the op, dtype, and site.

Exit codes:
  0  clean (no gating findings / HLO drift beyond the committed baselines)
  1  NEW gating findings, stale suppressions, or HLO baseline diffs
  2  internal failure (corpus build or analysis crashed)

Usage:
  python tools/lint_programs.py                    # the tier-1 CI gate
  python tools/lint_programs.py --hlo              # + the HLO audit tier
  python tools/lint_programs.py --hlo --json       # machine-readable report
  python tools/lint_programs.py --selftest         # fixture rules must fire
  python tools/lint_programs.py --inject dtype-f64 # prove tier 1 trips
  python tools/lint_programs.py --hlo --inject-hlo grad_reducer
                                                   # prove tier 2 trips
  python tools/lint_programs.py --update-baseline --reason "why"
  python tools/lint_programs.py --hlo --update-hlo-baseline --reason "why"

See paddle_tpu/analysis/README.md for the rule catalog and the
suppression/baseline workflow.
"""

import argparse
import json
import os
import sys
import time

# trace-only CPU setup must precede any jax import; force (not default) the
# platform — this no-execution lint must never take an accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)  # match the test environment

from paddle_tpu import analysis  # noqa: E402


def _selftest(verbose: bool) -> int:
    """Every required fixture must fire exactly its seeded rule."""
    failures = []
    for spec, expected_rule in analysis.fixture_specs():
        report = analysis.analyze_spec(spec)
        hit = sorted(report.rules_hit())
        status = "ok" if expected_rule in hit else "MISSING"
        if verbose or status != "ok":
            print(f"  fixture {spec.name}: expected {expected_rule}, "
                  f"got {hit} [{status}]")
        if expected_rule not in hit:
            failures.append(spec.name)
    required = set(analysis.REQUIRED_FIXTURE_RULES)
    covered = {rule for _, rule in analysis.fixture_specs()}
    missing_rules = required - covered
    if missing_rules:
        print(f"selftest: required rules with no fixture: {sorted(missing_rules)}")
        return 1
    if failures:
        print(f"selftest FAILED: {failures}")
        return 1
    print(f"selftest ok: {len(analysis.fixture_specs())} fixtures, "
          f"{len(required)} required rules covered")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", default=analysis.default_baseline_path())
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the full report as JSON on stdout")
    ap.add_argument("--update-baseline", action="store_true",
                    help="suppress all currently-new findings (needs --reason)")
    ap.add_argument("--reason", default="",
                    help="rationale recorded with --update-baseline")
    ap.add_argument("--selftest", action="store_true",
                    help="check every seeded fixture violation is detected")
    ap.add_argument("--inject", metavar="RULE",
                    help="add the fixture for RULE to the corpus (gate demo)")
    ap.add_argument("--hlo", action="store_true",
                    help="also run the post-partition HLO audit tier")
    ap.add_argument("--hlo-baseline",
                    default=analysis.default_hlo_baseline_path())
    ap.add_argument("--update-hlo-baseline", action="store_true",
                    help="re-record tools/hlo_baseline.json (needs --reason)")
    ap.add_argument("--inject-hlo", metavar="SITE",
                    help="force SITE's first sharded arg replicated before "
                         "the audit (HLO gate demo)")
    ap.add_argument("--verbose", "-v", action="store_true")
    ns = ap.parse_args(argv)

    if ns.selftest:
        return _selftest(ns.verbose)
    if ns.update_baseline and not ns.reason:
        ap.error("--update-baseline requires --reason")
    if ns.update_hlo_baseline and not ns.reason:
        ap.error("--update-hlo-baseline requires --reason")
    run_hlo = ns.hlo or ns.update_hlo_baseline or bool(ns.inject_hlo)

    t0 = time.monotonic()
    try:
        corpus_specs, skips = analysis.build_corpus()
        specs = list(corpus_specs)
        if ns.inject:
            injected = [s for s, rule in analysis.fixture_specs()
                        if rule == ns.inject]
            if not injected:
                ap.error(f"--inject: no fixture for rule '{ns.inject}'; "
                         f"have {sorted({r for _, r in analysis.fixture_specs()})}")
            specs = specs + injected
        build_s = time.monotonic() - t0
        report, errors = analysis.analyze_corpus(specs)
    except Exception as e:  # corpus construction itself broke
        print(f"lint_programs: internal failure: {e!r}", file=sys.stderr)
        return 2
    analyze_s = time.monotonic() - t0 - build_s

    # ---- tier 2: compile the real corpus (never the injected fixtures)
    # and audit the partitioned HLO against tools/hlo_baseline.json
    audits, hlo_diffs, audit_s = [], [], 0.0
    if run_hlo:
        t1 = time.monotonic()
        try:
            audit_specs = list(corpus_specs)
            if ns.inject_hlo:
                by_name = {s.name: i for i, s in enumerate(audit_specs)}
                if ns.inject_hlo not in by_name:
                    ap.error(f"--inject-hlo: no corpus site "
                             f"'{ns.inject_hlo}'; have {sorted(by_name)}")
                i = by_name[ns.inject_hlo]
                audit_specs[i] = analysis.inject_replicated_arg(
                    audit_specs[i])
            audits = analysis.audit_corpus(audit_specs)
        except Exception as e:
            print(f"lint_programs: hlo audit failure: {e!r}",
                  file=sys.stderr)
            return 2
        audit_s = time.monotonic() - t1
        hlo_baseline = analysis.load_hlo_baseline(ns.hlo_baseline)
        hlo_diffs = analysis.diff_against_baseline(audits, hlo_baseline)
        report.findings.extend(analysis.unexplained_findings(audits))

    baseline = analysis.load_baseline(ns.baseline)
    suppressed = set(analysis.baseline_fingerprints(baseline))
    new = report.new_against(suppressed)
    stale = sorted(suppressed - {f.fingerprint for f in report.findings})

    if ns.as_json:
        payload = {
            "programs": [s.name for s in specs],
            "skipped": [{"name": n, "reason": r} for n, r in skips],
            "build_seconds": round(build_s, 3),
            "analyze_seconds": round(analyze_s, 3),
            "counts": report.counts(),
            "findings": [f.as_dict() for f in report.findings],
            "new_gating": [f.as_dict() for f in new],
            "stale_suppressions": stale,
        }
        if run_hlo:
            payload["hlo"] = {
                "audit_seconds": round(audit_s, 3),
                "sites": [a.as_dict() for a in audits],
                "diffs": [d.render() for d in hlo_diffs],
            }
        print(json.dumps(payload, indent=2))
    else:
        print(f"lint_programs: {len(specs)} program(s) "
              f"(build {build_s:.1f}s, analyze {analyze_s:.1f}s"
              + (f", hlo audit {audit_s:.1f}s" if run_hlo else "") + ")"
              + (f"; skipped: {[n for n, _ in skips]}" if skips else ""))
        if ns.verbose or report.findings:
            print(report.render())
        if run_hlo and ns.verbose:
            for a in audits:
                print(f"  hlo {a.site}: {a.counts} "
                      f"wire={a.wire_bytes} "
                      f"peak={a.hbm.get('peak', 0)} "
                      f"err={a.error}")

    if ns.update_baseline:
        added = analysis.add_suppressions(baseline, new, ns.reason)
        pruned = analysis.prune_stale(
            baseline, [f.fingerprint for f in report.findings])
        analysis.save_baseline(baseline, ns.baseline)
        print(f"baseline updated: {added} suppression(s) added, "
              f"{pruned} stale pruned -> {ns.baseline}")
        new, stale = [], []

    if ns.update_hlo_baseline:
        hlo_baseline = analysis.audits_to_baseline(
            audits, ns.reason, analysis.load_hlo_baseline(ns.hlo_baseline))
        analysis.save_hlo_baseline(hlo_baseline, ns.hlo_baseline)
        print(f"hlo baseline updated: {len(hlo_baseline['sites'])} "
              f"site(s) -> {ns.hlo_baseline}")
        hlo_diffs = []

    failed = False
    if new:
        failed = True
    if stale:
        failed = True
    if hlo_diffs:
        failed = True
    if ns.as_json:  # machine output: the payload already carries the diffs
        return 1 if failed else 0
    if new:
        print(f"\nFAIL: {len(new)} new gating finding(s) not in baseline "
              f"({ns.baseline}):")
        for f in new:
            print("  " + f.render())
        print("\nfix the hazard, or suppress with a rationale:\n"
              "  python tools/lint_programs.py --update-baseline --reason '...'")
    if stale:
        print(f"\nFAIL: {len(stale)} stale suppression(s) in baseline "
              f"({ns.baseline}) — the suppressed finding no longer fires. "
              "Prune them so the baseline stays honest:\n"
              "  python tools/lint_programs.py --update-baseline "
              "--reason 'prune fixed findings'")
        for fp in stale:
            print(f"  stale fingerprint: {fp}")
    if hlo_diffs:
        print(f"\nFAIL: partitioned HLO drifted from {ns.hlo_baseline} "
              f"({len(hlo_diffs)} diff(s)):")
        for d in hlo_diffs:
            print("  " + d.render())
        print("\nfix the sharding regression, or re-record with:\n"
              "  python tools/lint_programs.py --hlo --update-hlo-baseline "
              "--reason '...'")
    if failed:
        return 1
    print("lint_programs: clean" + (" (hlo audited)" if run_hlo else ""))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
