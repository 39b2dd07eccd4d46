#!/usr/bin/env python
"""Perf attribution report + regression gate against tools/perf_baseline.json.

Renders the roofline attribution report (per bench config and per
analysis-corpus site: predicted step-time floors per resource, the
binding resource, predicted-vs-measured gap) from COMMITTED data — the
perf baseline's cost numbers and the HLO audit's wire bytes — and diffs
fresh bench rows against the committed baseline with noise-aware
tolerances. Same ledger pattern as ``tools/analysis_baseline.json`` /
``tools/hlo_baseline.json``: the baseline is the reviewed truth, drift
fails CI with a named cause, ``--update-baseline --reason`` re-records.

Runs standalone — no jax, no xprof — via the same synthetic-package
import as ``telemetry_report.py`` (``observability/attribution.py`` and
``aggregate.py`` are stdlib-only by contract). Only ``--refresh-sites``
(re-harvesting corpus cost_analysis numbers) imports jax.

Exit codes (the lint_programs convention):
  0  clean (attribution reconciles, no row regressed beyond tolerance)
  1  regression / reconciliation failure
  2  internal failure (unreadable baseline, bad rows file)

Usage:
  python tools/perf_report.py                        # text report
  python tools/perf_report.py --json                 # machine-readable
  python tools/perf_report.py --check rows.jsonl     # gate bench rows
  python tools/perf_report.py --check --inject gpt_dp  # prove the gate trips
  python tools/perf_report.py --metrics run/metrics-host*.jsonl   # measured
  python tools/perf_report.py --check rows.jsonl --update-baseline \
      --reason "why"                                 # re-record config rows
  python tools/perf_report.py --refresh-sites --reason "why"  # needs jax
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
import types

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_OBS_DIR = os.path.join(_REPO, "paddle_tpu", "observability")
_pkg = types.ModuleType("_ptobs")
_pkg.__path__ = [_OBS_DIR]
sys.modules.setdefault("_ptobs", _pkg)
attribution = importlib.import_module("_ptobs.attribution")
aggregate = importlib.import_module("_ptobs.aggregate")

SCHEMA = "paddle_tpu.perf_baseline.v1"


def default_baseline_path() -> str:
    return os.path.join(_REPO, "tools", "perf_baseline.json")


def default_hlo_baseline_path() -> str:
    return os.path.join(_REPO, "tools", "hlo_baseline.json")


def load_baseline(path: str) -> dict:
    with open(path) as f:
        b = json.load(f)
    if b.get("schema") != SCHEMA:
        raise ValueError(f"{path}: not a {SCHEMA} file")
    return b


def save_baseline(baseline: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(baseline, f, indent=2, sort_keys=True)
        f.write("\n")


def load_rows(paths) -> list:
    """Bench rows from files of JSON lines (bench.py prints one row per
    config; non-row lines are skipped)."""
    rows = []
    for p in paths:
        with open(p) as f:
            for line in f:
                line = line.strip()
                if not line or not line.startswith("{"):
                    continue
                try:
                    obj = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(obj, dict) and "config" in obj:
                    rows.append(obj)
    return rows


# --------------------------------------------------------------- report

def build_report(baseline: dict, hlo_baseline: dict,
                 metrics_paths=None) -> dict:
    """The attribution report from committed data (+ optional measured
    telemetry dumps): per-config and per-site roofline rows plus the
    cross-ledger reconciliation against the HLO audit."""
    backend = baseline.get("backend", "tpu")
    device_kind = baseline["device_kind"]  # the peaks row the floors use
    config_sites = {}
    for name, row in baseline.get("configs", {}).items():
        config_sites[name] = {
            "flops": row.get("flops_per_step"),
            "hbm_bytes": row.get("hbm_bytes_per_step"),
            "wire_bytes": row.get("wire_bytes_per_step"),
            "measured_s": (row["step_ms"] / 1e3
                           if row.get("step_ms") else None),
        }
    configs_report = attribution.site_report(config_sites, device_kind)

    measured = None
    if metrics_paths:
        fleet = aggregate.fleet_report(list(metrics_paths))
        step_s = attribution.measured_step_seconds(fleet)
        if step_s is not None:
            measured = {"train_step": step_s,
                        "train_step_grad_reduce": step_s}
    site_costs = {}
    for name, row in baseline.get("sites", {}).items():
        site_costs[name] = {
            "flops": row.get("flops"),
            "hbm_bytes": row.get("hbm_bytes"),
            "wire_bytes": row.get("wire_bytes"),
        }
    sites_report = attribution.site_report(site_costs, device_kind,
                                           measured=measured)
    mismatches = attribution.reconcile_sites(
        baseline.get("sites", {}), hlo_baseline.get("sites", {}))
    return {
        "schema": attribution.SCHEMA,
        "backend": backend,
        "device_kind": device_kind,
        "hardware": configs_report["hardware"],
        "configs": configs_report["sites"],
        "sites": sites_report["sites"],
        "reconciliation": {"ok": not mismatches, "mismatches": mismatches,
                           "against": "tools/hlo_baseline.json"},
    }


# ----------------------------------------------------------------- gate

def _higher_is_better(base_row: dict) -> bool:
    if "higher_is_better" in base_row:
        return bool(base_row["higher_is_better"])
    return not str(base_row.get("metric", "")).endswith("_ms")


def diff_rows(rows: list, baseline: dict) -> dict:
    """Diff bench rows against the committed config rows. Rows whose
    backend does not match the baseline's are SKIPPED, not compared — a
    CPU CI run must never be judged against TPU numbers (that is what the
    per-backend tolerance would otherwise have to absorb)."""
    backend = baseline.get("backend", "tpu")
    regressions, improvements, checked, skipped = [], [], [], []
    configs = baseline.get("configs", {})
    for row in rows:
        name = row.get("config")
        base = configs.get(name)
        if base is None:
            skipped.append({"config": name, "reason": "not in baseline"})
            continue
        row_backend = row.get("backend", "unknown")
        if row_backend != backend:
            skipped.append({"config": name,
                            "reason": f"backend {row_backend} != baseline "
                                      f"{backend}"})
            continue
        tol = float(base.get("tolerance",
                             baseline.get("tolerances", {})
                             .get("default", 0.10)))
        value = row.get("value")
        bval = base.get("value")
        if value is None or not bval:
            skipped.append({"config": name, "reason": "no value"})
            continue
        rel = (float(value) - float(bval)) / float(bval)
        worse = -rel if _higher_is_better(base) else rel
        entry = {"config": name, "metric": base.get("metric"),
                 "baseline": bval, "actual": value,
                 "rel_change": round(rel, 4), "tolerance": tol}
        checked.append(entry)
        if worse > tol:
            regressions.append(entry)
        elif -worse > tol:
            improvements.append(entry)
    return {"checked": checked, "regressions": regressions,
            "improvements": improvements, "skipped": skipped}


def inject_row(baseline: dict, config: str) -> dict:
    """A synthetic row for ``config`` regressed 2.5x past its tolerance —
    proof the gate trips, independent of any machine's noise."""
    base = baseline.get("configs", {}).get(config)
    if base is None:
        raise KeyError(f"--inject: no baseline config {config!r}; have "
                       f"{sorted(baseline.get('configs', {}))}")
    tol = float(base.get("tolerance",
                         baseline.get("tolerances", {}).get("default", 0.10)))
    factor = 2.5 * tol
    value = float(base["value"])
    value *= (1 - factor) if _higher_is_better(base) else (1 + factor)
    return {"config": config, "metric": base.get("metric"),
            "value": round(value, 1), "backend": baseline.get("backend"),
            "note": "synthetic --inject regression"}


# ---------------------------------------------------------------- render

def render_text(report: dict, diff: dict | None) -> str:
    lines = [attribution.render({"device_kind": report["device_kind"],
                                 "hardware": report["hardware"],
                                 "sites": report["configs"]}),
             "",
             "corpus sites (cost_analysis + hlo_baseline wire bytes):",
             attribution.render({"device_kind": report["device_kind"],
                                 "hardware": report["hardware"],
                                 "sites": report["sites"]})]
    rec = report["reconciliation"]
    if rec["ok"]:
        lines.append(f"\nreconciliation vs {rec['against']}: ok")
    else:
        lines.append(f"\nreconciliation vs {rec['against']} FAILED:")
        lines += ["  " + m for m in rec["mismatches"]]
    if diff is not None:
        lines.append(f"\nrow check: {len(diff['checked'])} compared, "
                     f"{len(diff['skipped'])} skipped, "
                     f"{len(diff['regressions'])} regression(s), "
                     f"{len(diff['improvements'])} improvement(s)")
        for s in diff["skipped"]:
            lines.append(f"  skip {s['config']}: {s['reason']}")
        for r in diff["regressions"]:
            lines.append(f"  REGRESSION {r['config']} {r['metric']}: "
                         f"{r['baseline']} -> {r['actual']} "
                         f"({r['rel_change']:+.1%}, tol {r['tolerance']:.0%})")
        for r in diff["improvements"]:
            lines.append(f"  improved {r['config']} {r['metric']}: "
                         f"{r['baseline']} -> {r['actual']} "
                         f"({r['rel_change']:+.1%}) — consider "
                         "--update-baseline")
    return "\n".join(lines)


# ------------------------------------------------------------- recording

def update_config_rows(baseline: dict, rows: list, reason: str) -> int:
    """Fold matching-backend rows into the baseline's config section."""
    backend = baseline.get("backend", "tpu")
    updated = 0
    for row in rows:
        name = row.get("config")
        if name not in baseline.get("configs", {}):
            continue
        row_backend = row.get("backend", "unknown")
        if row_backend != backend:
            continue
        base = baseline["configs"][name]
        base["value"] = row.get("value", base.get("value"))
        if row.get("step_ms") is not None:
            base["step_ms"] = row["step_ms"]
        if row.get("mfu") is not None:
            base["mfu"] = row["mfu"]
        attr = row.get("attribution", {})
        inputs = attr.get("inputs", {})
        for src, dst in (("flops", "flops_per_step"),
                         ("hbm_bytes", "hbm_bytes_per_step"),
                         ("wire_bytes", "wire_bytes_per_step")):
            if inputs.get(src) is not None:
                base[dst] = inputs[src]
        updated += 1
    if updated:
        baseline.setdefault("history", []).append(
            {"date": time.strftime("%Y-%m-%d"), "reason": reason,
             "updated_configs": updated})
    return updated


def refresh_sites(baseline: dict, reason: str) -> int:
    """Re-harvest the corpus sites' cost numbers (cost_analysis FLOPs /
    bytes accessed, audited wire bytes and HBM peak). The ONLY path in
    this tool that imports jax — it compiles the corpus exactly like
    ``lint_programs.py --hlo``."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if "xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()
    sys.path.insert(0, _REPO)
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)
    from paddle_tpu import analysis

    specs, _skips = analysis.build_corpus()
    audits = analysis.audit_corpus(specs)
    sites = {}
    for a in audits:
        if a.error is not None:
            continue
        sites[a.site] = {
            "flops": a.cost.get("flops", 0.0),
            "hbm_bytes": a.cost.get("bytes_accessed", 0.0),
            "wire_bytes": int(a.wire_bytes),
            "hbm_peak_bytes": int(a.hbm.get("peak", 0)),
        }
    baseline["sites"] = sites
    baseline.setdefault("history", []).append(
        {"date": time.strftime("%Y-%m-%d"), "reason": reason,
         "refreshed_sites": sorted(sites)})
    return len(sites)


# ------------------------------------------------------------------ main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("rows", nargs="*",
                    help="bench row files (JSON lines) for --check/"
                         "--update-baseline")
    ap.add_argument("--baseline", default=default_baseline_path())
    ap.add_argument("--hlo-baseline", default=default_hlo_baseline_path())
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the full report as JSON on stdout")
    ap.add_argument("--check", action="store_true",
                    help="gate: diff row files against the baseline, "
                         "exit 1 on regression")
    ap.add_argument("--inject", metavar="CONFIG",
                    help="add a synthetic regressed row for CONFIG "
                         "(gate demo; implies --check)")
    ap.add_argument("--metrics", nargs="*", default=[],
                    help="per-host metrics-host*.jsonl dumps: the "
                         "portable measured-time source for site rows")
    ap.add_argument("--update-baseline", action="store_true",
                    help="re-record config rows from the row files "
                         "(needs --reason)")
    ap.add_argument("--refresh-sites", action="store_true",
                    help="re-harvest corpus site costs — imports jax "
                         "(needs --reason)")
    ap.add_argument("--reason", default="",
                    help="rationale recorded with --update-baseline / "
                         "--refresh-sites")
    ns = ap.parse_args(argv)
    if ns.update_baseline and not ns.reason:
        ap.error("--update-baseline requires --reason")
    if ns.refresh_sites and not ns.reason:
        ap.error("--refresh-sites requires --reason")

    try:
        baseline = load_baseline(ns.baseline)
    except Exception as e:
        print(f"perf_report: cannot load {ns.baseline}: {e!r}",
              file=sys.stderr)
        return 2
    try:
        with open(ns.hlo_baseline) as f:
            hlo_baseline = json.load(f)
    except Exception as e:
        print(f"perf_report: cannot load {ns.hlo_baseline}: {e!r}",
              file=sys.stderr)
        return 2

    if ns.refresh_sites:
        n = refresh_sites(baseline, ns.reason)
        save_baseline(baseline, ns.baseline)
        print(f"perf baseline: {n} site(s) refreshed -> {ns.baseline}")

    try:
        rows = load_rows(ns.rows)
    except Exception as e:
        print(f"perf_report: cannot read rows: {e!r}", file=sys.stderr)
        return 2
    try:
        if ns.inject:
            rows.append(inject_row(baseline, ns.inject))
    except KeyError as e:
        print(f"perf_report: {e.args[0]}", file=sys.stderr)
        return 2

    if ns.update_baseline:
        n = update_config_rows(baseline, rows, ns.reason)
        save_baseline(baseline, ns.baseline)
        print(f"perf baseline: {n} config row(s) updated -> {ns.baseline}")
        rows = []

    report = build_report(baseline, hlo_baseline,
                          metrics_paths=ns.metrics or None)
    run_check = ns.check or bool(ns.inject) or bool(rows)
    diff = diff_rows(rows, baseline) if run_check else None

    failed = not report["reconciliation"]["ok"]
    if diff is not None and diff["regressions"]:
        failed = True

    if ns.as_json:
        payload = dict(report)
        if diff is not None:
            payload["check"] = diff
        payload["failed"] = failed
        print(json.dumps(payload, indent=2, sort_keys=True))
    else:
        print(render_text(report, diff))
        print("\nperf_report: " + ("FAIL" if failed else "clean"))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
