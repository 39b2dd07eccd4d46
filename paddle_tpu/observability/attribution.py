"""Roofline attribution: predicted step-time floors vs measured device time.

Per site (a bench config or an analysis-corpus entry point) this model
combines the three cost numbers the earlier tiers already produce —

    flops       <- ``compiled.cost_analysis()``            (compute)
    hbm_bytes   <- cost_analysis bytes accessed / the train-traffic
                   estimator below                          (HBM)
    wire_bytes  <- the HLO audit's exact per-collective
                   receive-side accounting
                   (``tools/hlo_baseline.json``)            (ICI)

— into a predicted time floor per resource (``t_r = work_r / peak_r``),
names the **binding resource** (the largest floor: the roofline wall the
site is up against), and reconciles the floor against measured time: the
XPlane op table on device (``observability/xplane.py``) or, portably, the
``train.step.seconds`` histogram / goodput buckets from a metrics dump.
``gap = measured / floor`` reads directly: 1.0 is the roofline, 2.0 means
half the step is not explained by the binding resource and is worth
hunting (dispatch, stalls, non-overlapped transfers).

Stdlib-only BY CONTRACT, like ``aggregate.py``: ``tools/perf_report.py``
imports this module through the synthetic-package trick with no jax
installed, so hardware peaks are mirrored constants and metric recording
goes through a lazily imported, failure-tolerant hook.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from typing import Any, Dict, List, Mapping, Optional

SCHEMA = "paddle_tpu.attribution.v1"

#: resource order also used for deterministic binding tie-breaks
RESOURCES = ("compute", "hbm", "ici")

#: default reconciliation tolerances — mirrors analysis/hlo_audit.py
#: (WIRE_TOLERANCE / HBM_TOLERANCE); a test pins the pairs equal
WIRE_TOLERANCE = 0.10
HBM_TOLERANCE = 0.05


@dataclass(frozen=True)
class HardwareSpec:
    """Per-chip peaks the floors divide by."""

    name: str
    peak_flops: float          # FLOP/s (bf16 MXU peak on TPU)
    hbm_bytes_per_s: float     # HBM bandwidth
    ici_bytes_per_s: float     # per-chip interconnect bandwidth

    def as_dict(self) -> Dict[str, Any]:
        return {"name": self.name, "peak_flops": self.peak_flops,
                "hbm_bytes_per_s": self.hbm_bytes_per_s,
                "ici_bytes_per_s": self.ici_bytes_per_s}


#: Per-chip peaks keyed by jax ``device_kind``
#: (``jax.devices()[0].device_kind``), each with its source. A device that
#: is not in the table is an ERROR, never a default: a utilization quoted
#: against a made-up peak is worse than none.
#: - "TPU v5 lite": Google Cloud documentation, "TPU v5e" — 197 TFLOP/s
#:   bf16, 819 GB/s HBM, 1,600 Gbit/s (200 GB/s) chip-to-chip interconnect.
#: - "cpu": a NOMINAL scale so tiny CPU test runs produce well-formed
#:   (clearly-labeled) reports, not a claim about the host.
HW_SPECS: Dict[str, HardwareSpec] = {
    "TPU v5 lite": HardwareSpec("tpu-v5e", 197e12, 819e9, 200e9),
    "cpu": HardwareSpec("cpu-nominal", 1e12, 50e9, 10e9),
}


def hardware_for_device(device_kind: str) -> HardwareSpec:
    """HardwareSpec for a jax ``device_kind``; unknown devices raise."""
    try:
        return HW_SPECS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks recorded for device_kind {device_kind!r}; add a row "
            f"with its source to attribution.HW_SPECS (known: "
            f"{sorted(HW_SPECS)})") from None


def floors(hw: HardwareSpec, flops: Optional[float] = None,
           hbm_bytes: Optional[float] = None,
           wire_bytes: Optional[float] = None) -> Dict[str, float]:
    """Per-resource time floors in seconds; resources with no cost number
    (None) are omitted rather than reported as a fake zero floor."""
    out: Dict[str, float] = {}
    if flops is not None and flops > 0:
        out["compute"] = float(flops) / hw.peak_flops
    if hbm_bytes is not None and hbm_bytes > 0:
        out["hbm"] = float(hbm_bytes) / hw.hbm_bytes_per_s
    if wire_bytes is not None and wire_bytes > 0:
        out["ici"] = float(wire_bytes) / hw.ici_bytes_per_s
    return out


def attribute(hw: HardwareSpec, measured_s: Optional[float] = None,
              flops: Optional[float] = None,
              hbm_bytes: Optional[float] = None,
              wire_bytes: Optional[float] = None) -> Dict[str, Any]:
    """One site's attribution row: floors, binding resource, and the
    predicted-vs-measured gap (``measured / max(floor)``; None when either
    side is missing)."""
    fl = floors(hw, flops=flops, hbm_bytes=hbm_bytes, wire_bytes=wire_bytes)
    binding = None
    floor_s = 0.0
    for r in RESOURCES:  # deterministic tie-break in RESOURCES order
        if r in fl and fl[r] > floor_s:
            binding, floor_s = r, fl[r]
    gap = None
    bound_frac = None
    if measured_s is not None and measured_s > 0 and floor_s > 0:
        gap = measured_s / floor_s
        bound_frac = min(1.0, floor_s / measured_s)
    return {
        "floors_ms": {r: round(s * 1e3, 4) for r, s in fl.items()},
        "binding": binding,
        "floor_ms": round(floor_s * 1e3, 4),
        "measured_ms": (round(measured_s * 1e3, 4)
                        if measured_s is not None else None),
        "gap": round(gap, 3) if gap is not None else None,
        "bound_fraction": (round(bound_frac, 3)
                           if bound_frac is not None else None),
        "inputs": {"flops": flops, "hbm_bytes": hbm_bytes,
                   "wire_bytes": wire_bytes},
    }


def train_hbm_bytes_estimate(n_params: int, param_bytes: int = 2,
                             grad_bytes: Optional[int] = None,
                             master: bool = True,
                             moment_bytes: int = 2) -> int:
    """Analytic LOWER BOUND on one optimizer step's HBM traffic from the
    parameter/optimizer working set alone (activations and remat reads are
    deliberately excluded — they depend on batch/remat policy, and a floor
    must not overclaim): params read fwd+bwd, grads written, fp32 master
    read+written when ``master``, two Adam moments read+written, updated
    params written back."""
    n = int(n_params)
    g = param_bytes if grad_bytes is None else grad_bytes
    per_param = (2 * param_bytes          # fwd + bwd param reads
                 + g                      # grad write
                 + (8 if master else 0)   # fp32 master read + write
                 + 4 * moment_bytes       # 2 moments, read + write
                 + param_bytes)           # updated param write
    return n * per_param


def site_report(sites: Mapping[str, Mapping[str, Any]],
                device_kind: str,
                measured: Optional[Mapping[str, float]] = None
                ) -> Dict[str, Any]:
    """Build the AttributionReport for {site: {"flops", "hbm_bytes",
    "wire_bytes", optional "measured_s"}} on one ``device_kind``.
    ``measured`` (site -> seconds) overrides/supplies measured time — the
    XPlane/goodput reconciliation feed."""
    hw = hardware_for_device(device_kind)
    rows: Dict[str, Any] = {}
    for name in sorted(sites):
        c = sites[name]
        m = c.get("measured_s")
        if measured is not None and name in measured:
            m = measured[name]
        rows[name] = attribute(
            hw, measured_s=m, flops=c.get("flops"),
            hbm_bytes=c.get("hbm_bytes"), wire_bytes=c.get("wire_bytes"))
    return {"schema": SCHEMA, "device_kind": device_kind,
            "hardware": hw.as_dict(), "sites": rows}


def reconcile_sites(perf_sites: Mapping[str, Mapping[str, Any]],
                    hlo_sites: Mapping[str, Mapping[str, Any]],
                    wire_tol: float = WIRE_TOLERANCE,
                    hbm_tol: float = HBM_TOLERANCE) -> List[str]:
    """Cross-check the attribution ledger against the HLO audit ledger
    (``tools/hlo_baseline.json``): every perf site that names wire bytes /
    an HBM peak must agree with the audited truth within tolerance, and
    its FLOPs must be present and positive. Returns human-readable
    mismatch strings; empty means reconciled."""

    def _off(base: float, actual: float, tol: float) -> bool:
        if base == 0:
            return actual != 0
        return abs(actual - base) / base > tol

    problems: List[str] = []
    for name in sorted(perf_sites):
        ps = perf_sites[name]
        hs = hlo_sites.get(name)
        if hs is None:
            problems.append(f"{name}: not in hlo baseline")
            continue
        flops = ps.get("flops")
        if flops is None or (flops <= 0 and not ps.get("hbm_bytes")):
            # zero flops with nonzero bytes-accessed is a real profile (a
            # pure data-movement program, e.g. reshard); zero BOTH means
            # cost_analysis never ran for the site
            problems.append(f"{name}: no cost_analysis flops recorded")
        pw, hw_ = ps.get("wire_bytes"), hs.get("wire_bytes", 0)
        if pw is not None and _off(float(hw_), float(pw), wire_tol):
            problems.append(
                f"{name}: wire_bytes {pw} vs hlo baseline {hw_} "
                f"(> {wire_tol:.0%})")
        pp, hp = ps.get("hbm_peak_bytes"), hs.get("hbm_peak_bytes", 0)
        if pp is not None and _off(float(hp), float(pp), hbm_tol):
            problems.append(
                f"{name}: hbm_peak_bytes {pp} vs hlo baseline {hp} "
                f"(> {hbm_tol:.0%})")
    return problems


def measured_step_seconds(source: Mapping[str, Any]) -> Optional[float]:
    """Portable measured step time from telemetry: the mean of the
    ``train.step.seconds`` histogram when present, else total goodput
    bucket seconds / ``train.steps``. Accepts either a registry snapshot
    (``metrics.snapshot()``) or an ``aggregate.fleet_report`` result."""
    hists = source.get("histograms", {})
    h = hists.get("train.step.seconds")
    if h and h.get("count"):
        return float(h["sum"]) / float(h["count"])
    counters = source.get("counters", {})

    def _val(key: str) -> float:
        v = counters.get(key, 0)
        if isinstance(v, Mapping):  # fleet_report counters: {"total": ...}
            v = v.get("total", 0)
        return float(v or 0)

    goodput = sum(_val(k) for k in counters
                  if k.startswith("train.goodput.seconds"))
    steps = _val("train.steps")
    if goodput > 0 and steps > 0:
        return goodput / steps
    return None


def render(report: Mapping[str, Any]) -> str:
    """Text table of an attribution report."""
    hw = report.get("hardware", {})
    lines = [f"attribution ({report.get('device_kind')}, {hw.get('name')}: "
             f"{hw.get('peak_flops', 0) / 1e12:.0f} TF/s, "
             f"{hw.get('hbm_bytes_per_s', 0) / 1e9:.0f} GB/s HBM, "
             f"{hw.get('ici_bytes_per_s', 0) / 1e9:.0f} GB/s ICI)", "",
             f"{'site':<28}{'binding':>9}{'floor ms':>12}"
             f"{'measured ms':>13}{'gap':>8}  floors"]
    lines.append("-" * 96)
    for name, row in sorted(report.get("sites", {}).items()):
        fl = " ".join(f"{r}={v:g}" for r, v in row["floors_ms"].items())
        lines.append(
            f"{name[:27]:<28}{str(row['binding']):>9}"
            f"{row['floor_ms']:>12g}"
            f"{('-' if row['measured_ms'] is None else format(row['measured_ms'], 'g')):>13}"
            f"{('-' if row['gap'] is None else format(row['gap'], 'g')):>8}"
            f"  {fl}")
    return "\n".join(lines)


def record_report(report: Mapping[str, Any]) -> None:
    """Flag-gated export of an attribution report into the metrics
    registry (``perf.attribution.*``). Lazily imports the registry so this
    module stays importable standalone (synthetic-package / no-jax hosts:
    the import fails harmlessly and recording is a no-op)."""
    try:
        from . import metrics  # noqa: PLC0415
    except Exception:
        return
    if not metrics.enabled():
        return
    for name, row in report.get("sites", {}).items():
        for r, ms in row["floors_ms"].items():
            metrics.gauge("perf.attribution.floor_ms", ms, site=name,
                          resource=r)
        if row["binding"] is not None:
            metrics.gauge("perf.attribution.bound", 1.0, site=name,
                          resource=row["binding"])
        if row["gap"] is not None:
            metrics.gauge("perf.attribution.gap", row["gap"], site=name)


def load_json(path: str) -> Dict[str, Any]:
    """Tiny helper shared by the report tools (kept here so they stay
    import-light)."""
    with open(path) as f:
        return json.load(f)
