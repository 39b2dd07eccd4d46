"""Roofline attribution tier (observability/attribution.py + xplane.py +
tools/perf_report.py): floor math, ledger reconciliation against the
committed baselines, the no-xprof degradation path, and the no-jax CLI."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_standalone(mod):
    """Import an observability module the way the no-jax tools do — through
    a synthetic package, never touching paddle_tpu/__init__ (proves the
    stdlib-only contract)."""
    pkg = types.ModuleType("_ptattr_test")
    pkg.__path__ = [os.path.join(REPO, "paddle_tpu", "observability")]
    sys.modules.setdefault("_ptattr_test", pkg)
    return importlib.import_module(f"_ptattr_test.{mod}")


attribution = _load_standalone("attribution")


# ----------------------------------------------------------- roofline math

def test_floors_and_binding():
    hw = attribution.HardwareSpec("test", peak_flops=100.0,
                                  hbm_bytes_per_s=10.0, ici_bytes_per_s=1.0)
    fl = attribution.floors(hw, flops=200.0, hbm_bytes=50.0, wire_bytes=3.0)
    assert fl == {"compute": 2.0, "hbm": 5.0, "ici": 3.0}
    row = attribution.attribute(hw, measured_s=10.0, flops=200.0,
                                hbm_bytes=50.0, wire_bytes=3.0)
    assert row["binding"] == "hbm"
    assert row["floor_ms"] == 5000.0
    assert row["gap"] == 2.0
    assert row["bound_fraction"] == 0.5


def test_floors_omit_absent_resources():
    hw = attribution.hardware_for_device("TPU v5 lite")
    fl = attribution.floors(hw, flops=1e12)
    assert set(fl) == {"compute"}
    row = attribution.attribute(hw, flops=1e12)  # no measured time
    assert row["binding"] == "compute"
    assert row["gap"] is None and row["measured_ms"] is None


def test_binding_tiebreak_deterministic():
    hw = attribution.HardwareSpec("t", 1.0, 1.0, 1.0)
    row = attribution.attribute(hw, flops=5.0, hbm_bytes=5.0, wire_bytes=5.0)
    # equal floors: first in RESOURCES order wins (compute, hbm, ici)
    assert row["binding"] == "compute"


def test_hardware_for_device():
    """Peaks are looked up by jax device_kind; the cpu row is explicit (for
    tests) and a device without a row RAISES — no unknown chip may borrow a
    nominal peak."""
    v5e = attribution.hardware_for_device("TPU v5 lite")
    assert (v5e.name, v5e.peak_flops, v5e.hbm_bytes_per_s,
            v5e.ici_bytes_per_s) == ("tpu-v5e", 197e12, 819e9, 200e9)
    assert attribution.hardware_for_device("cpu").name == "cpu-nominal"
    for unknown in ("tpu", "TPU v9", "???", ""):
        with pytest.raises(KeyError, match="no peaks recorded"):
            attribution.hardware_for_device(unknown)


def test_tolerances_pinned_to_hlo_audit():
    """reconcile_sites shares the HLO-audit gate's tolerances — the two
    ledgers cross-check the same bytes and must agree on 'close enough'."""
    from paddle_tpu.analysis import hlo_audit

    assert attribution.WIRE_TOLERANCE == hlo_audit.WIRE_TOLERANCE
    assert attribution.HBM_TOLERANCE == hlo_audit.HBM_TOLERANCE


def test_train_hbm_bytes_estimate():
    # bf16 params+grads, fp32 master, f32 moments:
    # 2*2 (fwd+bwd reads) + 2 (grad) + 8 (master rw) + 16 (moments rw)
    # + 2 (param write) = 32 B/param
    assert attribution.train_hbm_bytes_estimate(
        10, param_bytes=2, master=True, moment_bytes=4) == 320
    # pure-bf16 Adam, no master: 4 + 2 + 0 + 8 + 2 = 16 B/param
    assert attribution.train_hbm_bytes_estimate(
        10, param_bytes=2, master=False, moment_bytes=2) == 160


# ------------------------------------------------------------ reconciliation

def test_reconcile_sites_tolerances():
    hlo = {"a": {"wire_bytes": 1000, "hbm_peak_bytes": 1000}}
    ok = {"a": {"flops": 5.0, "wire_bytes": 1050, "hbm_peak_bytes": 980}}
    assert attribution.reconcile_sites(ok, hlo) == []
    # wire off by >10%
    bad = {"a": {"flops": 5.0, "wire_bytes": 1200}}
    assert any("wire_bytes" in p
               for p in attribution.reconcile_sites(bad, hlo))
    # hbm peak off by >5%
    bad = {"a": {"flops": 5.0, "hbm_peak_bytes": 1100}}
    assert any("hbm_peak_bytes" in p
               for p in attribution.reconcile_sites(bad, hlo))
    # missing from the hlo ledger
    assert any("not in hlo baseline" in p
               for p in attribution.reconcile_sites(
                   {"b": {"flops": 1.0}}, hlo))
    # flops never recorded (zero flops AND zero bytes)
    assert any("flops" in p for p in attribution.reconcile_sites(
        {"a": {"flops": 0.0, "hbm_bytes": 0.0}}, hlo))
    # zero flops with real bytes-accessed = a data-movement program, fine
    assert attribution.reconcile_sites(
        {"a": {"flops": 0.0, "hbm_bytes": 99.0}}, hlo) == []


def test_committed_ledgers_reconcile():
    """The acceptance invariant: tools/perf_baseline.json's site costs
    agree with tools/hlo_baseline.json's audited wire/HBM bytes within
    the shared tolerances — straight from the committed files."""
    perf = attribution.load_json(
        os.path.join(REPO, "tools", "perf_baseline.json"))
    hlo = attribution.load_json(
        os.path.join(REPO, "tools", "hlo_baseline.json"))
    assert perf["sites"], "perf baseline has no harvested sites"
    assert attribution.reconcile_sites(perf["sites"], hlo["sites"]) == []
    # and train_step carries real cost_analysis flops
    assert perf["sites"]["train_step"]["flops"] > 0
    assert perf["sites"]["train_step"]["wire_bytes"] == \
        hlo["sites"]["train_step"]["wire_bytes"]


def test_measured_step_seconds():
    # histogram source (fleet_report shape: sum/count)
    src = {"histograms": {"train.step.seconds": {"sum": 2.0, "count": 4}}}
    assert attribution.measured_step_seconds(src) == pytest.approx(0.5)
    # goodput-counter fallback (fleet_report counter dicts accepted too)
    src = {"counters": {"train.goodput.seconds{bucket=step}": {"total": 3.0},
                        "train.steps": 6}}
    assert attribution.measured_step_seconds(src) == pytest.approx(0.5)
    assert attribution.measured_step_seconds({}) is None


def test_site_report_and_render():
    report = attribution.site_report(
        {"s1": {"flops": 1e12, "hbm_bytes": 1e9, "measured_s": 0.02}},
        "TPU v5 lite", measured={"s1": 0.01})
    row = report["sites"]["s1"]
    assert row["measured_ms"] == 10.0  # explicit measured overrides
    text = attribution.render(report)
    assert "s1" in text and "compute" in text


def test_record_report_is_noop_standalone():
    # under the synthetic package the metrics import fails; must not raise
    attribution.record_report(
        {"sites": {"x": {"floors_ms": {"compute": 1.0},
                         "binding": "compute", "gap": 2.0}}})


# ------------------------------------------------------------------ xplane

def test_xplane_no_xprof_degradation():
    """Satellite (a): without the optional xprof converter the profile
    tooling degrades to 'paths collected, table unavailable' instead of
    crashing — this container exercises the real path."""
    from paddle_tpu.observability import xplane

    if xplane.have_xprof():  # pragma: no cover - xprof-equipped host
        pytest.skip("xprof installed; degradation path not reachable")
    assert xplane.op_table(["/nonexistent/foo.xplane.pb"]) is None


def test_xplane_op_rows_parsers():
    from paddle_tpu.observability import xplane

    # plain list-of-dicts table
    rows = xplane.op_rows(json.dumps(
        [{"Op": "fusion.1", "Self time (us)": 12.0}]))
    assert rows[0]["Op"] == "fusion.1"
    assert xplane.device_time_seconds(rows) == pytest.approx(12e-6)
    # gviz DataTable shape
    gviz = {"cols": [{"label": "Op"}, {"label": "self_time_us"}],
            "rows": [{"c": [{"v": "conv.2"}, {"v": 30.0}]},
                     {"c": [{"v": "bn.3"}, {"v": 10.0}]}]}
    rows = xplane.op_rows(json.dumps(gviz))
    assert [r["Op"] for r in rows] == ["conv.2", "bn.3"]
    assert xplane.device_time_seconds(rows, iters=2) == pytest.approx(20e-6)
    top = xplane.top_ops(rows, n=1)
    assert top[0]["Op"] == "conv.2"
    # unrecognized payloads parse to [] rather than raising
    assert xplane.op_rows("not json at all") == []
    assert xplane.op_rows(json.dumps({"weird": 1})) == []
    # no self-time column -> no device time
    assert xplane.device_time_seconds([{"Op": "x"}]) is None


# ------------------------------------------------------------- the CLI

def test_perf_report_json_no_jax():
    """Acceptance: `python tools/perf_report.py --json` runs with NO jax
    and names a binding resource per bench config from committed data."""
    env = dict(os.environ)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "perf_report.py"),
         "--json"],
        capture_output=True, text=True, cwd=REPO, timeout=120, env=env)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    payload = json.loads(r.stdout)
    assert payload["reconciliation"]["ok"] is True
    configs = payload["configs"]
    assert set(configs) == {"bert_sst2", "gpt_dp", "ernie_mp4", "resnet50",
                            "gpt_moe"}
    for name, row in configs.items():
        assert row["binding"] in ("compute", "hbm", "ici"), name
        assert row["gap"] is not None and row["gap"] >= 1.0, name
    # the roofline's bound_fraction reproduces the committed MFU for the
    # compute-bound training rows (same peak, same step time)
    baseline = json.load(
        open(os.path.join(REPO, "tools", "perf_baseline.json")))
    for name, row in configs.items():
        if row["binding"] == "compute":
            assert row["bound_fraction"] == pytest.approx(
                baseline["configs"][name]["mfu"], abs=0.01), name


def test_perf_report_check_clean_rows(tmp_path):
    """A row matching the baseline within tolerance passes; a backend
    mismatch is skipped, never compared."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        perf_report = importlib.import_module("perf_report")
    finally:
        sys.path.pop(0)
    baseline = perf_report.load_baseline(
        os.path.join(REPO, "tools", "perf_baseline.json"))
    ok_row = {"config": "bert_sst2", "value": 105396.0 * 0.95,
              "backend": "tpu"}
    cpu_row = {"config": "gpt_dp", "value": 1.0, "backend": "cpu"}
    diff = perf_report.diff_rows([ok_row, cpu_row], baseline)
    assert diff["regressions"] == []
    assert [c["config"] for c in diff["checked"]] == ["bert_sst2"]
    assert diff["skipped"][0]["config"] == "gpt_dp"
    # direction-aware: a lower-is-better metric regresses UPWARD
    baseline["configs"]["lat"] = {"metric": "step_ms", "value": 100.0,
                                  "tolerance": 0.1}
    up = {"config": "lat", "value": 120.0, "backend": "tpu"}
    down = {"config": "lat", "value": 85.0, "backend": "tpu"}
    diff = perf_report.diff_rows([up, down], baseline)
    assert [r["config"] for r in diff["regressions"]] == ["lat"]
    assert [r["config"] for r in diff["improvements"]] == ["lat"]
