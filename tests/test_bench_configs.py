"""BASELINE config bench harness (bench.py --config ...): the rows run on
CPU with tiny shapes so the harness itself is CI-guarded — shapes, JSON
contract, breakdown fields."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_row_contract(capsys):
    import bench

    row = bench.bench_gpt_moe()
    out = capsys.readouterr().out.strip().splitlines()[-1]
    parsed = json.loads(out)
    assert parsed == row
    assert parsed["config"] == "gpt_moe"
    assert parsed["value"] > 0 and np.isfinite(parsed["value"])
    bd = parsed["breakdown"]
    for key in ("compute", "collective_measured", "collective_est",
                "host_input", "other"):
        assert 0.0 <= bd[key] <= 1.0, (key, bd)
    assert parsed["step_ms"] > 0
    # every row names its backend (perf_report.py --check skips rows whose
    # backend mismatches the committed baseline's)
    assert parsed["backend"] in ("cpu", "tpu")
    # roofline attribution sub-object: per-resource floors, a binding
    # resource, and the predicted-vs-measured gap
    attr = parsed["attribution"]
    assert set(attr["floors_ms"]) <= {"compute", "hbm", "ici"}
    assert attr["binding"] in attr["floors_ms"]
    assert attr["floor_ms"] == max(attr["floors_ms"].values())
    assert attr["measured_ms"] == pytest.approx(parsed["step_ms"], rel=0.02)
    assert attr["gap"] >= 1.0 or attr["gap"] is None
    assert attr["inputs"]["flops"] > 0


def test_all_configs_registered():
    import bench

    assert set(bench.CONFIGS) == {"bert_sst2", "gpt_dp", "ernie_mp4",
                                  "resnet50", "gpt_moe", "serving", "ckpt",
                                  "data", "comm", "reshard", "obs",
                                  "analysis", "elastic", "health",
                                  "anatomy", "autoshard"}


def test_bench_ckpt_row_contract(capsys):
    """The ckpt row's acceptance invariant: blocking save time (device->host
    snapshot) is strictly less than total save time (snapshot + background
    disk write), both present in the telemetry sub-object."""
    import bench
    from paddle_tpu import observability

    row = bench.bench_ckpt()
    out = capsys.readouterr().out.strip().splitlines()[-1]
    parsed = json.loads(out)
    assert parsed == row
    assert parsed["config"] == "ckpt"
    assert parsed["value"] > 0 and np.isfinite(parsed["value"])
    assert parsed["save_total_ms"] >= parsed["value"]  # blocking <= total
    assert parsed["restore_ms"] > 0
    hists = parsed["telemetry"]["histograms"]
    blocking = hists["ckpt.save.blocking_seconds"]
    total = hists["ckpt.save.total_seconds"]
    assert blocking["count"] == total["count"] > 0
    assert blocking["avg"] <= total["avg"]
    assert "ckpt.restore.seconds" in hists
    assert parsed["telemetry"]["counters"]["ckpt.save.bytes"] > 0
    # the row must not leave the global observability flag flipped on
    assert not observability.enabled()


def test_bench_data_row_contract(capsys):
    """The data row's acceptance invariant: packing efficiency >= 0.85 on
    the synthetic mixed-length doc mix, with the data.* metric series in
    the telemetry sub-object."""
    import bench
    from paddle_tpu import observability

    row = bench.bench_data()
    out = capsys.readouterr().out.strip().splitlines()[-1]
    parsed = json.loads(out)
    assert parsed == row
    assert parsed["config"] == "data"
    assert parsed["value"] > 0 and np.isfinite(parsed["value"])
    assert parsed["packing_efficiency"] >= 0.85
    assert parsed["host_wait_ms_mean"] >= 0.0
    assert parsed["batch_shape"][1] == 1024
    tele = parsed["telemetry"]
    assert tele["counters"]["data.batches"] > 0
    assert tele["counters"]["data.tokens"] > 0
    assert tele["histograms"]["data.host_wait_seconds"]["count"] > 0
    assert 0.0 < tele["gauges"]["data.packing.efficiency"] <= 1.0
    # the row must not leave the global observability flag flipped on
    assert not observability.enabled()


def test_bench_comm_row_contract(capsys):
    """The comm row's acceptance invariant: int8 block-128 wire format
    gives >= 3.5x compression over fp32, with the comm.* metric series in
    the telemetry sub-object and exact static byte accounting."""
    import bench
    from paddle_tpu import observability

    row = bench.bench_comm()
    out = capsys.readouterr().out.strip().splitlines()[-1]
    parsed = json.loads(out)
    assert parsed == row
    assert parsed["config"] == "comm"
    assert parsed["value"] > 0 and np.isfinite(parsed["value"])  # reduce ms
    assert parsed["step_ms"] > 0
    assert parsed["compression_ratio"] >= 3.5
    assert 0 < parsed["bytes_wire_per_step"] < parsed["bytes_raw_per_step"]
    assert parsed["buckets"] >= 1
    tele = parsed["telemetry"]
    assert tele["counters"]["train.steps"] > 0
    if "comm.grad_reduce.steps" in tele["counters"]:  # multi-device run
        assert tele["counters"]["comm.grad_reduce.steps"] > 0
        assert tele["counters"]["comm.grad_reduce.bytes{kind=wire}"] > 0
        assert tele["gauges"]["comm.grad_reduce.compression_ratio"] >= 3.5
    # hybrid dp x mp sub-row: per-mp-shard compressed groups, >= 3.0x
    hy = parsed["hybrid"]
    assert hy["groups"] >= 2
    assert hy["compression_ratio"] >= 3.0
    assert 0 < hy["bytes_wire_per_reduction"] < hy["bytes_raw_per_reduction"]
    # compressed MoE dispatch sub-row: quant vs raw token-exchange bytes
    moe = parsed["moe_dispatch"]
    assert moe["block"] >= 8
    assert moe["compression_ratio"] >= 3.0
    if moe["bytes_wire_per_step"] is not None:  # multi-device run
        assert 0 < moe["bytes_wire_per_step"] < moe["bytes_raw_per_step"]
        assert tele["gauges"]["moe.dispatch.compression_ratio"] >= 3.0
    # the row must not leave the global observability flag flipped on
    assert not observability.enabled()


def test_bench_reshard_row_contract(capsys):
    """The reshard row's acceptance invariant: the planner-driven move
    beats naive replicate-then-slice by >= 2.0x on the (2,2) -> (4,)
    param move, with the comm.reshard.* metric series in the telemetry
    sub-object and no device_put fallbacks."""
    import bench
    from paddle_tpu import observability

    row = bench.bench_reshard()
    out = capsys.readouterr().out.strip().splitlines()[-1]
    parsed = json.loads(out)
    assert parsed == row
    assert parsed["config"] == "reshard"
    assert parsed["value"] > 0 and np.isfinite(parsed["value"])
    assert parsed["plan_ms"] > 0 and parsed["execute_ms"] > 0
    assert 0 < parsed["bytes_wire"] < parsed["bytes_naive"]
    assert parsed["reduction_ratio"] >= 2.0
    assert parsed["steps"]  # a real plan, not the identity
    tele = parsed["telemetry"]
    assert tele["counters"]["comm.reshard.plans"] > 0
    assert tele["counters"]["comm.reshard.bytes{kind=wire}"] > 0
    assert tele["counters"]["comm.reshard.bytes{kind=naive}"] > 0
    assert not any(k.startswith("comm.reshard.fallbacks")
                   for k in tele["counters"])
    assert tele["histograms"]["comm.reshard.execute_seconds"]["count"] > 0
    # the row must not leave the global observability flag flipped on
    assert not observability.enabled()


def test_bench_obs_row_contract(capsys):
    """The obs row's acceptance invariant: the full telemetry tier
    (exporter + flight recorder + goodput monitor) reports its own service
    latencies and HBM accounting, and with the flag off the bench step time
    is unchanged within noise — the overhead value must be small relative
    to the step itself."""
    import bench
    from paddle_tpu import observability

    row = bench.bench_obs()
    out = capsys.readouterr().out.strip().splitlines()[-1]
    parsed = json.loads(out)
    assert parsed == row
    assert parsed["config"] == "obs"
    assert np.isfinite(parsed["value"])
    assert parsed["step_ms_off"] > 0 and parsed["step_ms_on"] > 0
    # zero-overhead within noise: the tier may not cost more than half a
    # step (CPU-CI timing is jittery; on real hardware this is ~0)
    assert abs(parsed["value"]) <= 0.5 * parsed["step_ms_off"]
    assert parsed["export_flush_ms"] > 0
    assert parsed["flight_flush_ms"] > 0
    assert 0.0 < parsed["goodput_fraction"] <= 1.0
    assert parsed["hbm_peak_mb"] > 0  # train-step executable was gauged
    tele = parsed["telemetry"]
    assert tele["counters"]["obs.export.flushes"] > 0
    assert tele["counters"]["obs.flight.flushes"] > 0
    assert tele["counters"]["train.steps"] > 0
    assert tele["gauges"]["mem.exe.peak_bytes{site=sharded_train_step}"] > 0
    hist = tele["histograms"]["train.step.dispatch_seconds"]
    assert hist["count"] > 0 and "p99" in hist and "p50" in hist
    # the row must not leave the global observability flag flipped on
    assert not observability.enabled()


def test_bench_analysis_row_contract(capsys):
    """The analysis row's acceptance invariant: the full program corpus
    traces, lints AND hlo-audits on CPU inside the 60s lint-gate budget,
    with no trace errors and no skipped builders on the 8-device host."""
    import bench

    row = bench.bench_analysis()
    out = capsys.readouterr().out.strip().splitlines()[-1]
    parsed = json.loads(out)
    assert parsed == row
    assert parsed["config"] == "analysis"
    assert 0 < parsed["value"] < 60_000  # analyze_ms within the gate budget
    assert 0 < parsed["build_ms"] < 60_000
    assert parsed["corpus_programs"] >= 5
    assert parsed["skipped"] == []
    assert parsed["trace_errors"] == 0
    assert parsed["rules_run"] >= 8
    assert set(parsed["findings"]) == {"info", "warning", "error"}
    # tier 2: both tiers together must stay inside the same gate budget
    assert 0 < parsed["hlo_audit_ms"]
    assert parsed["value"] + parsed["build_ms"] + parsed["hlo_audit_ms"] \
        < 60_000
    # the partitioned train step's gradient all-reduces are on the wire
    assert any(k.startswith("all-reduce|f32")
               for k in parsed["hlo_collectives"])
    peaks = parsed["hbm_peak_mb_by_site"]
    assert set(peaks) >= {"train_step", "serving_prefill", "serving_decode"}
    assert all(v >= 0 for v in peaks.values())
    assert peaks["train_step"] > 0


def test_bench_serving_row_contract(capsys):
    """The serving row's new acceptance invariants: SLO-violation counts
    under the row's targets, and a sampled per-request trace file on disk
    with span-structured records."""
    import bench
    from paddle_tpu.serving import read_request_traces

    row = bench.bench_serving()
    out = capsys.readouterr().out.strip().splitlines()[-1]
    parsed = json.loads(out)
    assert parsed == row
    assert parsed["config"] == "serving"
    assert parsed["value"] > 0 and np.isfinite(parsed["value"])
    slo = parsed["slo"]
    assert slo["ttft_target_ms"] > 0 and slo["tpot_target_ms"] > 0
    # generous CI targets: a healthy run records no violations, and the
    # counts dict is how a serving regression would surface
    assert isinstance(slo["violations"], dict)
    tr = parsed["request_trace"]
    assert os.path.exists(tr["path"])
    records = read_request_traces(tr["path"])
    assert len(records) == tr["sampled"] > 0
    assert tr["finished"] >= tr["sampled"]  # sample_every=2 downsampling
    for rec in records:
        assert [s["name"] for s in rec["spans"]] == \
            ["queue", "prefill", "decode", "finish"]
        assert rec["request_id"] >= 0
    # decode-step roofline rides on the row too (measured side = TPOT p50)
    assert parsed["attribution"]["binding"] in ("compute", "hbm")
    # paged-KV capacity row (ISSUE 13 acceptance): at the dense cache's
    # exact HBM budget the paged pool must admit STRICTLY more concurrent
    # requests than the dense layout's B_max slots
    cap = parsed["concurrent_requests_per_chip"]
    assert cap["hbm_budget_bytes"] > 0
    assert cap["page_size"] > 0
    assert cap["tokens_per_request"] > 0
    assert cap["dense"] > 0
    assert cap["paged"] > cap["dense"]
    # prefix sharing (ISSUE 19 acceptance): splicing the common prefix's
    # pages once must admit strictly more concurrent requests than the
    # private-pages paged baseline at the same HBM budget
    assert cap["shared_prefix_blocks"] >= 1
    assert cap["paged_prefix_shared"] > cap["paged"]
    # cached-prefix TTFT: a hit (splice + suffix prefill through a smaller
    # bucket) must beat a cold full prefill of the same prompt
    px = parsed["prefix_cache"]
    assert px["hit_blocks"] >= 1
    assert 0 < px["shared_prefix_tokens"] < px["prompt_tokens"]
    assert 0 < px["ttft_ms"]["hit"] < px["ttft_ms"]["miss"]
    # speculative decoding: accepted-tokens-per-step rides the row, the
    # accept rate (emitted / verify slots) is a true rate in (0, 1] — its
    # floor is 1/(k+1), the guaranteed bonus token per verify step
    spec = parsed["speculative"]
    assert spec["k"] >= 1
    assert 0 <= spec["accepted_tokens"] <= spec["draft_tokens"]
    assert spec["accepted_tokens_per_step"] >= 0.0
    assert 1.0 <= spec["tokens_per_step"] <= spec["k"] + 1
    assert 0.0 < spec["accept_rate"] <= 1.0


def test_bench_elastic_row_contract(capsys):
    """The elastic row's acceptance invariant (ISSUE 12): a host dies
    mid-run and the row reports the recovery pipeline phase by phase —
    detection via heartbeat staleness (>= the 300ms deadline), mesh
    re-formation, live reshard, and the headline recovery time to the
    first completed step at the shrunk world — with exactly one restart
    and the elastic.* series in the telemetry sub-object."""
    import bench
    from paddle_tpu import observability

    row = bench.bench_elastic()
    out = capsys.readouterr().out.strip().splitlines()[-1]
    parsed = json.loads(out)
    assert parsed == row
    assert parsed["config"] == "elastic"
    assert parsed["metric"] == "recovery_time_to_first_step_ms"
    assert parsed["value"] > 0 and np.isfinite(parsed["value"])
    assert parsed["detection_ms"] >= 300.0  # found by staleness, not luck
    assert parsed["reform_ms"] > 0 and parsed["reshard_ms"] > 0
    assert parsed["recovery_ms"] > 0
    assert parsed["value"] >= parsed["recovery_ms"]  # + first-step compile
    assert parsed["restarts"] == 1
    assert parsed["steps_lost"] == 0  # live regrid loses nothing
    assert parsed["world"]["hosts"] == 1
    tele = parsed["telemetry"]
    assert tele["counters"]["elastic.restarts"] == 1
    assert tele["counters"]["elastic.hosts_lost"] == 1
    assert tele["histograms"]["elastic.detection_seconds"]["count"] >= 1
    assert tele["histograms"]["elastic.recovery_to_first_step_seconds"][
        "count"] == 1
    assert tele["gauges"]["elastic.world.hosts"] == 1
    # the row must not leave the global observability flag flipped on
    assert not observability.enabled()


def test_bench_health_row_contract(capsys):
    """The health row's acceptance invariants (ISSUE 15): the in-graph
    stat pass + HealthMonitor stay within noise of the flag-off step
    (<5% is the hardware acceptance; CPU-CI gets the same jitter bound
    as the obs row), and the injected-NaN sub-row names the EXACT
    poisoned param group at the pipelined one-step detection latency —
    all without a second compile of the train step."""
    import bench
    from paddle_tpu import observability

    row = bench.bench_health()
    out = capsys.readouterr().out.strip().splitlines()[-1]
    parsed = json.loads(out)
    assert parsed == row
    assert parsed["config"] == "health"
    assert np.isfinite(parsed["value"])
    assert parsed["step_ms_off"] > 0 and parsed["step_ms_on"] > 0
    # zero-overhead within noise: same jitter bound as the obs row —
    # the stat pass may not cost more than half a step on CPU CI
    assert abs(parsed["overhead_ms"]) <= 0.5 * parsed["step_ms_off"]
    assert parsed["groups"] >= 3  # embeddings + layers + final_ln
    # the injected fault is caught, named exactly, one step later
    assert parsed["detect_named_group"] == parsed["detect_target_group"]
    assert parsed["detect_steps"] == 1
    assert parsed["anomalies"].get("nonfinite", 0) >= 1
    tele = parsed["telemetry"]
    # one-compile contract with health stats on (poison is a traced input)
    assert tele["counters"][
        "jit.compile.cache_miss{site=sharded_train_step}"] == 1
    assert any(k.startswith("health.anomaly{") for k in tele["counters"])
    assert "health.grad_norm{group=_global}" in tele["gauges"]
    # the row must not leave the global observability flag flipped on
    assert not observability.enabled()


def test_bench_anatomy_row_contract(capsys):
    """The anatomy row's acceptance invariants (ISSUE 16): the per-scope
    roofline floors from the annotated step jaxpr sum to the whole-step
    floor within tolerance; the unattributed bucket stays under budget;
    the injected slowdown (one block's MLP run 8x) is named as the top
    gap contributor by scope; and with xprof absent (this host) the row
    still lands, static-only, with the measured column null."""
    import bench
    from paddle_tpu import observability

    row = bench.bench_anatomy()
    out = capsys.readouterr().out.strip().splitlines()[-1]
    parsed = json.loads(out)
    assert parsed == row
    assert parsed["config"] == "anatomy"
    assert parsed["metric"] == "floor_sum_ratio"
    # Σ per-scope floors reconciles against the whole-step floor
    assert 0.9 <= parsed["value"] <= 1.1
    assert parsed["floor_sum_ok"] is True
    assert parsed["unattributed_ok"] is True
    assert parsed["unattributed_fraction"] < 0.05
    # the scope table covers the full training-step anatomy
    scopes = {r["scope"] for r in parsed["anatomy"]["scopes"]}
    assert {"embed", "loss", "opt/update"} <= scopes
    assert any(s.startswith("block_00/") for s in scopes)
    # injected-slowdown acceptance: the 8x MLP in block 1 is named #1
    assert parsed["injected_top_scope"] == "block_01/mlp"
    assert parsed["injected_ok"] is True
    # static-only degradation on hosts without the xprof converter
    from paddle_tpu.observability import xplane
    if not xplane.have_xprof():
        assert parsed["measured_available"] is False
        assert all(r["measured_ms"] is None
                   for r in parsed["anatomy"]["scopes"])
    # the walker's flop count agrees with XLA's own cost analysis
    if parsed["xla_flops"]:
        assert parsed["walker_flops"] == pytest.approx(
            parsed["xla_flops"], rel=0.25)
    # flag-gated telemetry rode along
    assert any(k.startswith("perf.anatomy.floor_ms")
               for k in parsed["telemetry"]["gauges"])
    # the row must not leave the global observability flag flipped on
    assert not observability.enabled()


@pytest.mark.slow
def test_perf_report_inject_gate():
    """The perf-regression gate trips deterministically: --inject
    synthesizes a row degraded 2.5x past the config's tolerance from the
    committed baseline itself, and the gate must exit 1 naming it (the
    lint_programs.py --inject pattern). The clean report exits 0."""
    cmd = [sys.executable, os.path.join(REPO, "tools", "perf_report.py")]
    r = subprocess.run(cmd + ["--check", "--inject", "gpt_dp", "--json"],
                       capture_output=True, text=True, cwd=REPO, timeout=120)
    assert r.returncode == 1, r.stdout[-2000:] + r.stderr[-2000:]
    payload = json.loads(r.stdout)
    assert payload["failed"] is True
    assert [x["config"] for x in payload["check"]["regressions"]] == ["gpt_dp"]

    r = subprocess.run(cmd + ["--json"], capture_output=True, text=True,
                       cwd=REPO, timeout=120)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    payload = json.loads(r.stdout)
    assert payload["failed"] is False
    assert payload["reconciliation"]["ok"] is True


def test_bench_fails_when_backend_unavailable():
    """A bench that finds no device FAILS: it must not re-host itself on
    the CPU and print a row. JAX_PLATFORMS=cuda reproduces an
    unavailable-backend init failure on a CPU-only host."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--config", "comm"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cuda"))
    assert r.returncode != 0
    assert not r.stdout.strip(), r.stdout[-2000:]  # no row, no number
