"""The readers of the program's own spans (harness/program_spans.py): on a
hand-made reduced trace with hand-made ring spans, and on a traced and an
untraced overlay run of the tiny cells on the CPU (counts and plumbing only:
nothing here is a device measurement)."""

import json
import time

import pytest

from harness import program_spans as ps

#: host clock = trace clock - 100 s
OFFSET = 100.0
CHAT_METRICS = [
    "idle_in_upload_share.chat", "idle_in_dispatch_share.chat",
    "idle_in_fetch_share.chat", "idle_in_settle_share.chat",
    "idle_in_admit_share.chat", "idle_outside_step_share.chat",
    "queue_wait_p90_ms.chat", "admit_stall_p90_ms.chat",
    "prefill_pad_share.chat"]


class FakeRun:
    """What the readers touch of a Run: the reduced trace, the benchmark's
    host-clock spans, and ``say``."""

    def __init__(self, trace, spans):
        self.trace, self.spans, self.said = trace, spans, []

    def say(self, msg):
        self.said.append(msg)


def _trace():
    # window 100..110 on the trace clock; the device is busy 101..103 and
    # 105..108, so idle 100..101, 103..105, 108..110 = 5 s
    ops = [(101.0, 103.0, "fusion.1", ""), (105.0, 108.0, "paged_decode.2", "")]
    return {"window": (100.0, 110.0), "devices": {"/device:TPU:0": ops},
            "modules": {}, "spans": [(100.5, 104.0, "bench/engine_step"),
                                     (104.5, 109.0, "bench/engine_step")]}


def _host():
    return {"bench/window": [(0.0, 10.0)],
            "bench/engine_step": [(-3.0, -2.0), (0.5, 4.0), (4.5, 9.0)]}


def _ring():
    """Program spans on the host clock (trace clock - 100)."""
    sp = lambda s, e, n, **a: (s, e, n, a)
    return [
        sp(-3.0, -2.0, "serving/step", step=0),           # before the window
        sp(0.5, 4.0, "serving/step", step=1),
        sp(0.6, 1.2, "serving/admit", request_id=7, queued_s=0.05),
        sp(0.7, 0.9, "serving/admit/prefill", request_id=7, tokens=48,
           bucket=64),
        sp(1.2, 4.0, "serving/decode", step=1),
        sp(3.0, 3.2, "serving/decode/upload"),
        sp(3.2, 3.5, "serving/decode/dispatch"),
        sp(3.5, 3.9, "serving/decode/fetch"),
        sp(3.9, 4.0, "serving/decode/settle"),
        sp(4.5, 9.0, "serving/step", step=2),
        sp(4.6, 4.7, "serving/admit", request_id=8, blocked=1),
        sp(4.7, 4.9, "serving/admit", request_id=8, queued_s=0.25),
        sp(4.75, 4.85, "serving/admit/extend", request_id=8, tokens=16,
           bucket=32),
        sp(8.2, 8.9, "serving/decode/fetch"),
    ]


@pytest.fixture()
def ring(monkeypatch):
    monkeypatch.setattr(ps, "ring", _ring)


def test_idle_shares_sum_to_the_idle_and_the_innermost_span_wins(ring):
    run = FakeRun(_trace(), _host())
    by = ps.idle_by_phase(run)
    # idle 0..1 (host clock): 0..0.5 under no span, 0.5..0.6 step's own,
    # 0.6..1.0 admit (0.2 of it under its prefill child: admit all the same)
    # idle 3..5: upload .2, dispatch .3, fetch .4, settle .1, 4.0..4.5
    # outside, 4.5..4.6 step's own, 4.6..4.9 the two admits, 4.9..5.0 step
    # idle 8..10: step 8.0..8.2, fetch 8.2..8.9, step 8.9..9.0, outside 1.0
    assert by["outside"] == pytest.approx(0.5 + 0.5 + 1.0)
    assert by["admit"] == pytest.approx(0.4 + 0.3)
    assert by["upload"] == pytest.approx(0.2)
    assert by["dispatch"] == pytest.approx(0.3)
    assert by["fetch"] == pytest.approx(0.4 + 0.7)
    assert by["settle"] == pytest.approx(0.1 + 0.1 + 0.1 + 0.1 + 0.2 + 0.1)
    assert sum(by.values()) == pytest.approx(5.0)
    shares = [f(run) for f in (
        ps.idle_in_upload_share, ps.idle_in_dispatch_share,
        ps.idle_in_fetch_share, ps.idle_in_settle_share,
        ps.idle_in_admit_share, ps.idle_outside_step_share)]
    assert sum(shares) == pytest.approx(50.0)      # = device_idle_share
    assert ps.idle_outside_step_share(run) == pytest.approx(20.0)
    assert any("difference +0.0000 points" in m for m in run.said)
    # the inner span took its part from the outer one, by name
    by_name = ps.idle_by_span(run.trace, [
        (s + OFFSET, e + OFFSET, n) for s, e, n, _ in _ring()])
    assert by_name["serving/admit/prefill"] == pytest.approx(0.2)
    assert by_name["serving/admit"] == pytest.approx(0.2 + 0.1 + 0.1)


def test_queue_wait_stall_and_padding_read_the_attributes(ring):
    run = FakeRun(_trace(), _host())
    # two admissions (the blocked attempt is none): waits 50 and 250 ms
    assert ps.queue_wait_p90_ms(run) == pytest.approx(50 + 0.9 * 200)
    assert ps.admit_stall_p90_ms(run) == pytest.approx(200 + 0.9 * 400)
    assert ps.prefill_pad_share(run) == pytest.approx(100 * (1 - 64 / 96))
    assert ps.step_dispatch_ms(run) is None     # no train/step span


def test_a_clock_residual_over_the_limit_silences_every_reader(ring):
    host = _host()
    host["bench/engine_step"][1] = (0.5 + 0.0003, 4.0)   # 0.3 ms off
    run = FakeRun(_trace(), host)
    for f in (ps.idle_in_upload_share, ps.idle_outside_step_share,
              ps.queue_wait_p90_ms, ps.admit_stall_p90_ms,
              ps.prefill_pad_share, ps.step_dispatch_ms):
        assert f(run) is None
    assert any("do not line up" in m for m in run.said)
    assert sum("worst residual" in m for m in run.said) == 1  # said once
    ok = FakeRun(_trace(), _host())
    assert ps.idle_in_fetch_share(ok) is not None
    assert any("worst residual 0.0 us" in m for m in ok.said)


def test_a_program_without_spans_and_an_untraced_run_report_nothing(
        monkeypatch):
    monkeypatch.setattr(ps, "ring", lambda: [])
    run = FakeRun(_trace(), _host())
    assert ps.idle_outside_step_share(run) is None
    assert ps.queue_wait_p90_ms(run) is None
    assert any("records none" in m for m in run.said)
    untraced = FakeRun(None, _host())
    assert ps.idle_in_admit_share(untraced) is None and not untraced.said
    # the real ring, with nothing recorded, is empty and does not raise
    from paddle_tpu.observability import tracing

    monkeypatch.undo()
    tracing.clear_spans()
    assert ps.ring() == []


def test_a_compile_inside_the_window_is_named_and_transparent(monkeypatch):
    extra = (0.75, 0.85, "compile{site=serving.prefill}",
             {"site": "serving.prefill", "cache_hit": 0})
    monkeypatch.setattr(ps, "ring", lambda: _ring() + [extra])
    run = FakeRun(_trace(), _host())
    assert ps.idle_by_phase(run)["admit"] == pytest.approx(0.7)
    assert any("COMPILE inside the window, site serving.prefill" in m
               for m in run.said)


def _drive(cell, manifest, seconds, trace):
    import jax

    import run as bench_run
    from harness import common

    r = common.Run(cell, 5, seconds, trace, time.perf_counter())
    r.devices = jax.devices()[:1]
    r.dev_tag = "cpu test"
    r.peaks = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    out = bench_run.drive(r, manifest)
    json.dumps(out)
    return out, r


def _manifest():
    from conftest import TINY_MANIFEST

    entry = lambda name, cell: {"name": name, "unit": "x",
                                "workloads": [cell]}
    return dict(TINY_MANIFEST, per_layer=TINY_MANIFEST["per_layer"] + [
        entry(n, "tiny-chat") for n in CHAT_METRICS] + [
        entry("step_dispatch_ms.train", "tiny-train")])


def test_traced_overlay_runs_show_the_ten_metrics_and_untraced_none(overlay):
    from paddle_tpu.observability import tracing

    man = _manifest()
    tracing.clear_spans()
    out, r = _drive("tiny-chat", man, 2.0, trace=1)
    got = out["metrics"]
    assert set(CHAT_METRICS) <= set(got), r.said if hasattr(r, "said") else got
    shares = [got[n]["value"] for n in CHAT_METRICS[:6]]
    # no device plane in a CPU trace: the whole stretch is idle, and the six
    # phases account for all of it
    assert sum(shares) == pytest.approx(100.0, abs=0.1)
    assert got["idle_outside_step_share.chat"]["value"] < 100.0
    assert got["queue_wait_p90_ms.chat"]["value"] >= 0
    assert got["admit_stall_p90_ms.chat"]["value"] > 0
    assert 0 <= got["prefill_pad_share.chat"]["value"] < 100
    assert "latency_per_tok_p50_ms" not in got       # a traced line
    tracing.clear_spans()
    out, _ = _drive("tiny-train", man, 1.0, trace=1)
    assert out["metrics"]["step_dispatch_ms.train"]["value"] > 0
    tracing.clear_spans()
    out, _ = _drive("tiny-chat", man, 1.0, trace=0)
    assert not set(CHAT_METRICS) & set(out["metrics"])
    assert "latency_per_tok_p50_ms" in out["metrics"]
    assert tracing.spans() == []                     # the off path
    out, _ = _drive("tiny-train", man, 1.0, trace=0)
    assert "step_dispatch_ms.train" not in out["metrics"]
