"""benchmark/harness/launches.py (the launch join, the clock check by
causality and the split of the device's idle time) on hand-made reduced
traces and span rings with known answers. Nothing here is a device
measurement: the timelines are written down, not recorded."""

import os
import sys
import time

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, BENCH)
from harness import launches, program_spans  # noqa: E402

MS = 1e-3
OFFSET = 100.0      # trace clock = host clock + OFFSET
LAT, TAIL = 0.3 * MS, 0.2 * MS
OPS, OP, HOLE = 10, 0.5 * MS, 0.1 * MS      # a run: 10 ops, 10 holes of 0.1
BETWEEN = 0.25 * MS     # settle + the loop + the next step up to its call
PAD = 5 * MS            # of the window, before the first step and at its end


class FakeRun:
    def __init__(self, trace, spans):
        self.trace, self.spans, self.said = trace, spans, []

    def say(self, msg):
        self.said.append(msg)

    def line(self, start):
        return next(m for m in self.said if m.startswith(start))


class Timeline:
    """A synchronous engine's steps written down on the host's clock, with
    the device's clock ``d`` ahead of it."""

    def __init__(self, d=0.0, first=101):
        self.d, self.h, self.number = d, 0.001, first
        self.ring, self.runs, self.ops, self.steps = [], [], [], []
        self.free = 0.0     # device clock at which the device is free

    def span(self, s, e, name, **attrs):
        self.ring.append((s, e, name, attrs))

    def program(self, called, name, seconds):
        """A run launched at host time ``called``: (start, end) on the host's
        clock; ops of ``OP`` with holes of ``HOLE`` behind each."""
        s = max(called + LAT, self.free)
        e = s + seconds
        self.free = e
        self.runs.append((s + self.d + OFFSET, e + self.d + OFFSET, name))
        t = s
        while t + OP <= e + 1e-12:
            self.ops.append((t + self.d + OFFSET, t + OP + self.d + OFFSET,
                             "fusion.1", ""))
            t += OP + HOLE
        return s, e

    def launch(self):
        self.number += 1
        return self.number - 1

    def admit(self):
        """prefill + the eager argmax behind it, inside the open step."""
        t = self.h
        n = self.launch()
        self.span(t, t + 0.5 * MS, "serving/admit/prefill", request_id=1,
                  tokens=40, bucket=64, launch=n)
        _, e = self.program(t + 0.4 * MS - LAT, "jit_paged_prefill_fn(7)",
                            20 * (OP + HOLE))
        m = self.launch()
        _, e2 = self.program(t + 0.6 * MS, "jit_argmax(9)", OP + HOLE)
        self.span(t + 0.5 * MS, e2 + TAIL, "serving/admit/sample",
                  request_id=1, launch=m, eager=1, waits_for=m)
        self.span(t - 0.01 * MS, e2 + TAIL + 0.01 * MS, "serving/admit",
                  request_id=1, prompt_tokens=40, queued_s=0.0, hit_blocks=0)
        self.h = e2 + TAIL + 0.02 * MS

    def step(self, running=1, waiting=0, admit=False, finished=0,
             stall=0.0, name="jit_paged_decode_fn(3)", drop=False):
        t0 = self.h
        if admit:
            self.h += 0.02 * MS
            self.admit()
        self.h += BETWEEN - 0.07 * MS + stall   # grow, upload: host work
        call = self.h
        n = self.launch()
        if not drop:
            self.span(call, call + 0.8 * MS, "serving/decode/dispatch",
                      launch=n)
        _, e = self.program(call, name, OPS * (OP + HOLE))
        self.span(call + 0.8 * MS, e + TAIL, "serving/decode/fetch",
                  waits_for=n)
        self.span(e + TAIL, e + TAIL + 0.03 * MS, "serving/decode/settle",
                  finished=finished)
        self.span(t0 + 0.01 * MS, e + TAIL + 0.04 * MS, "serving/decode",
                  step=len(self.steps) + 1, running=running + admit)
        self.span(t0, e + TAIL + 0.05 * MS, "serving/step",
                  step=len(self.steps) + 1, running=running, waiting=waiting)
        self.steps.append((t0, e + TAIL + 0.05 * MS))
        self.h = e + TAIL + 0.07 * MS   # the caller's loop

    def run(self, ring=None):
        """(FakeRun, the ring) with the window around everything."""
        t0, t1 = 0.001 - PAD, self.h + PAD
        tr = {"window": (t0 + OFFSET, t1 + OFFSET),
              "devices": {"/device:TPU:0": sorted(self.ops)},
              "modules": {"/device:TPU:0": sorted(self.runs)},
              "spans": [(s + OFFSET, e + OFFSET, "bench/engine_step")
                        for s, e in self.steps]}
        host = {"bench/window": [(t0, t1)], "bench/engine_step": self.steps}
        return FakeRun(tr, host), sorted(self.ring if ring is None else ring)


def read(monkeypatch, timeline, ring=None):
    run, spans = timeline.run(ring)
    monkeypatch.setattr(program_spans, "ring", lambda: spans)
    return run, launches.split(run)


def steady(d=0.0, n=40, **kw):
    tl = Timeline(d)
    for i in range(n):
        tl.step(**kw)
    return tl


@pytest.mark.parametrize("d", [0.0, 0.7 * MS, -0.4 * MS, 1.5 * MS])
def test_a_known_offset_is_bounded_and_the_parts_sum_to_the_idle(
        monkeypatch, d):
    tl = Timeline(d)
    tl.step(running=0, waiting=1, admit=True)
    for _ in range(30):
        tl.step()
    run, got = read(monkeypatch, tl)
    assert "33 of 33 module runs joined" in run.line("launch join:")
    assert "unjoined launches 0 of 33" in run.line("launch join:")
    assert "numbers 101..133 contiguous yes" in run.line("launch join:")
    # causality: the bounds hold the injected d between them, as narrow as
    # the shortest launch plus the shortest tail
    line = run.line("clock check: device clock")
    lo, hi = [float(x) for x in (line.split(" us <= d <= ")[0].split()[-1],
                                 line.split(" us <= d <= ")[1].split()[0])]
    assert lo <= d * 1e6 + 1e-3 and d * 1e6 - 1e-3 <= hi
    assert hi - lo == pytest.approx((LAT + TAIL) * 1e6, abs=0.2)
    want = 0.0 if lo <= 0 <= hi else min((lo, hi), key=abs)
    assert got["skew_us"] == pytest.approx(abs(want), abs=1e-3)
    # the five parts are the device's idle time
    busy = sum(e - s for s, e, _, _ in tl.ops)
    assert sum(got[p] for p in launches.PARTS) == pytest.approx(
        got["window"] - busy, abs=1e-9)
    assert "difference +0.0000 points" in run.line("idle by launch")
    # 31 decode runs of 10 holes, a prefill of 20 and the argmax's one
    assert got["in_program"] == pytest.approx((310 + 21) * HOLE, abs=1e-9)
    # before the first step, which finds nothing running: no engine to run
    assert got["empty"] == pytest.approx(PAD, abs=1e-9)
    assert got["host_turn_p50_ms"] == pytest.approx(BETWEEN / MS, abs=1e-6)


def test_tail_plus_launch_does_not_depend_on_the_clock(monkeypatch):
    tails = []
    for d in (0.0, 0.7 * MS, -0.4 * MS, 1.5 * MS):
        _, got = read(monkeypatch, steady(d))
        whole = got["turns"][1:-1]      # the window's edges move with d
        assert len(whole) == 39
        for idle, _, host, empty, tail, launch in whole:
            assert host == pytest.approx(BETWEEN, abs=1e-9) and empty == 0
            assert tail + launch == pytest.approx(LAT + TAIL, abs=1e-9)
            assert idle == pytest.approx(BETWEEN + LAT + TAIL, abs=1e-9)
        tails.append(whole[0][4])
    # d = 0 lies inside its bounds, so d* = 0 and the tail is the true one;
    # elsewhere d* is the bound nearer 0, and the tail moves with it
    assert tails[0] == pytest.approx(TAIL, abs=1e-9)
    assert tails[1] == pytest.approx(0.0, abs=1e-9)         # d* = d - TAIL
    assert tails[2] == pytest.approx(LAT + TAIL, abs=1e-9)  # d* = d + LAT


def test_an_empty_engine_is_the_traffics_not_the_hosts(monkeypatch):
    tl = Timeline()
    for _ in range(5):
        tl.step()
    tl.step(finished=1)             # the last request ends
    tl.h += 0.050                   # nothing to run for 50 ms
    tl.step(running=0, waiting=1, admit=True)
    for _ in range(5):
        tl.step()
    tl.step(waiting=1)              # a backlog: what follows is not empty
    tl.h += 0.010
    tl.step(running=0, waiting=1, admit=True)
    tl.step(finished=1)
    tl.h += 0.020                   # and the stretch ends on an empty engine
    run, got = read(monkeypatch, tl)
    # under no serving/ span with nothing running: the 50 ms and the 20 ms +
    # the window's end, each behind the 0.02 ms of the caller's loop; not
    # the 10 ms behind a backlog, nor the window's start (a request runs)
    assert got["empty"] == pytest.approx(0.050 + 0.020 + PAD + 0.04 * MS,
                                         abs=1e-9)
    assert got["host_turn"] > 0.010 + PAD
    assert sum(got[p] for p in launches.PARTS) == pytest.approx(
        got["window"] - sum(e - s for s, e, _, _ in tl.ops), abs=1e-9)
    assert "empty 50.0" in [m for m in run.said if m.startswith("  turn")][0]


@pytest.mark.parametrize("fault", ["dropped_launch", "full_ring",
                                   "unjoinable_name", "no_numbers",
                                   "untraced"])
def test_what_cannot_be_joined_reports_nothing_and_says_why(
        monkeypatch, fault):
    tl, ring, why = Timeline(), None, None
    for i in range(40):
        tl.step(drop=(fault == "dropped_launch" and i == 20),
                name="jit_mystery(3)" if fault == "unjoinable_name" and i % 2
                else "jit_paged_decode_fn(3)")
    if fault == "dropped_launch":
        why = "the launch numbers have a hole"
    elif fault == "unjoinable_name":
        why = "module runs unjoined; launches unjoined"
    elif fault == "full_ring":
        pad = [(-2.0, -1.9, "serving/step", {"step": 0})] * (
            launches.RING - len(tl.ring))
        ring, why = pad + tl.ring, "the span ring was full and wrapped"
    elif fault == "no_numbers":     # the parent's program
        ring = [(s, e, n, {k: v for k, v in a.items()
                           if k not in ("launch", "waits_for", "eager")})
                for s, e, n, a in tl.ring]
        why = "no span carries a launch number"
    run, got = read(monkeypatch, tl, ring)
    if fault == "untraced":
        run.trace = None
        del run._launch_split, run._program_spans
        got = launches.split(run)
    assert got is None
    for f in (launches.device_clock_skew_us, launches.idle_in_program_share,
              launches.idle_in_host_turn_share, launches.idle_in_tail_share,
              launches.idle_in_launch_share, launches.idle_engine_empty_share,
              launches.host_turn_p50_ms, launches.host_stall_share):
        assert f(run) is None
    if why:
        assert sum(why in m for m in run.said) == 1, run.said   # said once
    if fault == "unjoinable_name":
        assert "'jit_mystery(3)': 20" in run.line("launch join:")
    if fault == "dropped_launch":
        assert "39 of 40 module runs joined" in run.line("launch join:")


def test_clocks_that_contradict_causality_leave_tail_and_launch_out(
        monkeypatch):
    tl = steady(n=20)
    # the device's clock jumps 0.6 ms back half-way: no d fits both halves
    half, back = tl.runs[10][0], 0.6 * MS
    tl.runs = [(s - back * (s >= half), e - back * (s >= half), n)
               for s, e, n in tl.runs]
    tl.ops = [(s - back * (s >= half), e - back * (s >= half), n, sh)
              for s, e, n, sh in tl.ops]
    run, got = read(monkeypatch, tl)
    assert "NO common point" in run.line("clock check: correction")
    assert got["tail"] is None and got["launch"] is None
    assert launches.device_clock_skew_us(run) is None
    assert launches.idle_in_tail_share(run) is None
    assert launches.idle_in_launch_share(run) is None
    assert launches.idle_in_program_share(run) == pytest.approx(
        100 * 200 * HOLE / got["window"])
    assert launches.idle_in_host_turn_share(run) > 0


def test_a_launch_ahead_of_the_fetch_has_no_host_turn(monkeypatch):
    """Speed 2's shape: step N + 1 is called before step N's tokens are
    fetched, so the host is never in the device's way."""
    tl = Timeline()
    call = tl.h
    n = tl.launch()
    tl.span(call, call + 0.8 * MS, "serving/decode/dispatch", launch=n)
    _, end = tl.program(call, "jit_paged_decode_fn(3)", OPS * (OP + HOLE))
    for _ in range(30):
        nxt = tl.launch()
        tl.span(call + 1 * MS, call + 1.8 * MS, "serving/decode/dispatch",
                launch=nxt)
        _, nxt_end = tl.program(call + 1 * MS, "jit_paged_decode_fn(3)",
                                OPS * (OP + HOLE))
        tl.span(call + 1.8 * MS, end + TAIL, "serving/decode/fetch",
                waits_for=n)
        tl.steps.append((call + 0.9 * MS, end + TAIL + 0.01 * MS))
        tl.span(*tl.steps[-1], "serving/step", step=len(tl.steps),
                running=1, waiting=0)
        call, n, end = end + TAIL + 0.02 * MS, nxt, nxt_end
    tl.h = end
    run, got = read(monkeypatch, tl)
    assert "31 of 31 module runs joined" in run.line("launch join:")
    assert got["host_turn_p50_ms"] == 0.0
    assert all(got[p] >= 0.0 for p in launches.PARTS)
    # the runs stand back to back: what idles is inside the programs, but
    # for the window's edges and the first launch
    assert got["in_program"] == pytest.approx(310 * HOLE, abs=1e-9)
    assert got["host_turn"] == pytest.approx(PAD, abs=1e-9)
    assert got["tail"] + got["launch"] == pytest.approx(LAT + PAD, abs=1e-9)


def test_one_stall_moves_the_stall_share_and_not_the_median(monkeypatch):
    calm, tl = steady(n=2000), Timeline()
    for i in range(2000):
        tl.step(stall=1.0 if i == 1234 else 0.0)
    t0 = time.perf_counter()
    _, a = read(monkeypatch, calm)
    took = time.perf_counter() - t0
    run, b = read(monkeypatch, tl)
    assert took < 2.0, took     # 2,000 steps, 20,000 ops, 10,000 spans
    assert a["host_turn_p50_ms"] == b["host_turn_p50_ms"] == pytest.approx(
        BETWEEN / MS, abs=1e-6)
    # the window's first and last PAD are host time too, each 20 turns long
    edge = 2 * PAD + BETWEEN + LAT + TAIL
    idle = lambda got: sum(got[p] for p in launches.PARTS)
    assert a["host_stall_share"] == pytest.approx(100 * edge / idle(a))
    assert b["host_stall_share"] == pytest.approx(
        100 * (edge + 1.0 + BETWEEN + LAT + TAIL) / idle(b))
    assert a["host_stall_share"] < 0.5 and b["host_stall_share"] > 20
    first = [m for m in run.said if m.startswith("  turn")][0]
    assert "after launch 1334 before launch 1335" in first
    assert "host 1000.25" in first and "under serving/decode" in first


def test_module_names_tell_the_programs_apart():
    assert launches.kind_of("jit_paged_decode_fn(12345)") == "decode"
    assert launches.kind_of("jit_verify_fn(1)") == "decode"
    assert launches.kind_of("jit_paged_prefill_fn(2)") == "prefill"
    assert launches.kind_of("jit_extend_fn(3)") == "extend"
    assert launches.kind_of("jit_copy_page_fn(4)") == "copy_page"
    assert launches.kind_of("jit_argmax(5)") is None


def test_page_copies_under_one_span_take_its_numbers(monkeypatch):
    tl = steady(n=5)
    t = tl.h + 0.02 * MS
    first = tl.launch()
    tl.launch()
    tl.span(t, t + 0.3 * MS, "serving/decode/grow_pages", allocated=0,
            cache_full=0, cow_copies=2, launch=first, launches=2)
    tl.program(t, "jit_copy_page_fn(8)", OP + HOLE)
    tl.program(t + 0.1 * MS, "jit_copy_page_fn(8)", OP + HOLE)
    tl.h = t + 0.3 * MS
    for _ in range(5):
        tl.step()
    run, got = read(monkeypatch, tl)
    assert "12 of 12 module runs joined" in run.line("launch join:")
    assert "numbers 101..112 contiguous yes" in run.line("launch join:")
    assert got["in_program"] == pytest.approx(102 * HOLE, abs=1e-9)
