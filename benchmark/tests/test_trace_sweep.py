"""The trace reduction's two rewritten pieces against what they replaced, kept
here verbatim as oracles: ``idle_gaps_by_span`` (gaps x spans until PR 37, one
sweep since) and ``reduce_planes`` (three regular expressions an event until
PR 37, one parse a distinct HLO line since). Every comparison is ``==`` on
lists, dicts and floats: the sweep sums the same pieces in the same order.
Nothing here is a device measurement."""

import math
import os
import random
import time
from collections import defaultdict
from types import SimpleNamespace

import pytest
from test_stats_trace import SMALL, _tr

from harness import program_spans, stats, trace


def oracle_idle_gaps_by_span(tr, k: int = 10, device=None):
    """``trace.idle_gaps_by_span`` as it stood at PR 35, body verbatim."""
    if not tr["devices"]:
        return []
    name = device or sorted(tr["devices"])[0]
    t0, t1 = tr["window"]
    busy = stats.merged([(s, e) for s, e, _, _ in tr["devices"][name]])
    gaps, cur = [], t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        gaps.append((cur, t1))
    acc = defaultdict(float)
    spans = tr["spans"]
    for gs, ge in gaps:
        # cut the gap at every span edge; each piece goes to the shortest
        # span covering it
        cuts = sorted({gs, ge} | {x for s, e, _ in spans for x in (s, e)
                                  if gs < x < ge})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            cover = [(e - s, n) for s, e, n in spans if s <= mid < e]
            acc[min(cover)[1] if cover else "_no_benchmark_span_"] += b - a
    return [[n, v] for n, v in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def oracle_reduce_planes(planes, device_plane=trace.DEVICE_PLANE,
                         ops_line=trace.OPS_LINE):
    """``trace.reduce_planes`` as it stood at PR 35, body verbatim but for
    the module prefix: every event's line parsed, three times."""
    def _shape_of(event) -> str:
        m = trace._SHAPE.search(event.name)
        return m.group(1) if m else ""

    devices, modules, spans = {}, {}, []
    for plane in planes:
        if device_plane.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == trace.MODULES_LINE:
                    modules[plane.name] = sorted(
                        (e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9, e.name)
                        for e in line.events)
                if line.name != ops_line:
                    continue
                for e in line.events:
                    if trace.base_name(e.name) in trace.CONTAINERS:
                        continue
                    s = e.start_ns * 1e-9
                    ops.append((s, s + e.duration_ns * 1e-9,
                                trace.instr_name(e.name), _shape_of(e)))
            devices[plane.name] = sorted(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(trace.SPAN_PREFIX):
                        s = e.start_ns * 1e-9
                        spans.append((s, s + e.duration_ns * 1e-9, e.name))
    spans.sort()
    win = [(s, e) for s, e, n in spans if n == trace.WINDOW_SPAN]
    if win:
        t0, t1 = win[0]
    else:
        evs = [o for ops in devices.values() for o in ops]
        t0 = min((o[0] for o in evs), default=0.0)
        t1 = max((o[1] for o in evs), default=0.0)
    clipped = {}
    for name, ops in devices.items():
        clipped[name] = [(max(s, t0), min(e, t1), n, sh)
                         for s, e, n, sh in ops if e > t0 and s < t1]
    return {"window": (t0, t1), "devices": clipped,
            "modules": {k: [(max(s, t0), min(e, t1), n) for s, e, n in v
                            if e > t0 and s < t1]
                        for k, v in modules.items()},
            "spans": [sp for sp in spans if sp[2] != trace.WINDOW_SPAN
                      and sp[1] > t0 and sp[0] < t1]}


SPAN_NAMES = ("bench/engine_step", "bench/add_request", "bench/a", "bench/b",
              "bench/engine_step/inner", "bench/z")


def random_trace(seed: int, n_ops: int = 120, n_spans: int = 40):
    """A reduced trace whose spans nest, partly overlap, repeat, have no
    length and cross the window's edges, and whose ops overlap and touch.
    Many instants lie on a grid of 1/16 s, so that span edges meet op edges,
    each other and the window exactly; some are the next float after an
    earlier one, so that a piece's midpoint rounds onto one of its ends; and
    some spans carry a name of their own, so that a piece of no length
    would show as one more name."""
    rng = random.Random(seed)
    seen = [10.0, 14.0]

    def instant(lo, hi):
        kind = rng.random()
        if kind < 0.4:
            x = round(rng.uniform(lo, hi) * 16) / 16
        elif kind < 0.5:
            x = math.nextafter(rng.choice(seen), rng.choice((0.0, 99.0)))
        else:
            x = rng.uniform(lo, hi)
        seen.append(x)
        return x

    t0, t1 = 10.0, 14.0
    ops = []
    for i in range(n_ops):
        s = min(max(instant(t0, t1), t0), t1)
        e = min(t1, s + rng.choice((0.0, 1 / 16, rng.uniform(0, 0.08))))
        ops.append((s, e, f"fusion.{i}", ""))
    spans = []
    while len(spans) < n_spans:
        kind = rng.random()
        s = instant(t0 - 0.5, t1 + 0.25)
        if kind < 0.15:
            e = s                                   # no length
        elif kind < 0.3 and spans:
            ps, pe, _ = rng.choice(spans)           # inside another
            s = instant(ps, pe) if pe > ps else ps
            e = max(instant(s, pe), s) if pe > s else s
        elif kind < 0.4 and spans:
            spans.append(rng.choice(spans))         # the same span again
            continue
        elif kind < 0.5 and spans:
            ps, pe, _ = rng.choice(spans)           # same length, other name
            e = s + (pe - ps)
        else:
            e = max(s, rng.choice((s + 1 / 16, s + rng.uniform(0, 1.5),
                                   instant(s, s + 0.5))))
        name = rng.choice(SPAN_NAMES) if rng.random() < 0.8 \
            else f"bench/own{len(spans)}"
        spans.append((s, e, name))
    spans.sort()
    return {"window": (t0, t1), "modules": {}, "spans": spans,
            "devices": {"/device:TPU:0": sorted(ops),
                        "/device:TPU:1": sorted(ops[::3])}}


def same_both_ways(tr, **kw):
    got, want = trace.idle_gaps_by_span(tr, **kw), \
        oracle_idle_gaps_by_span(tr, **kw)
    assert got == want  # names, order and every float to its last bit
    return got


def test_the_hand_made_trace_splits_as_before():
    got = same_both_ways(_tr())
    assert [n for n, _ in got] == ["bench/engine_step", "_no_benchmark_span_",
                                   "bench/add_request"]


@pytest.mark.skipif(not os.path.exists(SMALL), reason="no recorded trace")
def test_the_recorded_chip_trace_splits_as_before():
    tr = trace.reduce_file(SMALL)
    assert len(same_both_ways(tr)) == 3
    assert same_both_ways(tr, k=1) == same_both_ways(tr)[:1]


@pytest.mark.parametrize("seed", range(24))
def test_random_traces_split_as_before(seed):
    tr = random_trace(seed)
    full = same_both_ways(tr, k=len(tr["spans"]) + 5)    # k above the names
    assert 2 <= len(full) <= len(tr["spans"]) + 1
    assert same_both_ways(tr, k=2) == full[:2]           # k below them
    same_both_ways(tr, k=10, device="/device:TPU:1")
    assert sum(v for _, v in full) == pytest.approx(
        trace.window_seconds(tr) - stats.union_seconds(
            [(s, e) for s, e, _, _ in tr["devices"]["/device:TPU:0"]]))


@pytest.mark.parametrize("case", ["no_spans", "no_ops", "no_devices",
                                  "unsorted_spans", "busy_all_through"])
def test_edge_cases_split_as_before(case):
    tr = random_trace(99)
    if case == "no_spans":
        tr["spans"] = []
        assert [n for n, _ in same_both_ways(tr)] == ["_no_benchmark_span_"]
    elif case == "no_ops":
        tr["devices"] = {"/device:TPU:0": []}
        assert sum(v for _, v in same_both_ways(tr)) == pytest.approx(4.0)
    elif case == "no_devices":
        tr["devices"] = {}
        assert same_both_ways(tr) == []
    elif case == "unsorted_spans":   # program_spans hands over its own list
        random.Random(5).shuffle(tr["spans"])
        same_both_ways(tr)
    else:
        tr["devices"] = {"/device:TPU:0": [(9.0, 15.0, "fusion.1", "")]}
        assert same_both_ways(tr) == []


@pytest.mark.parametrize("seed", range(6))
def test_idle_by_program_span_is_the_same_dict(seed, monkeypatch):
    """``program_spans.idle_by_span`` (slices of the window, the program's
    spans in the benchmark's place) through the sweep and through the
    oracle."""
    tr = random_trace(1000 + seed, n_ops=300, n_spans=90)
    spans = tr["spans"]
    got = program_spans.idle_by_span(tr, spans)
    monkeypatch.setattr(trace, "idle_gaps_by_span", oracle_idle_gaps_by_span)
    assert got == program_spans.idle_by_span(tr, spans)
    assert got


def test_the_sweep_is_linear_in_ops_and_spans():
    """200,000 ops x 2,000 spans (a tenth of the chat cell's traced 20 s) in
    seconds on one CPU core; gaps x spans took the oracle minutes here and
    836 s in the cell (PERF.md §7)."""
    rng = random.Random(7)
    n_ops, n_spans, length = 200_000, 2_000, 20.0
    step = length / n_ops
    ops = [(i * step, i * step + step * rng.uniform(0.2, 0.9), "fusion.1", "")
           for i in range(n_ops)]
    spans = []
    for i in range(n_spans // 2):
        s = i * length / (n_spans // 2)
        spans.append((s, s + 0.017, "bench/engine_step"))
        spans.append((s + 0.003, s + 0.004, "bench/add_request"))
    tr = {"window": (0.0, length), "devices": {"/device:TPU:0": ops},
          "modules": {}, "spans": spans}
    t0 = time.perf_counter()
    got = dict(trace.idle_gaps_by_span(tr))
    took = time.perf_counter() - t0
    assert took < 10.0, took
    assert set(got) == {"bench/engine_step", "bench/add_request",
                        "_no_benchmark_span_"}
    assert sum(got.values()) == pytest.approx(
        length - sum(e - s for s, e, _, _ in ops), rel=1e-9)
    # a slice of it, small enough for the oracle: the same lists
    cut = {"window": (0.0, 0.2), "modules": {},
           "devices": {"/device:TPU:0": ops[:2000]},
           "spans": [sp for sp in spans if sp[0] < 0.2]}
    same_both_ways(cut)


def _event(name, start_ns, duration_ns):
    return SimpleNamespace(name=name, start_ns=start_ns,
                           duration_ns=duration_ns)


def _planes():
    """Planes as ``ProfileData`` hands them over, with HLO lines that repeat
    (as a step's ops do), a ``while`` and a ``call`` around their bodies, a
    line with no shape and host spans inside and outside the window."""
    lines = ["%fusion.12 = bf16[8,128]{1,0} fusion(%copy.3, %p.1), kind=kLoop",
             "%while.2 = (s32[], bf16[4]{0}) while(%tuple.1), body=%b",
             "%paged_decode.7 = bf16[32,16,128]{2,1,0} custom-call(%fusion.3)",
             "%call = f32[2]{0} call(%x), to_apply=%f", "copy-start.5",
             "%all-reduce.1 = (f32[16]{0}, f32[]) all-reduce(%a, %b)"]
    ops = [_event(lines[i % len(lines)], 1_000 + 700 * i, 300 + 40 * (i % 5))
           for i in range(60)]
    device = SimpleNamespace(name="/device:TPU:0", lines=[
        SimpleNamespace(name="XLA Modules", events=[
            _event("jit_decode(1)", 900, 20_000),
            _event("jit_extend(2)", 30_000, 9_000)]),
        SimpleNamespace(name="XLA Ops", events=ops),
        SimpleNamespace(name="Steps", events=[_event("step", 0, 50_000)])])
    host = SimpleNamespace(name="/host:CPU", lines=[SimpleNamespace(
        name="python", events=[
            _event("bench/window", 2_000, 36_000),
            _event("bench/engine_step", 1_000, 5_000),
            _event("bench/engine_step", 20_000, 30_000),
            _event("bench/add_request", 50_000, 100),
            _event("serving/step", 3_000, 4_000)])])
    other = SimpleNamespace(name="/device:CUSTOM:0", lines=[])
    return [device, host, other]


def test_reduce_planes_parses_each_line_once_and_reduces_as_before():
    got = trace.reduce_planes(_planes())
    assert got == oracle_reduce_planes(_planes())
    ops = got["devices"]["/device:TPU:0"]
    assert {n for _, _, n, _ in ops} == {
        "fusion.12", "paged_decode.7", "copy-start.5", "all-reduce.1"}
    assert {sh for _, _, n, sh in ops if n == "all-reduce.1"} == {"f32[16]"}
    # no bench/window among the spans: first..last device event is the window
    planes = _planes()
    planes[1].lines[0].events = planes[1].lines[0].events[1:]
    assert trace.reduce_planes(planes) == oracle_reduce_planes(planes)


@pytest.mark.skipif(not os.path.exists(SMALL), reason="no recorded trace")
def test_the_recorded_chip_trace_reduces_as_before():
    from jax.profiler import ProfileData

    got = trace.reduce_file(SMALL)
    assert got == oracle_reduce_planes(ProfileData.from_file(SMALL).planes)
    assert sum(len(v) for v in got["devices"].values()) > 0
