"""Reduction of a jax profiler trace (``*.xplane.pb``) to what the per-layer
metrics read, with nothing but ``jax.profiler.ProfileData``.

A reduced trace is a plain dict, so the readers and the tests need no
profiler types:

    window      (t0, t1) seconds on the trace clock: the span of the
                benchmark's own ``bench/window`` annotation when present,
                else first..last device event
    devices     {plane name: [(start, end, name, shape), ...]} device ops,
                clipped to the window, seconds
    modules     {plane name: [(start, end, name)]} program executions (the
                device's "XLA Modules" line), clipped to the window
    spans       [(start, end, name)] host annotations named ``bench/...``
"""

from __future__ import annotations

import glob
import heapq
import os
import re
from bisect import bisect_left, bisect_right
from collections import defaultdict

from . import stats

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
#: control-flow ops span their bodies' ops on the same line: counting both
#: would count the body twice
CONTAINERS = ("while", "conditional", "call")
_SHAPE = re.compile(r"=\s*\(?\s*(\w+\[[\d,]*\])")
_SUFFIX = re.compile(r"[.\d]+$")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return files[-1]


def instr_name(event_name: str) -> str:
    """The instruction's own name. On the TPU an op event is named by its
    whole HLO line (``%fusion.12 = bf16[8,128]{1,0} fusion(%copy.3, ...)``):
    only the part before `` = `` names the op, the rest names its operands
    (matching a kernel's name there would count its consumers too)."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def base_name(name: str) -> str:
    """Instruction name without its numeric suffix: ``fusion.12`` ->
    ``fusion``; ``%copy.3 = ...`` -> ``copy``."""
    n = instr_name(name)
    return _SUFFIX.sub("", n) or n


def _shape_of(event_name: str) -> str:
    """First output shape in the event's HLO line, '' where there is none."""
    m = _SHAPE.search(event_name)
    return m.group(1) if m else ""


def reduce_file(path: str, device_plane=DEVICE_PLANE, ops_line=OPS_LINE):
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, device_plane,
                         ops_line)


def reduce_planes(planes, device_plane=DEVICE_PLANE, ops_line=OPS_LINE):
    devices, modules, spans = {}, {}, []
    # an op event is named by its whole HLO line, and millions of events
    # hold a few thousand distinct lines: each is parsed once
    parsed = {}  # event name -> (is a container, instruction name, shape)
    for plane in planes:
        if device_plane.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules[plane.name] = sorted(
                        (e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9, e.name)
                        for e in line.events)
                if line.name != ops_line:
                    continue
                for e in line.events:
                    name = e.name
                    if name not in parsed:
                        parsed[name] = (base_name(name) in CONTAINERS,
                                        instr_name(name), _shape_of(name))
                    container, instr, shape = parsed[name]
                    if container:
                        continue
                    s = e.start_ns * 1e-9
                    ops.append((s, s + e.duration_ns * 1e-9, instr, shape))
            devices[plane.name] = sorted(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = e.start_ns * 1e-9
                        spans.append((s, s + e.duration_ns * 1e-9, e.name))
    spans.sort()
    win = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
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
            "spans": [sp for sp in spans if sp[2] != WINDOW_SPAN
                      and sp[1] > t0 and sp[0] < t1]}


def window_seconds(tr) -> float:
    return tr["window"][1] - tr["window"][0]


def busy_seconds(tr) -> float:
    """Seconds in which an operation ran on the device: union of op
    intervals per device, averaged over the devices in the trace."""
    per = [stats.union_seconds([(s, e) for s, e, _, _ in ops])
           for ops in tr["devices"].values()]
    return sum(per) / len(per) if per else 0.0


def op_seconds(tr, match) -> float:
    """Summed device time of ops whose name ``match`` accepts, averaged over
    devices."""
    per = [sum(e - s for s, e, n, _ in ops if match(n))
           for ops in tr["devices"].values()]
    return sum(per) / len(per) if per else 0.0


def op_count(tr, match) -> float:
    per = [sum(1 for _, _, n, _ in ops if match(n))
           for ops in tr["devices"].values()]
    return sum(per) / len(per) if per else 0.0


def top_ops(tr, k: int = 10):
    """The k (name_shape, seconds) groups with most device time, averaged
    over devices."""
    acc = defaultdict(float)
    for ops in tr["devices"].values():
        for s, e, n, sh in ops:
            key = base_name(n) + ("_" + re.sub(r"[\[\],]+", "_", sh) if sh else "")
            acc[key] += e - s
    nd = max(len(tr["devices"]), 1)
    return [[k_, v / nd] for k_, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps_by_span(tr, k: int = 10, device=None):
    """Idle time of one device (the first by name unless given) split by the
    benchmark span that covers it: for every gap between device ops, the
    part under each ``bench/...`` span goes to that span (innermost wins),
    the rest to ``_no_benchmark_span_``.

    One sweep in time order over the gaps, the span edges and a heap of the
    spans that are open: (gaps + spans) log spans, whatever the engine's
    speed."""
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
    spans = sorted(tr["spans"], key=lambda sp: sp[0])
    edges = sorted({x for s, e, _ in spans for x in (s, e)})
    entered, opened = 0, []  # spans[:entered] are in the heap of open spans
    for gs, ge in gaps:
        # cut the gap at every span edge; each piece goes to the shortest
        # span covering it (ties by name)
        cuts = [gs, *edges[bisect_right(edges, gs):bisect_left(edges, ge)], ge]
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2  # never falls from piece to piece
            while entered < len(spans) and spans[entered][0] <= mid:
                s, e, n = spans[entered]
                heapq.heappush(opened, (e - s, n, e))
                entered += 1
            while opened and opened[0][2] <= mid:  # a span that has ended
                heapq.heappop(opened)              # leaves when it is on top
            acc[opened[0][1] if opened else "_no_benchmark_span_"] += b - a
    return [[n, v] for n, v in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def is_collective(name: str) -> bool:
    n = base_name(name)
    return any(n.startswith(c) for c in COLLECTIVES)


def exposed_collective_seconds(tr) -> float:
    """Device time in collective ops during which no other op runs on that
    device, averaged over devices."""
    per = []
    for ops in tr["devices"].values():
        compute = stats.merged([(s, e) for s, e, n, _ in ops
                                if not is_collective(n)])
        coll = stats.merged([(s, e) for s, e, n, _ in ops if is_collective(n)])
        per.append(sum((e - s) - stats.overlap_with(compute, s, e)
                       for s, e in coll))
    return sum(per) / len(per) if per else 0.0


def span_seconds_between(tr, span_name: str):
    """[(start, end)] of the named benchmark span, in trace order."""
    return [(s, e) for s, e, n in tr["spans"] if n == span_name]


def module_runs(tr, match):
    """Durations (seconds) of the program executions whose module name
    ``match`` accepts, on the first device."""
    if not tr["modules"]:
        return []
    runs = tr["modules"][sorted(tr["modules"])[0]]
    return [e - s for s, e, n in runs if match(n)]
