"""Readers of the program's OWN spans (``paddle_tpu.observability.tracing``:
``serving/step`` and its phases, ``train/step``, ``compile``), for the
per-layer metrics whose source is ``program_span``.

The program records its spans in a ring on ``time.perf_counter()`` while the
profiler session of a traced run is open. They are read here after the
window, moved onto the trace's clock by the one pair both clocks hold (the
``bench/window`` annotation), and that offset is checked against every
``bench/engine_step`` / ``bench/train_step`` pair: where the worst residual
passes 0.2 ms every reader returns None and the line says why. A program that
records no such span (the parent of the PR that added them) gives None too.

Idle by program phase puts the moved spans in place of the benchmark's own in
a copy of the reduced trace and calls ``trace.idle_gaps_by_span`` (innermost
span wins, uncovered time goes to its catch-all) — slice by slice of the
window: the one sweep of that function needs no slices, but the order in which
the pieces are summed, and so every share's last digits, follows them.
"""

from __future__ import annotations

import math
from bisect import bisect_left
from collections import defaultdict
from statistics import median

from . import stats, trace

MAX_RESIDUAL_S = 0.2e-3
SLICE_S = 0.1
PAIR_SPANS = ("bench/engine_step", "bench/train_step")
#: idle_gaps_by_span's name for time under no span
OUTSIDE = "_no_benchmark_span_"
#: the phases the idle shares are reported by; a span that is none of the
#: others is the rest of the step's own time and goes to "settle"
PHASES = ("upload", "dispatch", "fetch", "settle", "admit", "outside")


def ring():
    """The program's recorded spans as (start, end, name, attrs), seconds on
    ``perf_counter``; [] where the program keeps none."""
    try:
        from paddle_tpu.observability import tracing

        events = tracing.spans()
    except (ImportError, AttributeError):  # a program without the ring
        return []
    return [(e["ts"] * 1e-6, (e["ts"] + e["dur"]) * 1e-6, e["name"],
             e["attrs"]) for e in events if "attrs" in e]


def clock_residual(offset: float, host_spans: dict, tr) -> tuple:
    """(pairs matched, worst |host start + offset - trace start|) over the
    benchmark's own step spans, which both clocks hold."""
    n, worst = 0, 0.0
    for name in PAIR_SPANS:
        starts = sorted(s for s, _ in host_spans.get(name, ()))
        if not starts:
            continue
        for s, _ in trace.span_seconds_between(tr, name):
            want = s - offset
            i = bisect_left(starts, want)
            near = min(abs(x - want) for x in starts[max(i - 1, 0):i + 1])
            n, worst = n + 1, max(worst, near)
    return n, worst


def view(run):
    """The program's spans of the traced stretch on the trace's clock,
    [(start, end, name, attrs)] sorted, or None (said once, with the reason):
    untraced run, nothing recorded, or a clock that cannot be trusted."""
    if not hasattr(run, "_program_spans"):
        run._program_spans = _view(run)
    return run._program_spans


def _view(run):
    if run.trace is None or not run.spans.get("bench/window"):
        return None
    t0, t1 = run.trace["window"]
    offset = t0 - run.spans["bench/window"][0][0]
    recorded = ring()
    moved = sorted((s + offset, e + offset, n, a) for s, e, n, a in recorded
                   if e + offset > t0 and s + offset < t1)
    if not moved:
        run.say(f"program spans: none in the traced stretch ({len(recorded)} "
                "in the ring): this program records none")
        return None
    pairs, worst = clock_residual(offset, run.spans, run.trace)
    run.say(f"program spans: {len(moved)} in the traced stretch "
            f"({len(recorded)} in the ring); clock offset checked on "
            f"{pairs} benchmark span pairs, worst residual "
            f"{worst * 1e6:.1f} us (limit {MAX_RESIDUAL_S * 1e6:.0f})")
    if not pairs or worst > MAX_RESIDUAL_S:
        run.say("program spans: the two clocks do not line up; no "
                "program_span metric is reported")
        return None
    for s, e, n, a in moved:
        if n.startswith("compile"):
            run.say(f"program spans: COMPILE inside the window, site "
                    f"{a.get('site')}, {e - s:.3f} s")
    return moved


def phase_of(name: str) -> str:
    if name == OUTSIDE:
        return "outside"
    if name.startswith("serving/admit"):
        return "admit"
    leaf = name.rsplit("/", 1)[-1]
    if name.startswith("serving/decode/") and leaf in PHASES[:3]:
        return leaf
    return "settle"


def idle_by_span(tr, spans):
    """{span name: idle seconds of the first device under it}: the spans in
    place of the benchmark's own, ``trace.idle_gaps_by_span`` on each slice
    of the window."""
    t0, t1 = tr["window"]
    dev = sorted(tr["devices"])[0] if tr["devices"] else None
    busy = stats.merged([(s, e) for s, e, _, _ in tr["devices"].get(dev, ())])
    ends = [e for _, e in busy]
    n = max(1, math.ceil((t1 - t0) / SLICE_S))
    edges = [t0 + (t1 - t0) * i / n for i in range(n)] + [t1]
    acc = defaultdict(float)
    for a, b in zip(edges, edges[1:]):
        ops, i = [], bisect_left(ends, a)
        while i < len(busy) and busy[i][0] < b:
            ops.append((max(busy[i][0], a), min(busy[i][1], b), "", ""))
            i += 1
        piece = {"window": (a, b), "devices": {"device": ops},
                 "spans": [sp for sp in spans if sp[1] > a and sp[0] < b]}
        for name, sec in trace.idle_gaps_by_span(piece, k=len(spans) + 1):
            acc[name] += sec
    return dict(acc)


def idle_by_phase(run):
    """{phase: idle seconds} over PHASES, or None. Says the split by span
    name and how far the phases' sum is from the device's idle time."""
    if not hasattr(run, "_idle_by_phase"):
        run._idle_by_phase = _idle_by_phase(run)
    return run._idle_by_phase


def _idle_by_phase(run):
    spans = view(run)
    if spans is None:
        return None
    tr = run.trace
    # a compile span is transparent here: its time is its phase's
    by_name = idle_by_span(tr, [(s, e, n) for s, e, n, _ in spans
                                if not n.startswith("compile")])
    out = dict.fromkeys(PHASES, 0.0)
    for name, sec in by_name.items():
        out[phase_of(name)] += sec
    win = trace.window_seconds(tr)
    steps = sum(1 for sp in spans if sp[2] == "serving/step") or 1
    table = ", ".join(f"{n} {1e3 * v / steps:.3f}" for n, v in
                      sorted(by_name.items(), key=lambda kv: -kv[1]))
    idle = win - trace.busy_seconds(tr)
    run.say(f"idle by program span, ms a step over {steps} steps: {table}")
    run.say("idle by phase, % of the stretch: "
            + ", ".join(f"{p} {100 * out[p] / win:.3f}" for p in PHASES)
            + f"; sum {100 * sum(out.values()) / win:.3f} against device "
            f"idle {100 * idle / win:.3f} (difference "
            f"{100 * (sum(out.values()) - idle) / win:+.4f} points)")
    return out


def _idle_share(phase):
    def read(run):
        by = idle_by_phase(run)
        win = trace.window_seconds(run.trace) if by else 0.0
        return 100.0 * by[phase] / win if win > 0 else None
    return read


idle_in_upload_share = _idle_share("upload")
idle_in_dispatch_share = _idle_share("dispatch")
idle_in_fetch_share = _idle_share("fetch")
idle_in_settle_share = _idle_share("settle")
idle_in_admit_share = _idle_share("admit")
idle_outside_step_share = _idle_share("outside")


def _admissions(run):
    """The stretch's ``serving/admit`` spans that admitted (a blocked
    attempt has no ``queued_s``)."""
    return [sp for sp in view(run) or ()
            if sp[2] == "serving/admit" and "queued_s" in sp[3]]


def queue_wait_p90_ms(run):
    return stats.percentile(
        [1e3 * a["queued_s"] for _, _, _, a in _admissions(run)], 90)


def admit_stall_p90_ms(run):
    return stats.percentile(
        [1e3 * (e - s) for s, e, _, _ in _admissions(run)], 90)


def prefill_pad_share(run):
    work = [a for _, _, n, a in view(run) or ()
            if n in ("serving/admit/prefill", "serving/admit/extend")]
    bucket = sum(a["bucket"] for a in work)
    if not bucket:
        return None
    return 100.0 * (1.0 - sum(a["tokens"] for a in work) / bucket)


def step_dispatch_ms(run):
    steps = [e - s for s, e, n, _ in view(run) or () if n == "train/step"]
    return 1e3 * median(steps) if steps else None
