"""Every engine launch joined to its run on the device, the two clocks checked
by causality, and the device's idle time split by what it waited for: the
readers behind the ``program_span`` / ``device_trace`` metrics
``device_clock_skew_us``, ``idle_in_{program,host_turn,tail,launch}_share``,
``idle_engine_empty_share``, ``host_turn_p50_ms`` and ``host_stall_share``.

What is joined. ``serving.Engine`` counts its calls of a compiled executable
(``Engine._launch_i``) and the span open around a call carries the number as
``launch`` (``serving/decode/dispatch``, ``serving/admit/prefill`` /
``extend``; ``launches`` = how many where one span covers several, the
page copies under ``serving/decode/grow_pages``; ``eager`` = 1 where the span
covers a stretch of eager ops under one number, the sampler's under
``serving/admit/sample``). The span in which the host blocks on a result
carries ``waits_for`` = that launch's number. The reduced trace keeps every
program execution of the device's "XLA Modules" line (``modules``). Launches
in number order are walked against the first device's runs in start order,
kind against kind (``decode`` / ``verify``, ``prefill``, ``extend``,
``copy_page`` in the module's name; an eager stretch takes the runs of no such
kind that start inside its span). A run that began more than ``SLACK_S``
before the next launch's call did belongs to no launch of the stretch. Where
more than 1% of either side stays unjoined, the numbers have a hole or the
ring wrapped, every reader returns None and the line says which; a program
that numbers no launch (the parent of the PR that added this) gives None too.

The clock check. Write the device's clock as the host's + d. A run cannot
start before the call that launched it began, nor end after the host had its
result, so ``d_hi`` = min(run start - launch span start) and ``d_lo`` =
max(run end - ``waits_for`` span's end) bound d; runs the window's edge
clipped take no part. The correction d* is 0 where ``d_lo <= 0 <= d_hi``, else
the bound nearer 0 (``device_clock_skew_us`` = |d*|). ``d_lo > d_hi``: the
clocks contradict the order of events, and what needs d (the skew, tail,
launch) is None.

The split. The device's idle time is parted run by run and turn by turn (a
turn = one run's end to the next run's start, whatever the programs; the
stretch's first and last partial turns by the same rule):

* in program: a run's interval less the union of its ops (device clock);
* host turn: from the end of the span that waited for run i (the wait with
  the smallest ``waits_for`` >= its number: the device runs in order) to the
  start of the span that launched run i + 1, host clock only, clamped to
  [0, the turn's idle]: 0 once a later engine launches ahead of its fetch;
* empty engine: the part of that host time under no ``serving/`` span while
  nothing ran (the ``serving/step`` after it starts with ``running`` = 0 and
  the one before it started with ``waiting`` = 0; after the stretch's last
  step: its decode's ``running`` = its settle's ``finished``; or no step);
* tail + launch: the rest, known without d; d* parts it into tail (run i's
  end -> the host has the result) and launch (the call's start -> run i + 1
  starts).

The five sum to the first device's idle time by construction; the reader says
the sum against ``window - busy`` and the ten longest turns.
"""

from __future__ import annotations

import time
from bisect import bisect_left, bisect_right
from collections import Counter
from statistics import median

from . import program_spans, stats, trace

#: observability.tracing's ring holds this many spans: at it, it wrapped
RING = 65536
MAX_UNJOINED = 0.01
#: a run that began this long before a launch's call did is not that launch's
SLACK_S = 4e-3
#: a turn is a stall where its host part passes this many medians
STALL_MEDIANS = 10
PARTS = ("in_program", "host_turn", "empty", "tail", "launch")
KIND_OF_SPAN = {"serving/decode/dispatch": "decode",
                "serving/admit/prefill": "prefill",
                "serving/admit/extend": "extend",
                "serving/decode/grow_pages": "copy_page"}


def kind_of(module: str):
    """Which of the engine's compiled programs a module run is, by its name
    (``jit_paged_decode_fn(..)``, ``jit_verify_fn``: the speculative decode
    step); None for an eager op's program or an unknown one."""
    for k in ("copy_page", "decode", "verify", "prefill", "extend"):
        if k in module:
            return "decode" if k == "verify" else k
    return None


def join(launches, runs):
    """Launch spans ``[(number, how many, start, end, name, eager)]`` in
    number order against ``runs [(start, end, name)]`` in start order: ([run
    indices] a launch, launch index or None a run)."""
    got, owner, j = [[] for _ in launches], [None] * len(runs), 0
    for i, (_, count, s, e, name, eager) in enumerate(launches):
        while j < len(runs) and runs[j][0] < s - SLACK_S:
            j += 1                      # began before this call: not its run
        if eager:
            while (j < len(runs) and kind_of(runs[j][2]) is None
                   and runs[j][0] <= e + SLACK_S):
                got[i].append(j)
                j += 1
            continue
        want = KIND_OF_SPAN.get(name)
        while len(got[i]) < count and j < len(runs):
            kind = kind_of(runs[j][2])
            if want is not None and kind is not None and kind != want:
                break                   # a later launch's program
            if want is None or kind == want:
                got[i].append(j)
            j += 1                      # else: a name no launch claims
        if len(got[i]) < count:
            got[i] = []
    for i, mine in enumerate(got):
        for r in mine:
            owner[r] = i
    return got, owner


def _busy_in(busy, segments):
    """Seconds of each of the sorted disjoint ``segments`` that the sorted
    disjoint ``busy`` intervals cover: one sweep."""
    out, i = [], 0
    for a, b in segments:
        while i < len(busy) and busy[i][1] <= a:
            i += 1
        total, k = 0.0, i
        while k < len(busy) and busy[k][0] < b:
            total += min(b, busy[k][1]) - max(a, busy[k][0])
            k += 1
        out.append(total)
    return out


def _empty_gaps(spans, t0, t1):
    """[(start, end)] of the stretch under no ``serving/`` span in which the
    engine held no request (module docstring)."""
    cover = stats.merged([(s, e) for s, e, n, _ in spans
                          if n.startswith("serving/")])
    steps = [(s, e, a) for s, e, n, a in spans if n == "serving/step"]
    starts, out, cur = [s for s, _, _ in steps], [], t0
    for s, e in cover + [[t1, t1]]:
        if min(s, t1) > cur:
            k = bisect_left(starts, cur)    # steps[k] is the next one
            before = steps[k - 1][2] if k else None
            if k < len(steps):
                empty = steps[k][2].get("running") == 0
            else:
                empty = not steps or _left_running(spans, steps[-1]) == 0
            if empty and (before is None or before.get("waiting") == 0):
                out.append((cur, min(s, t1)))
        cur = max(cur, e)
    return out


def _left_running(spans, step) -> int:
    """Requests still running when ``step`` ended: its decode's ``running``
    less its settle's ``finished``."""
    inside = {n: a for s, e, n, a in spans if step[0] <= s and e <= step[1]}
    return (inside.get("serving/decode", {}).get("running", 0)
            - inside.get("serving/decode/settle", {}).get("finished", 0))


def _overlap(gaps, ends, a, b) -> float:
    """Seconds of [a, b) inside the sorted disjoint ``gaps`` (``ends``:
    their ends)."""
    total, i = 0.0, bisect_right(ends, a)
    while i < len(gaps) and gaps[i][0] < b:
        total += min(b, gaps[i][1]) - max(a, gaps[i][0])
        i += 1
    return total


def _clock(run, launches, got, runs, waits, t0, t1):
    """(d_lo, d_hi) over the stretch, said with each second's."""
    whole = lambda r: runs[r][0] > t0 and runs[r][1] < t1
    by_s = {}
    for (number, count, s, *_), mine in zip(launches, got):
        if not mine:
            continue
        sec = by_s.setdefault(int(s - t0), [float("-inf"), float("inf")])
        if whole(mine[0]):
            sec[1] = min(sec[1], runs[mine[0]][0] - s)
        last = number + count - 1
        if last in waits and whole(mine[-1]):
            sec[0] = max(sec[0], runs[mine[-1]][1] - waits[last])
    lo = max((v[0] for v in by_s.values()), default=float("-inf"))
    hi = min((v[1] for v in by_s.values()), default=float("inf"))
    run.say(f"clock check: device clock = host clock + d, {lo * 1e6:.1f} us "
            f"<= d <= {hi * 1e6:.1f} us over the stretch; by second "
            + ", ".join(f"{k}: [{v[0] * 1e6:.1f}, {v[1] * 1e6:.1f}]"
                        for k, v in sorted(by_s.items())))
    return lo, hi


def split(run):
    """The parts of the first device's idle time in seconds (``PARTS``; tail
    and launch None where the clocks contradict causality) with ``window``,
    ``turns`` (a turn's idle, its index, host turn, empty, tail, launch),
    ``skew_us``, ``host_turn_p50_ms`` and ``host_stall_share``, or None:
    said once, with the reason."""
    if not hasattr(run, "_launch_split"):
        t = time.perf_counter()
        run._launch_split = _split(run)
        if run._launch_split is not None:
            run.say("launch join, clock check and split read in "
                    f"{time.perf_counter() - t:.1f} s")
    return run._launch_split


def _split(run):
    spans = program_spans.view(run)
    if spans is None:
        return None
    tr = run.trace
    launches = sorted((a["launch"], a.get("launches", 1), s, e, n,
                       bool(a.get("eager"))) for s, e, n, a in spans
                      if "launch" in a)
    if not launches or not tr["modules"] or not tr["devices"]:
        run.say("launch join: no span carries a launch number or no device "
                "is in the trace: nothing to join")
        return None
    dev = sorted(tr["modules"])[0]
    runs = tr["modules"][dev]
    t0, t1 = tr["window"]
    got, owner = join(launches, runs)
    lost_runs = Counter(runs[r][2] for r, i in enumerate(owner) if i is None)
    lost = sum(1 for mine in got if not mine)
    contiguous = all(b[0] == a[0] + a[1]
                     for a, b in zip(launches, launches[1:]))
    ring = len(program_spans.ring())
    run.say(f"launch join: {len(runs) - sum(lost_runs.values())} of "
            f"{len(runs)} module runs joined, unjoined runs by name "
            f"{dict(lost_runs)}, unjoined launches {lost} of {len(launches)}, "
            f"numbers {launches[0][0]}..{launches[-1][0]} contiguous "
            f"{'yes' if contiguous else 'no'}; {ring} spans in the ring")
    why = [w for w, bad in (
        ("module runs unjoined", sum(lost_runs.values())
         > MAX_UNJOINED * len(runs)),
        ("launches unjoined", lost > MAX_UNJOINED * len(launches)),
        ("the launch numbers have a hole", not contiguous),
        ("the span ring was full and wrapped", ring >= RING)) if bad]
    if why:
        run.say("launch join: " + "; ".join(why) + ": no launch metric is "
                "reported")
        return None

    # ---------------------------------------------------- the two clocks
    waits = {a["waits_for"]: e for _, e, _, a in spans if "waits_for" in a}
    numbers = sorted(waits)
    lo, hi = _clock(run, launches, got, runs, waits, t0, t1)
    d = 0.0 if lo <= 0.0 <= hi else min((lo, hi), key=abs)
    sound = lo <= hi
    run.say(f"clock check: correction d* = {d * 1e6:.1f} us"
            + ("" if sound else "; the seconds' intervals have NO common "
               "point: tail and launch are not parted"))

    # ------------------------------------------------------------ the split
    busy = stats.merged([(s, e) for s, e, _, _ in tr["devices"][dev]])
    edges, cur = [], t0                 # runs that never overlap, in order
    for s, e, _ in runs:
        edges.append((max(s, cur), max(e, cur)))
        cur = edges[-1][1]
    in_program = sum(e - s for s, e in edges) - sum(_busy_in(busy, edges))
    turns = list(zip([t0] + [e for _, e in edges],
                     [s for s, _ in edges] + [t1]))
    idle = [b - a - c for (a, b), c in zip(turns, _busy_in(busy, turns))]
    gaps = _empty_gaps(spans, t0, t1)
    gap_ends = [e for _, e in gaps]
    parts, rows = dict.fromkeys(PARTS, 0.0), []
    parts["in_program"] = in_program
    for k, ((a, b), room) in enumerate(zip(turns, idle)):
        before = owner[k - 1] if k else None       # launch of the run before
        after = owner[k] if k < len(runs) else None
        known = t0 if k == 0 else None  # when the host had the last result
        if before is not None:
            number, count = launches[before][:2]
            w = bisect_left(numbers, number + count - 1)
            known = waits[numbers[w]] if w < len(numbers) else None
        called = t1 if k == len(runs) else (
            launches[after][2] if after is not None else None)
        host = empty = 0.0
        if known is not None and called is not None:
            host = min(max(called - known, 0.0), room)
            empty = min(_overlap(gaps, gap_ends, known, called), host)
        rest = room - host
        tail = min(max(known + d - a, 0.0), rest) \
            if k and known is not None else 0.0
        for name, v in (("host_turn", host - empty), ("empty", empty),
                        ("tail", tail), ("launch", rest - tail)):
            parts[name] += v
        rows.append((room, k, host - empty, empty, tail, rest - tail))

    window = trace.window_seconds(tr)
    total = sum(parts.values())
    device_idle = window - sum(e - s for s, e in busy)
    run.say("idle by launch, % of the stretch: "
            + ", ".join(f"{p} {100 * parts[p] / window:.3f}" for p in PARTS)
            + f"; sum {100 * total / window:.3f} against device idle "
            f"{100 * device_idle / window:.3f} (difference "
            f"{100 * (total - device_idle) / window:+.4f} points)"
            + ("" if sound else "; tail and launch stand on no clock"))
    decode = lambda r: kind_of(runs[r][2]) == "decode"
    between = [row[2] for row in rows if 0 < row[1] < len(runs)
               and decode(row[1] - 1) and decode(row[1])]
    p50 = median(between) if between else None
    stalls = sum(row[0] for row in rows
                 if p50 is not None and row[2] > STALL_MEDIANS * p50)
    run.say(f"host turn between two decode runs: median "
            f"{1e3 * (p50 or 0):.3f} ms over {len(between)} turns; idle in "
            f"turns whose host part passes {STALL_MEDIANS} medians "
            f"{stalls:.3f} s of {total:.3f}")
    number_of = lambda r: None if owner[r] is None else launches[owner[r]][0]
    for room, k, host, empty, tail, launch in sorted(rows, reverse=True)[:10]:
        mid = (turns[k][0] + turns[k][1]) / 2 - d
        over = min(((e - s, n) for s, e, n, _ in spans if s <= mid < e),
                   default=(0, "no span"))[1]
        run.say(f"  turn {1e3 * room:.3f} ms idle after launch "
                f"{number_of(k - 1) if k else None} before launch "
                f"{number_of(k) if k < len(runs) else None}: host "
                f"{1e3 * host:.3f}, empty {1e3 * empty:.3f}, tail "
                f"{1e3 * tail:.3f}, launch {1e3 * launch:.3f}; at its "
                f"middle under {over}")
    if not sound:
        parts["tail"] = parts["launch"] = None
    return dict(parts, window=window, turns=rows,
                skew_us=abs(d) * 1e6 if sound else None,
                host_turn_p50_ms=None if p50 is None else 1e3 * p50,
                host_stall_share=100.0 * stalls / total if total > 0
                else None)


def _share(part):
    def read(run):
        got = split(run)
        if not got or got[part] is None:
            return None
        return 100.0 * got[part] / got["window"]
    return read


def _value(key):
    def read(run):
        got = split(run)
        return got[key] if got else None
    return read


idle_in_program_share = _share("in_program")
idle_in_host_turn_share = _share("host_turn")
idle_engine_empty_share = _share("empty")
idle_in_tail_share = _share("tail")
idle_in_launch_share = _share("launch")
device_clock_skew_us = _value("skew_us")
host_turn_p50_ms = _value("host_turn_p50_ms")
host_stall_share = _value("host_stall_share")
