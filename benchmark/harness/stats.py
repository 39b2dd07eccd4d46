"""Percentiles and window arithmetic on plain lists of host timestamps."""

from __future__ import annotations

import math
from bisect import bisect_left, bisect_right


def percentile(values, q: float):
    """The q-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default 'linear' method), or None for no samples."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def tokens_in_window(stamps, t0: float, t1: float) -> int:
    """How many of the sorted emission times fall in [t0, t1): a token counts
    where it was emitted, whichever request it belongs to and whether or not
    that request started or finished inside the window."""
    return bisect_left(stamps, t1) - bisect_left(stamps, t0)


def gaps_in_window(per_request_stamps, t0: float, t1: float):
    """Gaps between consecutive output tokens of one request, for every gap
    whose later token was emitted in [t0, t1)."""
    out = []
    for stamps in per_request_stamps:
        for a, b in zip(stamps, stamps[1:]):
            if t0 <= b < t1:
                out.append(b - a)
    return out


def union_seconds(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals):
    """Sorted disjoint intervals covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def overlap_with(merged_ivals, s: float, e: float) -> float:
    """Seconds of [s, e) covered by the disjoint sorted ``merged_ivals``."""
    starts = [iv[0] for iv in merged_ivals]
    i = max(bisect_right(starts, s) - 1, 0)
    total = 0.0
    while i < len(merged_ivals) and merged_ivals[i][0] < e:
        total += max(0.0, min(e, merged_ivals[i][1]) - max(s, merged_ivals[i][0]))
        i += 1
    return total
