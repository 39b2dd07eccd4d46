"""Process-global metrics registry: counters, gauges, histograms with labels.

The measurement substrate every perf/robustness PR reports through (GSPMD /
EQuARX attribute their wins via per-collective byte accounting and compiler
pass statistics; this is the same idea as a framework service). Everything is
off by default behind ``FLAGS_observability`` (core/flags.py): a disabled
call site reduces to one flag check and the registry stays empty, so tier-1
timing is unaffected.

Metric naming scheme (see observability/README.md):

    <layer>.<subject>.<measure>{label=value,...}

e.g. ``ir.pass.seconds{pass=cse}``, ``dist.collective.bytes{op=ppermute}``,
``jit.compile.cache_miss{site=sharded_train_step}``,
``train.step.dispatch_seconds``.

Thread safety: all mutation and the snapshot/reset API take one lock;
snapshots are deep copies so a caller can never observe a half-updated
histogram.
"""

from __future__ import annotations

import bisect
import json
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ..core.flags import flag_value, register_flag, set_flags

register_flag(
    "observability", False,
    "Enable the runtime telemetry substrate (metrics registry + span "
    "tracer). Off by default: instrumented sites reduce to one flag check "
    "and the registry stays empty")


def enabled() -> bool:
    """One-flag gate every instrumented call site checks first."""
    return bool(flag_value("observability"))


def enable() -> None:
    set_flags({"observability": True})


def disable() -> None:
    set_flags({"observability": False})


# label sets are stored canonicalized: a sorted tuple of (key, str(value))
_LabelKey = Tuple[Tuple[str, str], ...]
_MetricKey = Tuple[str, _LabelKey]

# latency-oriented decade buckets (seconds): le-style upper bounds.
# aggregate.py (which must stay stdlib-only) mirrors this constant; a test
# asserts the two stay equal.
_BUCKET_BOUNDS = tuple(10.0 ** e for e in range(-7, 4))
BUCKET_BOUNDS = _BUCKET_BOUNDS


def _labels_key(labels: Dict[str, Any]) -> _LabelKey:
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


def _render_key(name: str, labels: _LabelKey) -> str:
    if not labels:
        return name
    return name + "{" + ",".join(f"{k}={v}" for k, v in labels) + "}"


class _Hist:
    __slots__ = ("count", "total", "min", "max", "buckets")

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.min = float("inf")
        self.max = float("-inf")
        self.buckets = [0] * (len(_BUCKET_BOUNDS) + 1)

    def observe(self, value: float):
        self.count += 1
        self.total += value
        self.min = min(self.min, value)
        self.max = max(self.max, value)
        self.buckets[bisect.bisect_left(_BUCKET_BOUNDS, value)] += 1

    def percentile(self, q: float) -> float:
        """Estimate the q-quantile (0..1) from the decade buckets: find the
        bucket the rank falls in, interpolate linearly inside it, clamp to
        the observed [min, max] so single-bucket histograms stay exact-ish."""
        if not self.count:
            return 0.0
        target = q * self.count
        cum = 0
        for i, n in enumerate(self.buckets):
            if not n:
                continue
            if cum + n >= target:
                lo = _BUCKET_BOUNDS[i - 1] if i > 0 else 0.0
                hi = (_BUCKET_BOUNDS[i] if i < len(_BUCKET_BOUNDS)
                      else self.max)
                lo = max(lo, self.min)
                hi = min(hi, self.max)
                if hi < lo:
                    hi = lo
                return lo + (hi - lo) * ((target - cum) / n)
            cum += n
        return self.max

    def as_dict(self) -> Dict[str, Any]:
        return {
            "count": self.count,
            "sum": self.total,
            "avg": self.total / self.count if self.count else 0.0,
            "min": self.min if self.count else 0.0,
            "max": self.max if self.count else 0.0,
            "p50": self.percentile(0.50),
            "p95": self.percentile(0.95),
            "p99": self.percentile(0.99),
            "buckets": list(self.buckets),
        }


class MetricsRegistry:
    """Counters / gauges / histograms keyed by (name, labels)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[_MetricKey, float] = {}
        self._gauges: Dict[_MetricKey, float] = {}
        self._hists: Dict[_MetricKey, _Hist] = {}

    # -- mutation (callers gate on enabled(); these never gate themselves so
    #    tests can drive the registry directly) --
    def counter(self, name: str, value: float = 1, **labels):
        key = (name, _labels_key(labels))
        with self._lock:
            self._counters[key] = self._counters.get(key, 0) + value

    def gauge(self, name: str, value: float, **labels):
        with self._lock:
            self._gauges[(name, _labels_key(labels))] = value

    def histogram(self, name: str, value: float, **labels):
        key = (name, _labels_key(labels))
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                h = self._hists[key] = _Hist()
            h.observe(value)

    # -- read side --
    def snapshot(self, reset: bool = False) -> Dict[str, Dict[str, Any]]:
        """{'counters': {key: v}, 'gauges': {...}, 'histograms': {...}} with
        rendered ``name{label=value}`` keys; a deep copy, isolated from
        later mutation. ``reset=True`` atomically clears after copying."""
        with self._lock:
            out = {
                "counters": {_render_key(*k): v
                             for k, v in self._counters.items()},
                "gauges": {_render_key(*k): v
                           for k, v in self._gauges.items()},
                "histograms": {_render_key(*k): h.as_dict()
                               for k, h in self._hists.items()},
            }
            if reset:
                self._counters.clear()
                self._gauges.clear()
                self._hists.clear()
        return out

    def records(self) -> List[Dict[str, Any]]:
        """Structured (labels kept as a dict) records, for JSON-lines."""
        with self._lock:
            recs: List[Dict[str, Any]] = []
            for (name, labels), v in self._counters.items():
                recs.append({"type": "counter", "name": name,
                             "labels": dict(labels), "value": v})
            for (name, labels), v in self._gauges.items():
                recs.append({"type": "gauge", "name": name,
                             "labels": dict(labels), "value": v})
            for (name, labels), h in self._hists.items():
                recs.append({"type": "histogram", "name": name,
                             "labels": dict(labels), **h.as_dict()})
        return sorted(recs, key=lambda r: (r["type"], r["name"],
                                           sorted(r["labels"].items())))

    def hist_totals(self, name: str) -> Tuple[float, int]:
        """(sum, count) across every label set of one histogram name — the
        cheap delta source goodput.py polls every step."""
        total, count = 0.0, 0
        with self._lock:
            for (n, _), h in self._hists.items():
                if n == name:
                    total += h.total
                    count += h.count
        return total, count

    def reset(self):
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()

    def __len__(self):
        with self._lock:
            return len(self._counters) + len(self._gauges) + len(self._hists)


_registry = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    return _registry


# -- module-level API: the flag-gated face instrumentation sites call --
def counter(name: str, value: float = 1, **labels):
    if enabled():
        _registry.counter(name, value, **labels)


def gauge(name: str, value: float, **labels):
    if enabled():
        _registry.gauge(name, value, **labels)


def histogram(name: str, value: float, **labels):
    if enabled():
        _registry.histogram(name, value, **labels)


def snapshot(reset: bool = False) -> Dict[str, Dict[str, Any]]:
    return _registry.snapshot(reset=reset)


def hist_totals(name: str) -> Tuple[float, int]:
    return _registry.hist_totals(name)


def reset():
    _registry.reset()


def dump_jsonl(path: str, reset: bool = False) -> str:
    """Write one JSON object per metric (tools/metrics_dump.py reads this)."""
    ts = time.time()
    with open(path, "w") as f:
        for rec in _registry.records():
            f.write(json.dumps({**rec, "ts": ts}) + "\n")
    if reset:
        _registry.reset()
    return path


def summary() -> str:
    """Text table of the live registry (profiler.summary() analog)."""
    snap = _registry.snapshot()
    lines = []
    if snap["counters"]:
        lines.append(f"{'Counter':<56}{'Value':>16}")
        lines.append("-" * 72)
        for k in sorted(snap["counters"]):
            v = snap["counters"][k]
            sv = f"{v:.6g}" if isinstance(v, float) and v != int(v) else f"{int(v)}"
            lines.append(f"{k[:55]:<56}{sv:>16}")
    if snap["gauges"]:
        if lines:
            lines.append("")
        lines.append(f"{'Gauge':<56}{'Value':>16}")
        lines.append("-" * 72)
        for k in sorted(snap["gauges"]):
            lines.append(f"{k[:55]:<56}{snap['gauges'][k]:>16.6g}")
    if snap["histograms"]:
        if lines:
            lines.append("")
        lines.append(f"{'Histogram':<46}{'Count':>8}{'Sum':>12}"
                     f"{'Avg':>12}{'Min':>12}{'Max':>12}")
        lines.append("-" * 102)
        for k in sorted(snap["histograms"]):
            h = snap["histograms"][k]
            lines.append(
                f"{k[:45]:<46}{h['count']:>8}{h['sum']:>12.6g}"
                f"{h['avg']:>12.6g}{h['min']:>12.6g}{h['max']:>12.6g}")
    return "\n".join(lines) if lines else "(registry empty)"
