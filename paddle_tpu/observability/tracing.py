"""The one span primitive: host regions on the profiler's clock.

``with span(name, **attrs) as sp:`` records a region when
``FLAGS_observability`` is on **or** a jax profiler session is recording
(between ``jax.profiler.start_trace`` and ``stop_trace``). Otherwise it
returns a shared no-op after those two checks: no clock read, no event, no
registry entry.

A recorded span is one event dict in a bounded ring, written nowhere during
the run:

    {"name": "ir.pass{pass=cse}", "ts": <start, us>, "dur": <us>, "tid": ...,
     "id": 41, "parent": 40, "attrs": {"pass": "cse"}}

* ``ts``/``dur`` are ``time.perf_counter()`` microseconds; ``parent`` is the
  id of the span open on this thread when this one started (None at the top).
* Attributes are free. String-valued ones label the span: they are part of
  its ``name`` and of its ``<name>.seconds`` histogram series (``pass``,
  ``site``). Numbers are facts of this one span (``request_id``, ``step``,
  ``tokens``) and label nothing; ``sp.set(k=v)`` adds more before the span
  closes.
* While a profiler session records, the span is also a
  ``jax.profiler.TraceAnnotation`` of the same name, so it is a host event in
  the XPlane beside the device ops, on the device trace's clock.
* The ``<name>.seconds`` histogram is written only under the flag (the
  registry stays empty under a bare profiler session).
* The span is forwarded into ``profiler._HostEventRecorder`` — the SAME
  buffer ``profiler.RecordEvent`` writes — so an active ``profiler.Profiler``
  merges it into its ``export_chrome_tracing`` output, and to every span sink
  (the flight recorder).
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List

from jax.profiler import TraceAnnotation

from ..profiler.profiler import _recorder
from . import metrics

_MAX_SPANS = 65536
_spans: deque = deque(maxlen=_MAX_SPANS)
_lock = threading.Lock()
# consumers (the flight recorder) that want every finished span as it lands;
# mutated only under _lock, iterated on a local copy
_sinks: List[Callable[[Dict[str, Any]], None]] = []
_ids = itertools.count(1)
_local = threading.local()  # .stack: ids of the spans open on this thread


def add_span_sink(fn: Callable[[Dict[str, Any]], None]):
    """Register a callable invoked with every finished span event dict."""
    with _lock:
        if fn not in _sinks:
            _sinks.append(fn)


def remove_span_sink(fn: Callable[[Dict[str, Any]], None]):
    with _lock:
        if fn in _sinks:
            _sinks.remove(fn)


def set_max_spans(n: int):
    """Resize the bounded span ring (keeps the most recent entries)."""
    global _spans
    with _lock:
        _spans = deque(_spans, maxlen=max(1, int(n)))


def _labels(attrs: Dict[str, Any]) -> Dict[str, str]:
    """The string-valued attributes: the span's labels."""
    return {k: v for k, v in attrs.items() if isinstance(v, str)}


def _span_name(name: str, labels: Dict[str, Any]) -> str:
    if not labels:
        return name
    inner = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
    return f"{name}{{{inner}}}"


class _Off:
    """What ``span()`` returns when nothing records: enters, exits and
    ``set``s for free, reports zero seconds, and is false (a recording
    ``Span`` is true), so ``if sp:`` guards an attribute whose value costs
    something to compute."""

    __slots__ = ()
    seconds = 0.0

    def __bool__(self):
        return False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        pass


_OFF = _Off()


class Span:
    """One recording region (see the module docstring); ``seconds`` is its
    duration once closed."""

    __slots__ = ("name", "attrs", "id", "parent", "seconds", "_t0", "_ann")

    def __init__(self, name: str, attrs: Dict[str, Any], annotate: bool):
        self.name, self.attrs = name, attrs
        self.seconds = 0.0
        self._ann = (TraceAnnotation(_span_name(name, _labels(attrs)))
                     if annotate else None)

    def set(self, **attrs):
        self.attrs.update(attrs)

    def __enter__(self):
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        self.id = next(_ids)
        self.parent = stack[-1] if stack else None
        stack.append(self.id)
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t0, t1 = self._t0, time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _local.stack.pop()
        self.seconds = t1 - t0
        labels = _labels(self.attrs)
        metrics.histogram(f"{self.name}.seconds", t1 - t0, **labels)
        full = _span_name(self.name, labels)
        # no-ops unless a Profiler is in a RECORD state — the merge seam
        _recorder.record(full, t0, t1)
        event = {
            "name": full,
            "ts": t0 * 1e6,
            "dur": (t1 - t0) * 1e6,
            "tid": threading.get_ident() % 100000,
            "id": self.id,
            "parent": self.parent,
            "attrs": self.attrs,
        }
        with _lock:
            dropped = (_spans.maxlen is not None
                       and len(_spans) == _spans.maxlen)
            _spans.append(event)
            sinks = list(_sinks)
        if dropped:
            # the ring silently evicted its oldest span — make the loss
            # visible so long runs know the buffer undersized
            metrics.counter("obs.trace.dropped", 1)
        for sink in sinks:
            try:
                sink(event)
            except Exception:
                metrics.counter("obs.trace.sink_errors", 1)
        return False


def span(name: str, **attrs):
    """Context manager around a region: a recording ``Span`` under the flag
    or a profiler session, else the shared no-op (two checks)."""
    annotate = TraceAnnotation.is_enabled()
    if not (annotate or metrics.enabled()):
        return _OFF
    return Span(name, attrs, annotate)


def spans() -> List[Dict[str, Any]]:
    """Copy of the local span buffer (most recent _MAX_SPANS)."""
    with _lock:
        return list(_spans)


def clear_spans():
    with _lock:
        _spans.clear()
