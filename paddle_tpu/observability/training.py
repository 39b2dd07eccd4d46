"""Per-step training telemetry: step, sample and token counts.

Utilisation is not derived here: host dispatch time says nothing about the
device, so MFU comes from the benchmark's device-side count (PERF.md §3).
"""

from __future__ import annotations

from typing import Optional

from . import metrics


def record_step(*, seconds: Optional[float] = None,
                samples: Optional[int] = None,
                tokens: Optional[int] = None, **labels):
    """One training step dispatched (fleet ShardedTrainStep calls this)."""
    if not metrics.enabled():
        return
    metrics.counter("train.steps", 1, **labels)
    if seconds is not None:
        metrics.histogram("train.step.seconds", seconds, **labels)
    if samples:
        metrics.counter("train.samples", samples, **labels)
    if tokens:
        metrics.counter("train.tokens", tokens, **labels)
