"""Per-step training telemetry: tokens/sec, achieved FLOPs, MFU.

The shared arithmetic bench.py and the fleet training loops report through
instead of private computation — so every BENCH_*.json round and any training
loop derive MFU the same way from the same registry.
"""

from __future__ import annotations

from typing import Optional

from . import metrics
from .attribution import hardware_for_device


def peak_flops(device_kind: Optional[str] = None) -> float:
    """Per-chip peak FLOP/s the MFU denominator uses — read from
    ``attribution.HW_SPECS`` (the roofline table), so MFU and the
    roofline floors can never quote different peaks for one device
    (a pin test in tests/test_attribution.py holds them equal). Defaults
    to the device in use; a device without a row raises."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    return hardware_for_device(device_kind).peak_flops


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


def record_window(*, tokens: Optional[int] = None,
                  seconds: Optional[float] = None,
                  flops: Optional[float] = None,
                  peak: Optional[float] = None,
                  tokens_per_sec: Optional[float] = None,
                  mfu: Optional[float] = None, **labels):
    """Aggregate telemetry for a timed window of steps: derives (or accepts
    pre-computed) throughput and MFU gauges.

    bench.py field mapping: ``value``/``tokens_per_sec`` ->
    ``train.tokens_per_sec``, ``mfu`` -> ``train.mfu``, achieved FLOP/s ->
    ``train.achieved_flops``."""
    if not metrics.enabled():
        return
    if tokens_per_sec is None and tokens and seconds:
        tokens_per_sec = tokens / seconds
    if tokens_per_sec is not None:
        metrics.gauge("train.tokens_per_sec", tokens_per_sec, **labels)
    achieved = flops / seconds if (flops and seconds) else None
    if achieved is not None:
        metrics.gauge("train.achieved_flops", achieved, **labels)
    if mfu is None and achieved is not None:
        mfu = achieved / (peak if peak else peak_flops())
    if mfu is not None:
        metrics.gauge("train.mfu", mfu, **labels)
