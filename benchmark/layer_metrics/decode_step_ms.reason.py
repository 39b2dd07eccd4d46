"""Per-layer metric ``decode_step_ms.reason`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers import decode_step_ms as read  # noqa: F401
