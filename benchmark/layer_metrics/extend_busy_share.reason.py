"""Per-layer metric ``extend_busy_share.reason`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers import prefill_busy_share as read  # noqa: F401
