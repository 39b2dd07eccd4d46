"""Per-layer metric ``device_idle_share.reason`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers import device_idle_share as read  # noqa: F401
