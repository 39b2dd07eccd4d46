"""Per-layer metric ``batch_occupancy.reason`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers import batch_occupancy as read  # noqa: F401
