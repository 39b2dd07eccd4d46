"""Per-layer metric ``exposed_collective_share.train`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers import exposed_collective_share as read  # noqa: F401
