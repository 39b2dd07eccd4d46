"""Per-layer metric ``snapshot_pool_fill.reason`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers_hybrid import snapshot_pool_fill as read  # noqa: F401
