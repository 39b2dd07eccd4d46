"""Per-layer metric ``prefix_hit_share.reason`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers import prefix_hit_share as read  # noqa: F401
