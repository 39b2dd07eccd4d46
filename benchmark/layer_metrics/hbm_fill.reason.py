"""Per-layer metric ``hbm_fill.reason`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers import hbm_fill as read  # noqa: F401
