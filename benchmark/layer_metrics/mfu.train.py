"""Per-layer metric ``mfu.train`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers import mfu as read  # noqa: F401
