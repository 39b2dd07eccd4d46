"""Per-layer metric ``cross_rows_share.loop`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers_phi4 import cross_rows_share as read  # noqa: F401
