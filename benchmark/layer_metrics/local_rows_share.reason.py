"""Per-layer metric ``local_rows_share.reason`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers_solar import local_rows_share as read  # noqa: F401
