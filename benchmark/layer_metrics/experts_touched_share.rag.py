"""Per-layer metric ``experts_touched_share.rag`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers_window import experts_touched_share as read  # noqa: F401
