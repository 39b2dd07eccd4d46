"""Per-layer metric ``window_pool_fill.rag`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers_window import window_pool_fill as read  # noqa: F401
