"""Per-layer metric ``idle_engine_empty_share.chat`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.launches import idle_engine_empty_share as read  # noqa: F401
