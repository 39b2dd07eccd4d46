"""Per-layer metric ``experts_touched_share.reason`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers_mamba import experts_touched_share as read  # noqa: F401
