"""Per-layer metric ``host_stall_share.chat`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.launches import host_stall_share as read  # noqa: F401
