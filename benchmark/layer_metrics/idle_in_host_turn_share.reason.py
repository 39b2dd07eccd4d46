"""Per-layer metric ``idle_in_host_turn_share.reason`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.launches import idle_in_host_turn_share as read  # noqa: F401
