"""Per-layer metric ``host_turn_p50_ms.chat`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.launches import host_turn_p50_ms as read  # noqa: F401
