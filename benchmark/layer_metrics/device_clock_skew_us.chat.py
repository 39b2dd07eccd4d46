"""Per-layer metric ``device_clock_skew_us.chat`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.launches import device_clock_skew_us as read  # noqa: F401
