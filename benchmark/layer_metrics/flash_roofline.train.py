"""Per-layer metric ``flash_roofline.train`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers import flash_roofline as read  # noqa: F401
