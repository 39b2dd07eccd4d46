"""Per-layer metric ``extend_flash_roofline.rag`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers_extend import extend_flash_roofline as read  # noqa: F401
