"""Per-layer metric ``paged_decode_roofline.rag`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers_window import paged_decode_roofline as read  # noqa: F401
