"""Per-layer metric ``gdn_chunk_roofline.hybrid`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers_hybrid import gdn_chunk_roofline as read  # noqa: F401
