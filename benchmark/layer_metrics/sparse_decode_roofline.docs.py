"""Per-layer metric ``sparse_decode_roofline.docs`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers_docs import sparse_decode_roofline as read  # noqa: F401
