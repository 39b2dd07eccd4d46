"""Per-layer metric ``sparse_read_share.docs`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers_docs import sparse_read_share as read  # noqa: F401
