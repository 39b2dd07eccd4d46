"""Per-layer metric ``recomputed_token_share.reason`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers_hybrid import recomputed_token_share as read  # noqa: F401
