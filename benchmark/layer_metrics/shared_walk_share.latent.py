"""Per-layer metric ``shared_walk_share.latent`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.shared_walk import shared_walk_share as read  # noqa: F401
