"""Per-layer metric ``latent_decode_share.latent`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers_latent import latent_decode_share as read  # noqa: F401
