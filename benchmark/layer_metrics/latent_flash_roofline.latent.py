"""Per-layer metric ``latent_flash_roofline.latent`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers_latent import latent_flash_roofline as read  # noqa: F401
