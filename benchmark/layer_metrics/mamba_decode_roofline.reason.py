"""Per-layer metric ``mamba_decode_roofline.reason`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers_mamba import mamba_decode_roofline as read  # noqa: F401
