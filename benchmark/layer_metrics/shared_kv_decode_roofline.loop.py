"""Per-layer metric ``shared_kv_decode_roofline.loop`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers_phi4 import shared_kv_decode_roofline as read  # noqa: F401
