"""Per-layer metric ``kda_decode_roofline.agents`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers_solar import kda_decode_roofline as read  # noqa: F401
