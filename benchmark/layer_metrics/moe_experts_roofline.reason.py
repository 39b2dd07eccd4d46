"""Per-layer metric ``moe_experts_roofline.reason`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers_mamba import moe_experts_roofline as read  # noqa: F401
