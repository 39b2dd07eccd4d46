"""Per-layer metric ``moe_experts_roofline.rag`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers_window import moe_experts_roofline as read  # noqa: F401
