"""Per-layer metric ``fused_adamw_roofline.train`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers import fused_adamw_roofline as read  # noqa: F401
