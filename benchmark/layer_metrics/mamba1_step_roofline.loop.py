"""Per-layer metric ``mamba1_step_roofline.loop`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers_phi4 import mamba1_step_roofline as read  # noqa: F401
