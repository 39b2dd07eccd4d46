"""Per-layer metric ``sampler_draw_share.reason`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.sampler import sampler_draw_share as read  # noqa: F401
