"""Per-layer metric ``mamba_step_share.reason`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers_mamba import mamba_step_share as read  # noqa: F401
