"""Per-layer metric ``step_ms.train`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers import step_ms as read  # noqa: F401
