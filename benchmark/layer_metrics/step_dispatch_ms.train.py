"""Per-layer metric ``step_dispatch_ms.train`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.program_spans import step_dispatch_ms as read  # noqa: F401
