"""Per-layer metric ``queue_wait_p90_ms.chat`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.program_spans import queue_wait_p90_ms as read  # noqa: F401
