"""Per-layer metric ``admit_stall_p90_ms.chat`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.program_spans import admit_stall_p90_ms as read  # noqa: F401
