"""Per-layer metric ``tok_gap_p95_ms.chat`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers import tok_gap_p95_ms as read  # noqa: F401
