"""Per-layer metric ``tpot_p90_ms.prefix`` (layer, unit, source, moves and cells: its
entry in BENCHMARK.json). Returns None where it finds nothing to read."""

from harness.readers import tpot_p90_ms as read  # noqa: F401
