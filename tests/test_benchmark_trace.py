"""The trace reduction's sweep in the driver's own suite: every case of
``benchmark/tests/test_trace_sweep.py`` (``idle_gaps_by_span`` and
``reduce_planes`` against the bodies they replaced at PR 37, ``==`` on lists,
dicts and floats) collected here too, by import: ``python -m pytest
benchmark/tests`` is not among the tier-1 commands, and every traced run of
every cell stands on these two functions. Nothing here is a device
measurement."""

import os
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
for p in (BENCH, os.path.join(BENCH, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

from test_trace_sweep import *  # noqa: E402,F401,F403
