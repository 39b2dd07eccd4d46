"""The ``serve_mamba`` runner, the ``.reason`` readers,
``benchmark/roofline/mamba2_step.py`` / ``moe_experts_relu2.py`` and the
Nemotron 3 Nano configuration file in the driver's own suite: every case of
``benchmark/tests/test_drive_mamba.py`` collected here too, by import, as
``tests/test_benchmark_window.py`` does for the window cell's. Nothing here
is a device measurement."""

import os
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
for p in (BENCH, os.path.join(BENCH, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

from test_drive_mamba import *  # noqa: E402,F401,F403
