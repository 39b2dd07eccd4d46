"""The ``serve_phi4`` runner, the ``.loop`` readers,
``benchmark/roofline/shared_kv_decode.py`` / ``mamba1_step.py`` /
``mamba1_scan.py`` and the Phi-4-mini-flash configuration file in the
driver's own suite: every case of ``benchmark/tests/test_drive_phi4.py``
collected here too, by import, as ``tests/test_benchmark_mamba.py`` does for
the reasoning cell's. Nothing here is a device measurement."""

import os
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
for p in (BENCH, os.path.join(BENCH, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

from test_drive_phi4 import *  # noqa: E402,F401,F403
