"""The ``serve_window`` runner, the ``.rag`` readers,
``benchmark/roofline/paged_decode_window.py`` and the Command A+
configuration file in the driver's own suite: every case of
``benchmark/tests/test_drive_window.py`` collected here too, by import, as
``tests/test_benchmark_latent.py`` does for the latent cell's. Nothing here
is a device measurement."""

import os
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
for p in (BENCH, os.path.join(BENCH, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

from test_drive_window import *  # noqa: E402,F401,F403
