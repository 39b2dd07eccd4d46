"""The ``serve_latent`` runner, the ``.latent`` readers,
``benchmark/roofline/latent_decode.py`` and the DeepSeek-V3 configuration
file in the driver's own suite: every case of
``benchmark/tests/test_drive_latent.py`` collected here too, by import, as
``tests/test_benchmark_sampler.py`` does for the sampler's reader. Nothing
here is a device measurement."""

import os
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
for p in (BENCH, os.path.join(BENCH, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

from test_drive_latent import *  # noqa: E402,F401,F403
