"""``benchmark/harness/shared_walk.py`` (the reader of
``shared_walk_share.latent``) in the driver's own suite: every case of
``benchmark/tests/test_shared_walk_reader.py`` collected here too, by
import, as ``tests/test_benchmark_sampler.py`` does for the sampler's
reader. Nothing here is a device measurement."""

import os
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
for p in (BENCH, os.path.join(BENCH, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

from test_shared_walk_reader import *  # noqa: E402,F401,F403
