"""``benchmark/harness/readers_extend.py`` (the reader of
``extend_flash_roofline.rag``) and ``benchmark/roofline/extend_flash.py`` in
the driver's own suite: every case of
``benchmark/tests/test_extend_reader.py`` collected here too, by import, as
``tests/test_benchmark_shared_walk.py`` does for the shared walk's reader.
Nothing here is a device measurement."""

import os
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
for p in (BENCH, os.path.join(BENCH, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

from test_extend_reader import *  # noqa: E402,F401,F403
