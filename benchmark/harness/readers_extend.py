"""Reader of ``extend_flash_roofline.rag``: the extend-attention kernels'
share of their roofline in a model of sliding and full layers on head-major
pools.

The admissions are the program's ``serving/admit/extend`` spans of the
traced stretch (``tokens``, ``start``); device time is the trace's, the
kernels found by their NAME (``extend_flash``, which ``window_extend_flash``
holds too), never by the shapes of ops around them. A program with no such
kernel (the parent of the PR that brought it), an untraced run, or a
configuration of another kind gives None, never an error."""

from __future__ import annotations

import sys

from . import program_spans, trace
from .common import BENCH
from .readers import _share

sys.path.insert(0, BENCH)
from roofline import extend_flash  # noqa: E402

KERNEL = "extend_flash"


def extend_flash_roofline(run):
    """Required seconds (``roofline/extend_flash.py``: every real query of
    every extend against the keys it sees, a full layer all before it, a
    sliding layer its window) over the named kernels' device time."""
    c = run.config
    if run.trace is None or run.trace_host is None \
            or "sliding_window" not in c or "layer_types" not in c:
        return None
    ta, tb = run.trace_host
    pieces = [a for s, e, n, a in program_spans.ring()
              if n.split("{")[0] == "serving/admit/extend"
              and ta <= s and e <= tb and "start" in a]
    secs = trace.op_seconds(run.trace, lambda n: KERNEL in n)
    if not pieces or secs <= 0:
        return None
    shape = (c["num_attention_heads"], c["num_key_value_heads"],
             c["head_dim"])
    layers = ((c["layer_types"].count("full_attention"), None),
              (c["layer_types"].count("sliding_attention"),
               c["sliding_window"]))
    work = {"flops": 0.0, "bytes": 0.0}
    for a in pieces:
        for n, window in layers:
            w = extend_flash.call(a["tokens"], a["start"], *shape, window)
            for k in work:
                work[k] += n * w[k]
    t, bound = extend_flash.min_seconds(work, run.peaks)
    return _share(run, t, secs, bound, KERNEL)
