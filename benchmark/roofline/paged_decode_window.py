"""Required work of one paged-decode attention call UNDER A WINDOW with
grouped heads (one sliding layer, one decode step; the named kernel
``window_decode``), the same whatever implements it: each running slot's
single query attends to the last ``min(context, window)`` tokens of its
context, so the call reads K and V of those tokens once at their STORED
width (``H_kv`` heads of ``D``) and does two length-``D`` multiply-adds per
QUERY head per token (q.k and p.v); each slot's query goes in and its
output comes out once. A kernel fetches whole pages, so it reads at least
this: the unfilled part of the page the window starts in, tokens of free
slots and everything behind the window are not required work, and a kernel
that walked the whole context would be timed against the window's bytes
alone (it reads a quarter of the share at four windows of context)."""

from .flash import min_seconds  # noqa: F401


def call(window_tokens, slot_steps, Hq, Hkv, D, itemsize=2):
    """``window_tokens``: ``min(context, window)`` summed over the running
    slots (and layer-steps); ``slot_steps``: running slots summed over
    layer-steps."""
    return {"flops": 4.0 * window_tokens * Hq * D,
            "bytes": (2.0 * window_tokens * Hkv * D
                      + 2.0 * slot_steps * Hq * D) * itemsize}
