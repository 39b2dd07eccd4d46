"""Readers of the ``.rag`` per-layer metrics that the other readers have no
function for: the windowed and the full paged-decode kernels' shares of
their rooflines, what the window saves a step's attention, how full the
window group's pool stands, and what a chip that holds SOME of a layer's
experts did with the rows it routed (``readers_solar``'s three, under this
configuration's keys).

The counts come from the attributes the program puts on its
``serving/decode`` span: per layer ``window_tokens_read`` /
``full_tokens_read`` (the cached tokens a sliding layer and a full layer
attended, summed over the live slots) and ``experts_touched``; a step
``running``, ``window_pages_live`` and ``window_pages``. Device time is the
trace's: the kernels are found by their NAMES (``window_decode``,
``paged_decode``), never by the shapes of ops around them. A program that
records no such span or attribute, or a configuration of another kind,
gives None, never an error."""

from __future__ import annotations

import sys

from .common import BENCH
from .readers import _pct, _share
from .readers_docs import _in_decode, decode_spans
from .readers_solar import _total

sys.path.insert(0, BENCH)
from roofline import moe_experts, paged_decode_gqa, paged_decode_window  # noqa: E402

WINDOW_KERNEL = "window_decode"
FULL_KERNEL = "paged_decode"


def _steps(run):
    """The decode spans that carry the window's counts."""
    if "sliding_window" not in run.config:
        return []
    return [a for a in decode_spans(run)
            if "window_tokens_read" in a and "full_tokens_read" in a
            and "local_rows" in a]


def _heads(c):
    return c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]


def window_decode_roofline(run):
    """Required seconds (``roofline/paged_decode_window.py``: every running
    slot's last ``min(context, window)`` tokens, a sliding layer a step)
    over the named kernel's device time in decode programs."""
    sp = _steps(run)
    if not sp:
        return None
    c = run.config
    secs = _in_decode(run, lambda n, sh: WINDOW_KERNEL in n)
    layers = c["layer_types"].count("sliding_attention")
    t, bound = paged_decode_window.min_seconds(paged_decode_window.call(
        sum(x for a in sp for x in a["window_tokens_read"]),
        sum(a["running"] for a in sp) * layers, *_heads(c)), run.peaks)
    return _share(run, t, secs, bound, WINDOW_KERNEL)


def paged_decode_roofline(run):
    """The FULL layer's calls: required seconds
    (``roofline/paged_decode_gqa.py``: every running slot's whole context,
    a full layer a step) over the device time of the kernel named
    ``paged_decode`` in decode programs."""
    sp = _steps(run)
    if not sp:
        return None
    secs = _in_decode(run, lambda n, sh: FULL_KERNEL in n)
    t, bound = paged_decode_gqa.min_seconds(paged_decode_gqa.call(
        sum(x for a in sp for x in a["full_tokens_read"]),
        *_heads(run.config)), run.peaks)
    return _share(run, t, secs, bound, FULL_KERNEL)


def window_read_share(run):
    """Tokens a sliding layer attended over tokens a full layer attended,
    layer for layer: ``min(context, window) / context`` over the window's
    steps. 100% would say the window is a mask over a full read."""
    sp = _steps(run)
    c = run.config
    full = sum(x for a in sp for x in a["full_tokens_read"])
    if not full:
        return None
    kinds = c["layer_types"]
    win = sum(x for a in sp for x in a["window_tokens_read"])
    return _pct((win / kinds.count("sliding_attention"))
                / (full / kinds.count("full_attention")))


def window_pool_fill(run):
    """Pages of the window group that are mapped by a slot or kept by the
    trie, over the pages it has: the mean over the window's decode steps
    (the program's span attribute), else what the runner read off the
    group's allocator at the window's end."""
    sp = [a for a in _steps(run) if a.get("window_pages")]
    if sp:
        return _pct(sum(a["window_pages_live"] / a["window_pages"]
                        for a in sp) / len(sp))
    cap = run.counters.get("window_pages")
    return _pct(run.counters["window_pages_live"] / cap) if cap else None


def experts_touched_share(run):
    """Mean held experts with a row, a layer a step, over the experts
    held."""
    per_layer = [x for a in _steps(run) for x in a["experts_touched"]]
    if not per_layer:
        return None
    return _pct(sum(per_layer) / len(per_layer) / run.config["num_experts"])


def local_rows_share(run):
    """Rows that landed on held experts over rows routed (to all experts),
    summed over layers and steps (uniform routing: held / published,
    12.5%)."""
    sp = _steps(run)
    routed = _total(sp, "routed_rows")
    return _pct(_total(sp, "local_rows") / routed) if routed else None


def moe_experts_roofline(run):
    """Each touched HELD expert's three matrices once and the local rows'
    FLOPs, over ``moe_grouped_matmul`` in decode programs."""
    sp = _steps(run)
    if not sp:
        return None
    c = run.config
    secs = _in_decode(run, lambda n, sh: "moe_grouped_matmul" in n)
    t, bound = moe_experts.min_seconds(moe_experts.call(
        _total(sp, "experts_touched"), _total(sp, "local_rows"),
        c["hidden_size"], c["intermediate_size"]), run.peaks)
    return _share(run, t, secs, bound, "moe_grouped_matmul (held experts)")
