"""Readers of the ``.loop`` per-layer metrics that the older readers have no
function for: the shared K/V pool's reads (its share of the roofline and of
a decode step), the windowed reads', the Mamba-1 step's and scan's shares of
their rooflines, and the rows of an admission that entered the cross-decoder.

The counts come from what the program puts on its spans: per layer of a
``serving/decode`` span ``shared_read`` (cached tokens a layer read from the
ONE pool the cross layers share), ``window_tokens_read`` and
``ssm_slots_stepped``; of a ``serving/admit`` span ``cross_rows`` beside
``prompt_tokens``; of a ``serving/admit/extend|prefill`` span ``tokens``.
Device time is the trace's: the reads are found by the NAMES of the kernels
that do them (``paged_decode``: in this model's decode program the layer
that writes the shared pool and the cross layers alone call it, under the
scopes ``diff/self`` and ``diff/cross``; ``window_decode``: the sliding
layers, ``diff/window``; ``mamba1_decode_step``, ``mamba1_scan``), never by
the shapes of ops around them. A program that records no such span or
attribute, or a configuration of another kind, gives None, never an
error."""

from __future__ import annotations

import sys

from . import program_spans, trace
from .common import BENCH
from .readers import _pct, _share
from .readers_docs import _in_decode, decode_spans

sys.path.insert(0, BENCH)
from roofline import (mamba1_scan, mamba1_step, paged_decode_window,  # noqa: E402
                      shared_kv_decode)

SHARED_KERNEL = "paged_decode"
WINDOW_KERNEL = "window_decode"
STEP_KERNEL = "mamba1_decode_step"
SCAN_KERNEL = "mamba1_scan"


def _steps(run, name):
    """The decode spans that carry the per-layer count ``name``, of a
    configuration of this kind."""
    if "mb_per_layer" not in run.config:
        return []
    return [a for a in decode_spans(run) if name in a]


def _total(sp, name):
    return sum(x for a in sp for x in a[name])


def _pairs(c):
    """(query heads, K/V pair-heads, a pair's lanes)."""
    D = c["hidden_size"] // c["num_attention_heads"]
    return c["num_attention_heads"], c["num_key_value_heads"] // 2, 2 * D


def shared_kv_decode_roofline(run):
    """Required seconds (``roofline/shared_kv_decode.py``: every running
    slot's context, a READING layer a step) over the named kernel's device
    time in decode programs."""
    sp = _steps(run, "shared_read")
    if not sp:
        return None
    secs = _in_decode(run, lambda n, sh: SHARED_KERNEL in n)
    t, bound = shared_kv_decode.min_seconds(shared_kv_decode.call(
        _total(sp, "shared_read"), *_pairs(run.config)), run.peaks)
    return _share(run, t, secs, bound, SHARED_KERNEL + " (shared pool)")


def shared_kv_read_share(run):
    """The shared pool's reads' device seconds over the decode programs':
    how much of a decode step the eight reading layers' attention is."""
    if run.trace is None or not _steps(run, "shared_read"):
        return None
    decode = sum(trace.module_runs(run.trace, lambda n: "decode" in n))
    secs = _in_decode(run, lambda n, sh: SHARED_KERNEL in n)
    return _pct(secs / decode) if decode > 0 and secs > 0 else None


def window_decode_roofline(run):
    """Required seconds (``roofline/paged_decode_window.py``: every running
    slot's last ``min(context, window)`` tokens, a sliding layer a step, at
    the pair-head pools' width) over ``window_decode`` in decode programs."""
    sp = _steps(run, "window_tokens_read")
    if not sp:
        return None
    layers = sum(1 for x in sp[0]["window_tokens_read"] if x)
    secs = _in_decode(run, lambda n, sh: WINDOW_KERNEL in n)
    t, bound = paged_decode_window.min_seconds(paged_decode_window.call(
        _total(sp, "window_tokens_read"),
        sum(a["running"] for a in sp) * layers, *_pairs(run.config)),
        run.peaks)
    return _share(run, t, secs, bound, WINDOW_KERNEL)


def _widths(c):
    a = c["assumed_sizes"]
    return a["mamba_inner"], a["mamba_state"]


def mamba1_step_roofline(run):
    """Each running slot's state read and written once a layer, over the
    ``mamba1_decode_step`` calls in decode programs."""
    sp = _steps(run, "ssm_slots_stepped")
    if not sp:
        return None
    t, bound = mamba1_step.min_seconds(mamba1_step.call(
        _total(sp, "ssm_slots_stepped"), *_widths(run.config)), run.peaks)
    secs = _in_decode(run, lambda n, sh: STEP_KERNEL in n)
    return _share(run, t, secs, bound, STEP_KERNEL)


def _admit_runs(run):
    """Attributes of the ``serving/admit/extend`` and ``/prefill`` spans
    wholly inside the traced stretch."""
    if run.trace is None or run.trace_host is None \
            or "mb_per_layer" not in run.config:
        return []
    ta, tb = run.trace_host
    return [a for s, e, n, a in program_spans.ring()
            if n.split("{")[0] in ("serving/admit/extend",
                                   "serving/admit/prefill")
            and ta <= s and e <= tb and "tokens" in a]


def mamba1_scan_roofline(run):
    """Each admission's real tokens once and its state in and out once a
    Mamba-1 layer, over the ``mamba1_scan`` calls (extend and prefill
    programs)."""
    runs = _admit_runs(run)
    if not runs:
        return None
    c = run.config
    layers = c["num_hidden_layers"] // 4 + 1    # the even layers 0 .. L / 2
    t, bound = mamba1_scan.min_seconds(mamba1_scan.call(
        sum(a["tokens"] for a in runs) * layers, len(runs) * layers,
        *_widths(c)), run.peaks)
    secs = trace.op_seconds(run.trace, lambda n: SCAN_KERNEL in n)
    return _share(run, t, secs, bound, SCAN_KERNEL)


def cross_rows_share(run):
    """Rows that entered the cross-decoder over prompt tokens, over the
    window's admissions (the program's ``serving/admit`` spans): one row an
    admission where the last-token cut works, every run token where not."""
    n = run.counters.get("admit_prompt_rows")
    return _pct(run.counters["admit_cross_rows"] / n) if n else None
