"""Readers of the ``.hybrid`` per-layer metrics that ``readers.py`` has no
function for: what the snapshot rule recomputed, how full the snapshot pool
stands, and the roofline shares of the two forms of the gated delta rule.

The counts come from what the runner counted off the program's
``serving/admit`` spans (``recomputed_tokens``, ``prompt_tokens``), from the
engine's snapshot allocator, and from the ``serving/decode`` spans'
``running``; device time from the trace, by program (decode, or extend and
prefill). A program that records no such span or attribute, or runs no such
kernel, gives None, never an error."""

from __future__ import annotations

import sys
from bisect import bisect_right

from . import program_spans
from .common import BENCH
from .readers import _pct, _share
from .readers_docs import decode_spans

sys.path.insert(0, BENCH)
from roofline import gdn_chunk, gdn_step  # noqa: E402


def recomputed_token_share(run):
    """Prompt tokens run again for want of a snapshot (matched by the trie's
    pages, behind the deepest snapshot on their path) over prompt tokens
    admitted."""
    n = run.counters.get("admit_prompt_tokens")
    return _pct(run.counters["admit_recomputed_tokens"] / n) if n else None


def snapshot_pool_fill(run):
    cap = run.counters.get("snapshots_capacity")
    return _pct(run.counters["snapshots_held"] / cap) if cap else None


def _in_programs(run, is_program, match) -> float:
    """Device seconds of the ops whose (name, output shape) ``match``
    accepts and that started inside a run of a program ``is_program``
    accepts by name."""
    tr = run.trace
    if tr is None or not tr["modules"]:
        return 0.0
    dev = sorted(tr["modules"])[0]
    runs = sorted((s, e) for s, e, n in tr["modules"][dev] if is_program(n))
    starts = [s for s, _ in runs]
    total = 0.0
    for s, e, n, sh in tr["devices"].get(dev, ()):
        if match(n, sh):
            i = bisect_right(starts, s) - 1
            if i >= 0 and s < runs[i][1]:
                total += e - s
    return total


def _linear_layers(c) -> int:
    return c["layer_types"].count("linear_attention")


def gdn_decode_roofline(run):
    sp = decode_spans(run)
    if not sp or "linear_value_head_dim" not in run.config:
        return None
    c = run.config
    secs = _in_programs(run, lambda n: "decode" in n,
                        lambda n, sh: "gdn_decode_step" in n)
    slots = sum(a["running"] for a in sp) * _linear_layers(c)
    t, bound = gdn_step.min_seconds(gdn_step.call(
        slots, c["linear_num_value_heads"], c["linear_key_head_dim"],
        c["linear_value_head_dim"]), run.peaks)
    return _share(run, t, secs, bound, "gdn_decode_step")


def is_chunk_shape(c, shape: str) -> bool:
    """Whether an op's output shape is one only the chunked form's ops have
    in an extend or prefill program of this configuration: float32, with
    the ``H`` heads followed by a chunk's ``C`` rows (scores, decays, solved
    rows, outputs; stacked over the chunks or not) or by the ``dv x dk``
    state. (The attention routine's float32 scores are ``[H, query_chunk,
    L]``: ``query_chunk`` is set apart from ``C``.)"""
    if not shape.startswith("f32["):
        return False
    dims = [int(d) for d in shape[4:-1].split(",") if d]
    H, dk, dv = (c["linear_num_value_heads"], c["linear_key_head_dim"],
                 c["linear_value_head_dim"])
    C = c.get("program", {}).get("gdn_chunk", 64)
    return any(dims[i:i + len(run)] == run
               for run in ([H, C], [H, 1, C], [H, dv, dk])
               for i in range(len(dims)))


def gdn_chunk_roofline(run):
    """The chunked form is plain XLA: its ops carry no name in the trace and
    are told by their output shapes (``is_chunk_shape``) inside extend and
    prefill programs. Ops of it that the compiler fused under another shape
    are missed, so the time is a lower bound and the share an upper one; it
    reads low all the same (small batched matmuls)."""
    if run.trace is None or run.trace_host is None \
            or "linear_value_head_dim" not in run.config:
        return None
    c = run.config
    ta, tb = run.trace_host
    pieces = [a for s, e, n, a in program_spans.ring()
              if n.split("{")[0] in ("serving/admit/extend",
                                     "serving/admit/prefill")
              and ta <= s and e <= tb]
    if not pieces:
        return None
    secs = _in_programs(run, lambda n: "extend" in n or "prefill" in n,
                        lambda n, sh: is_chunk_shape(c, sh))
    L = _linear_layers(c)
    t, bound = gdn_chunk.min_seconds(gdn_chunk.call(
        sum(a["tokens"] for a in pieces) * L, len(pieces) * L,
        c["linear_num_value_heads"], c["linear_key_head_dim"],
        c["linear_value_head_dim"],
        c.get("program", {}).get("gdn_chunk", 64)), run.peaks)
    return _share(run, t, secs, bound, "gdn_chunked (XLA ops by shape)")
