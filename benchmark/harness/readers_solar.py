"""Readers of the ``.agents`` per-layer metrics that ``readers.py``,
``readers_docs.py`` and ``readers_hybrid.py`` have no function for: what a
chip that holds SOME of a layer's experts did with the rows it routed, and
the roofline shares of its kernels at this configuration's shapes (a decay a
key channel, grouped heads, held experts).

The counts come from the attributes the program puts on its
``serving/decode`` span, per layer: ``experts_touched`` (held experts with a
row), ``local_rows`` (rows on held experts), ``routed_rows`` (rows routed,
to all experts), and ``running``; and from its ``serving/admit/extend`` /
``prefill`` spans' ``tokens``. Device time is the trace's, by program. A
program that records no such span or attribute, or a configuration that is
not of this kind, gives None, never an error."""

from __future__ import annotations

import sys

from . import program_spans, trace
from .common import BENCH
from .readers import _pct, _share
from .readers_docs import _in_decode, decode_spans
from .readers_hybrid import _in_programs, is_chunk_shape

sys.path.insert(0, BENCH)
from roofline import kda_chunk, kda_step, moe_experts, paged_decode_gqa  # noqa: E402


def _steps(run):
    """The decode spans that carry the held-expert counts."""
    if "n_routed_experts_published" not in run.config:
        return []
    return [a for a in decode_spans(run) if "local_rows" in a]


def _total(sp, name):
    """A per-layer count summed over layers and steps."""
    return sum(x for a in sp for x in a[name])


def experts_touched_share(run):
    """Mean held experts with a row, a layer a step, over the experts held
    (uniform routing of 128 slots' 1,024 rows over 320 experts: 96%)."""
    per_layer = [x for a in _steps(run) for x in a["experts_touched"]]
    if not per_layer:
        return None
    return _pct(sum(per_layer) / len(per_layer)
                / run.config["n_routed_experts"])


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
        c["hidden_size"], c["moe_intermediate_size"]), run.peaks)
    return _share(run, t, secs, bound, "moe_grouped_matmul (held experts)")


def _linear(c):
    """(linear layers, heads, dk = dv) of the configuration."""
    lin = c["linear_attn_config"]
    return (c["num_hidden_layers"] - len(c["gqa_layers"]), lin["num_heads"],
            lin["head_dim"])


def kda_decode_roofline(run):
    sp = _steps(run)
    if not sp:
        return None
    L, H, d = _linear(run.config)
    secs = _in_decode(run, lambda n, sh: "gdn_decode_step" in n)
    t, bound = kda_step.min_seconds(kda_step.call(
        sum(a["running"] for a in sp) * L, H, d, d), run.peaks)
    return _share(run, t, secs, bound, "gdn_decode_step (a decay a channel)")


def kda_chunk_roofline(run):
    """The chunked form is plain XLA: its ops carry no name in the trace and
    are told by their output shapes (``readers_hybrid.is_chunk_shape``)
    inside extend and prefill programs. Ops of it that the compiler fused
    under another shape are missed, so the time is a lower bound and the
    share an UPPER reading."""
    if run.trace is None or run.trace_host is None \
            or "n_routed_experts_published" not in run.config:
        return None
    c = run.config
    L, H, d = _linear(c)
    C = c.get("program", {}).get("gdn_chunk", 64)
    shape_of = {"linear_num_value_heads": H, "linear_key_head_dim": d,
                "linear_value_head_dim": d, "program": {"gdn_chunk": C}}
    ta, tb = run.trace_host
    pieces = [a for s, e, n, a in program_spans.ring()
              if n.split("{")[0] in ("serving/admit/extend",
                                     "serving/admit/prefill")
              and ta <= s and e <= tb]
    if not pieces:
        return None
    secs = _in_programs(run, lambda n: "extend" in n or "prefill" in n,
                        lambda n, sh: is_chunk_shape(shape_of, sh))
    t, bound = kda_chunk.min_seconds(kda_chunk.call(
        sum(a["tokens"] for a in pieces) * L, len(pieces) * L, H, d, d, C),
        run.peaks)
    return _share(run, t, secs, bound,
                  "gdn_chunked, a decay a channel (XLA ops by shape)")


def paged_decode_roofline(run):
    """``readers.paged_decode_roofline`` with grouped heads: K and V are
    read at their stored 8 heads, the FLOPs are the 64 query heads'."""
    if run.trace is None or run.trace_host is None \
            or "n_routed_experts_published" not in run.config:
        return None
    c = run.config
    ta, tb = run.trace_host
    ctx = sum(s[3] for s in run.counters["steps"] if ta <= s[0] and s[1] <= tb)
    secs = trace.op_seconds(run.trace, lambda n: "paged_decode" in n)
    t, bound = paged_decode_gqa.min_seconds(paged_decode_gqa.call(
        ctx * len(c["gqa_layers"]), c["num_attention_heads"],
        c["num_key_value_heads"], c["head_dim"]), run.peaks)
    return _share(run, t, secs, bound, "paged_decode (grouped heads)")
