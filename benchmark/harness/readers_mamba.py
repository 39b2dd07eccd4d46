"""Readers of the ``.reason`` per-layer metrics that the older readers have
no function for: the Mamba-2 recurrent step's roofline share and its share
of the decode programs' time, the UNGATED experts' roofline share (two
matrices an expert), the held experts touched an EXPERT layer, and the paged
attend's share at this configuration's heads.

The counts come from the attributes the program puts on its
``serving/decode`` span, per layer: ``ssm_slots_stepped`` (the running slots
a Mamba-2 layer's step advanced), ``experts_touched`` (held experts with a
row), ``local_rows`` (rows on held experts); device time is the trace's, by
program. A program that records no such span or attribute, or a
configuration that is not of this kind, gives None, never an error."""

from __future__ import annotations

import sys

from . import trace
from .common import BENCH
from .readers import _pct, _share
from .readers_docs import _in_decode, decode_spans

sys.path.insert(0, BENCH)
from roofline import mamba2_step, moe_experts_relu2, paged_decode_gqa  # noqa: E402


def _steps(run, name):
    """The decode spans that carry the per-layer count ``name``, of a
    configuration of this kind."""
    if "mamba_num_heads" not in run.config:
        return []
    return [a for a in decode_spans(run) if name in a]


def _total(sp, name):
    """A per-layer count summed over layers and steps."""
    return sum(x for a in sp for x in a[name])


def _step_seconds(run) -> float:
    return _in_decode(run, lambda n, sh: "mamba2_decode_step" in n)


def mamba_decode_roofline(run):
    """Each running slot's state read and written once a layer, over the
    ``mamba2_decode_step`` calls in decode programs."""
    sp = _steps(run, "ssm_slots_stepped")
    if not sp:
        return None
    c = run.config
    t, bound = mamba2_step.min_seconds(mamba2_step.call(
        _total(sp, "ssm_slots_stepped"), c["mamba_num_heads"],
        c["mamba_head_dim"], c["n_groups"], c["ssm_state_size"]), run.peaks)
    return _share(run, t, _step_seconds(run), bound, "mamba2_decode_step")


def mamba_step_share(run):
    """The ``mamba2_decode_step`` calls' device seconds over the decode
    programs': how much of a decode step the recurrence is."""
    if run.trace is None or not _steps(run, "ssm_slots_stepped"):
        return None
    decode = sum(trace.module_runs(run.trace, lambda n: "decode" in n))
    secs = _step_seconds(run)
    return _pct(secs / decode) if decode > 0 and secs > 0 else None


def experts_touched_share(run):
    """Mean held experts with a row, an EXPERT layer a step, over the
    experts held (``readers_solar.experts_touched_share`` averages over
    every layer, which is every layer an expert layer there; here eight of
    thirteen layers have no experts and count 0)."""
    sp = _steps(run, "experts_touched")
    E = run.config.get("hybrid_override_pattern", "").count("E")
    if not sp or not E:
        return None
    return _pct(_total(sp, "experts_touched") / (len(sp) * E)
                / run.config["n_routed_experts"])


def moe_experts_roofline(run):
    """Each touched HELD expert's TWO matrices once and the local rows'
    FLOPs, over ``moe_grouped_matmul`` in decode programs."""
    sp = _steps(run, "local_rows")
    if not sp:
        return None
    c = run.config
    secs = _in_decode(run, lambda n, sh: "moe_grouped_matmul" in n)
    t, bound = moe_experts_relu2.min_seconds(moe_experts_relu2.call(
        _total(sp, "experts_touched"), _total(sp, "local_rows"),
        c["hidden_size"], c["moe_intermediate_size"]), run.peaks)
    return _share(run, t, secs, bound,
                  "moe_grouped_matmul (held relu2 experts)")


def paged_decode_roofline(run):
    """``readers_solar.paged_decode_roofline`` over this pattern's attention
    layers: K and V are read at their stored 2 heads, the FLOPs are the 32
    query heads'."""
    if run.trace is None or run.trace_host is None \
            or "mamba_num_heads" not in run.config:
        return None
    c = run.config
    ta, tb = run.trace_host
    ctx = sum(s[3] for s in run.counters["steps"] if ta <= s[0] and s[1] <= tb)
    secs = trace.op_seconds(run.trace, lambda n: "paged_decode" in n)
    t, bound = paged_decode_gqa.min_seconds(paged_decode_gqa.call(
        ctx * c["hybrid_override_pattern"].count("*"),
        c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]),
        run.peaks)
    return _share(run, t, secs, bound, "paged_decode (32 / 2 heads)")
