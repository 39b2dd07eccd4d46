"""Readers of the ``.latent`` per-layer metrics that the other readers have
no function for: the absorbed latent-attention decode kernel's share of its
roofline and of the decode program, the expanded form's kernel's share of
its roofline, and the held experts touched a step in a model whose first
layers are dense.

The counts come from the attributes the program puts on its
``serving/decode`` span: per layer ``latent_tokens_read`` (the cached
tokens a layer's attention read, summed over the live slots) and
``experts_touched``; a step ``running`` and ``distinct_pages`` (the live
pages counted once each, however many slots map them). Device time is the
trace's: the kernel is found by its NAME (``latent_paged_decode``), never by
the shapes of ops around it. A program that records no such span or
attribute, or a configuration of another kind, gives None, never an
error."""

from __future__ import annotations

import sys

from . import program_spans, trace
from .common import BENCH
from .readers import _is_decode, _pct, _share
from .readers_docs import _in_decode, decode_spans

sys.path.insert(0, BENCH)
from roofline import latent_decode, latent_flash  # noqa: E402

KERNEL = "latent_paged_decode"
FLASH = "latent_flash"


def _steps(run):
    """The decode spans that carry the latent layer's counts."""
    if "kv_lora_rank" not in run.config:
        return []
    return [a for a in decode_spans(run)
            if "latent_tokens_read" in a and "distinct_pages" in a]


def latent_decode_roofline(run):
    """Required seconds (``roofline/latent_decode.py``: the heads' FLOPs
    over every slot's context, each distinct live page once a layer-step)
    over the named kernel's device time in decode programs."""
    sp = _steps(run)
    if not sp:
        return None
    c = run.config
    secs = _in_decode(run, lambda n, sh: KERNEL in n)
    layers = c["num_hidden_layers"]
    t, bound = latent_decode.min_seconds(latent_decode.call(
        sum(x for a in sp for x in a["latent_tokens_read"]),
        sum(a["distinct_pages"] for a in sp) * layers,
        sum(a["running"] for a in sp) * layers,
        c["num_attention_heads"], c["kv_lora_rank"], c["qk_rope_head_dim"],
        c["engine"]["page_size"]), run.peaks)
    return _share(run, t, secs, bound, KERNEL)


def latent_decode_share(run):
    """The named kernel's device time over the decode programs' own."""
    if run.trace is None or "kv_lora_rank" not in run.config:
        return None
    programs = sum(trace.module_runs(run.trace, _is_decode))
    secs = _in_decode(run, lambda n, sh: KERNEL in n)
    if programs <= 0 or secs <= 0:
        return None
    return _pct(secs / programs)


def latent_flash_roofline(run):
    """Required seconds (``roofline/latent_flash.py``: every real query of
    every admission against the keys up to its own position, a layer) over
    the named kernel's device time, in the prefill and extend programs of
    the traced stretch. The admissions are the program's
    ``serving/admit/extend`` / ``prefill`` spans (``tokens``, ``start``)."""
    if run.trace is None or run.trace_host is None \
            or "kv_lora_rank" not in run.config:
        return None
    ta, tb = run.trace_host
    pieces = [a for s, e, n, a in program_spans.ring()
              if n.split("{")[0] in ("serving/admit/extend",
                                     "serving/admit/prefill")
              and ta <= s and e <= tb and "start" in a]
    secs = trace.op_seconds(run.trace, lambda n: FLASH in n)
    if not pieces or secs <= 0:
        return None
    c = run.config
    dq = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    work = {"flops": 0.0, "bytes": 0.0}
    for a in pieces:
        w = latent_flash.call(a["tokens"], a["start"],
                              c["num_attention_heads"], dq, c["v_head_dim"])
        for k in work:
            work[k] += w[k] * c["num_hidden_layers"]
    t, bound = latent_flash.min_seconds(work, run.peaks)
    return _share(run, t, secs, bound, FLASH)


def experts_touched_share(run):
    """Mean held experts with a row, an EXPERT layer a step, over the
    experts held (the leading dense layers count nothing and are left out
    of the mean)."""
    c = run.config
    dense = c.get("first_k_dense_replace", 0)
    per_layer = [x for a in _steps(run) if "experts_touched" in a
                 for x in a["experts_touched"][dense:]]
    if not per_layer:
        return None
    return _pct(sum(per_layer) / len(per_layer) / c["n_routed_experts"])
