"""Readers of the ``.docs`` per-layer metrics that ``readers.py`` has no
function for: what the decode step's attention selects, how many experts a
step touches, and the two new kernels' roofline shares.

The counts come from the attributes the program puts on its
``serving/decode`` span (``ctx_tokens``, ``selected_tokens``, and per layer
``experts_touched`` / ``expert_max_load``, fetched with the step's tokens);
they are read from the span ring on the host clock, for the decode steps
wholly inside the traced stretch. A program that records no such span or
attribute gives None, never an error."""

from __future__ import annotations

import sys
from bisect import bisect_right

from . import program_spans
from .common import BENCH
from .readers import _pct, _share

sys.path.insert(0, BENCH)
from roofline import moe_experts, sparse_decode  # noqa: E402


def decode_spans(run):
    """Attributes of the ``serving/decode`` spans wholly inside the traced
    stretch that ran a step, [] where there are none."""
    if run.trace is None or run.trace_host is None:
        return []
    if not hasattr(run, "_decode_spans"):
        ta, tb = run.trace_host
        run._decode_spans = [
            a for s, e, n, a in program_spans.ring()
            if n == "serving/decode" and ta <= s and e <= tb
            and "ctx_tokens" in a]
    return run._decode_spans


def sparse_read_share(run):
    """Selected over cached tokens, summed over slots and decode steps."""
    sp = decode_spans(run)
    ctx = sum(a["ctx_tokens"] for a in sp)
    return _pct(sum(a["selected_tokens"] for a in sp) / ctx) if ctx else None


def _touched(sp):
    return [a["experts_touched"] for a in sp if "experts_touched" in a]


def experts_touched_share(run):
    """Mean distinct experts a layer a step is routed to, over all of
    them."""
    per_layer = [x for step in _touched(decode_spans(run)) for x in step]
    if not per_layer:
        return None
    return _pct(sum(per_layer) / len(per_layer) / run.config["num_experts"])


def _in_decode(run, match) -> float:
    """Device seconds of the ops whose (name, output shape) ``match``
    accepts and that started inside a decode program's run (the kernels run in the extend and prefill programs
    too, on other shapes)."""
    tr = run.trace
    if not tr["modules"]:
        return 0.0
    dev = sorted(tr["modules"])[0]
    runs = sorted((s, e) for s, e, n in tr["modules"][dev] if "decode" in n)
    starts = [s for s, _ in runs]
    total = 0.0
    for s, e, n, sh in tr["devices"].get(dev, ()):
        if match(n, sh):
            i = bisect_right(starts, s) - 1
            if i >= 0 and s < runs[i][1]:
                total += e - s
    return total


def sparse_decode_roofline(run):
    """The sparse read = the named kernel AND the two XLA row gathers that
    bring the selected rows of K and V out of the pools for it (told by
    their output shape, [slots * topk, H_kv * D]: the compiler keeps their
    output in fast memory, so the kernel alone reads no HBM and would read
    above its own roofline)."""
    sp = decode_spans(run)
    if not sp:
        return None
    c = run.config
    rows = c["engine"]["max_batch_size"] * c["sa_config"]["topk"]
    gathered = f"bf16[{rows},{c['num_key_value_heads'] * c['head_dim']}]"
    kernel = _in_decode(run, lambda n, sh: "sparse_paged_decode" in n)
    gathers = _in_decode(run, lambda n, sh: sh == gathered
                         and "sparse_paged_decode" not in n)
    run.say(f"roofline sparse read: kernel {kernel:.4f} s, row gathers "
            f"{gathered} {gathers:.4f} s")
    secs = kernel + gathers
    sel = sum(a["selected_tokens"] for a in sp) * c["num_hidden_layers"]
    t, bound = sparse_decode.min_seconds(sparse_decode.call(
        sel, c["num_attention_heads"], c["num_key_value_heads"],
        c["head_dim"]), run.peaks)
    return _share(run, t, secs, bound, "sparse_paged_decode")


def moe_experts_roofline(run):
    sp = decode_spans(run)
    touched = _touched(sp)
    if not touched:
        return None
    c = run.config
    secs = _in_decode(run, lambda n, sh: "moe_grouped_matmul" in n)
    rows = sum(a["running"] for a in sp if "experts_touched" in a) \
        * c["num_experts_per_tok"] * c["num_hidden_layers"]
    t, bound = moe_experts.min_seconds(moe_experts.call(
        sum(map(sum, touched)), rows, c["hidden_size"],
        c["moe_intermediate_size"]), run.peaks)
    return _share(run, t, secs, bound, "moe_grouped_matmul")
