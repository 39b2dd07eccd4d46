"""Required work of the sparse decode read (one layer, one decode step), the
part the NAMED kernel ``sparse_paged_decode`` does: each running slot's
query attends to its selected positions only, so the call reads K and V of
every selected token once (``H_kv`` heads of ``D``, stored once for the
``H / H_kv`` query heads that share them) and does two length-D
multiply-adds per QUERY head per selected token (q.k and p.v).

``indexer`` is the rest of the mechanism's required work: the indexer's key
of every live token read once and ``H_i`` dots of ``D_i`` with it. The
program does that part in XLA fusions that carry no name in the device
trace, so it stands on neither side of ``sparse_decode_roofline.docs``
(PERF.md section 3 says so); it is here so that the two can be put side by
side from the trace's own op table."""

from .flash import min_seconds  # noqa: F401


def call(selected_tokens, H, H_kv, D, itemsize=2):
    """``selected_tokens``: selected positions summed over the running
    slots."""
    return {"flops": 4.0 * selected_tokens * H * D,
            "bytes": 2.0 * selected_tokens * H_kv * D * itemsize}


def indexer(context_tokens, H_i, D_i, itemsize=2):
    """``context_tokens``: live tokens summed over the running slots."""
    return {"flops": 2.0 * context_tokens * H_i * D_i,
            "bytes": 1.0 * context_tokens * D_i * itemsize}
