"""Required work of one paged-decode attention call with grouped heads (one
layer, one decode step): each running slot's single query attends to its
live context, so the call reads K and V of every live token once at their
STORED width (``H_kv`` heads of ``D``) and does two length-``D``
multiply-adds per QUERY head per token (q.k and p.v). Tokens of free slots
and of unfilled page tails are not required work."""

from .flash import min_seconds  # noqa: F401


def call(context_tokens, Hq, Hkv, D, itemsize=2):
    """``context_tokens``: live tokens summed over the running slots."""
    return {"flops": 4.0 * context_tokens * Hq * D,
            "bytes": 2.0 * context_tokens * Hkv * D * itemsize}
