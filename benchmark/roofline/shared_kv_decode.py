"""Required work of the decode-step reads of a K/V pool that SEVERAL layers
share (a decoder-hybrid-decoder: the layer that writes the pool and every
cross layer behind it attend to the same cached keys and values), the same
whatever kernel or XLA op does the read: each READING layer's query of a
running slot attends to the slot's live context, so a step reads K and V of
every live token once A READING LAYER at their stored width (``pairs``
pair-heads of ``2 D`` lanes: ``[k_2r | k_2r+1]``, ``[v_2r | v_2r+1]``) and
does two multiply-adds of the pair's ``2 D`` lanes per QUERY head per token
(the widened ``q . [k_1 | k_2]`` and ``p . [v_1 | v_2]``; half of the first
are zeros, which a differential read over pair-head pools cannot skip, and
which are counted as the kernel computes them: they are a tenth of the
memory bound at these widths). ``shared_read`` (the program's step statistic,
per reading layer) is the token count. Tokens of free slots and of unfilled
page tails are not required work. The pool is STORED once; that is the
configuration's memory and no part of this count."""

from .flash import min_seconds  # noqa: F401


def call(shared_read_tokens, Hq, pairs, D2, itemsize=2):
    """``shared_read_tokens``: live tokens summed over the running slots,
    the reading layers and the steps."""
    return {"flops": 4.0 * shared_read_tokens * Hq * D2,
            "bytes": 2.0 * shared_read_tokens * pairs * D2 * itemsize}
