"""Required work of one extend-attention call over head-major pools (one
layer, one admission; the named kernels ``extend_flash`` and
``window_extend_flash``): ``tokens`` real queries at positions ``start ..
start + tokens - 1``, every query head, each against the keys it sees: all
up to its own position in a full layer, the last ``window`` of them in a
sliding one. Two multiply-adds of the head's width a (head, query, visible
key); it reads K and V of the visible positions ONCE a K/V head (at their
stored width, whatever the number of query heads that share them), reads
the queries and writes the attended values. A bucket's padding behind the
real tokens, key blocks a program walks beside a query's own keys, and the
gather that lays the pages out for the call are not required work."""

from .flash import min_seconds  # noqa: F401


def visible(tokens, start, window=None):
    """Keys seen, summed over the real queries."""
    if window is None:
        return tokens * start + tokens * (tokens + 1) / 2.0
    # the queries not yet a window deep see all before them
    rising = min(max(window - 1 - start, 0), tokens)
    return rising * start + rising * (rising + 1) / 2.0 \
        + (tokens - rising) * float(window)


def call(tokens, start, Hq, Hkv, D, window=None, itemsize=2):
    keys = start + tokens if window is None \
        else min(start + tokens, window + tokens - 1)
    return {"flops": 4.0 * Hq * D * visible(tokens, start, window),
            "bytes": float(itemsize) * D * (2.0 * Hq * tokens
                                            + 2.0 * Hkv * keys)}
