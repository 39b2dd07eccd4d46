"""Required work of one expanded latent-attention call (one layer, one
admission; the named kernel ``latent_flash``): ``tokens`` real queries at
positions ``start .. start + tokens - 1``, every head, each against the
keys up to its own position: two multiply-adds of the query/key width and
of the value width a (head, query, visible key); it reads the queries, the
expanded keys and values of the ``start + tokens`` visible positions, and
writes the attended values. A bucket's padding behind the real tokens, and
key blocks a program walks behind a query's position, are not required
work; the expansion from the latents is outside the call."""

from .flash import min_seconds  # noqa: F401


def call(tokens, start, heads, dq, dv, itemsize=2):
    visible = tokens * start + tokens * (tokens + 1) / 2.0   # sum over queries
    return {"flops": 2.0 * heads * (dq + dv) * visible,
            "bytes": float(itemsize) * heads * (
                tokens * (dq + dv) + (start + tokens) * (dq + dv))}
