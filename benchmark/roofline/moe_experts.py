"""Required work of the expert matmuls of one layer in one decode step
(the three calls of the named kernel ``moe_grouped_matmul``): each expert
some token was routed to has its three ``hidden x width`` matrices read
once, whatever the number of its tokens, and each (token, expert) row costs
three matmuls of ``2 x hidden x width`` FLOPs. An expert nobody was routed
to is not required work."""

from .flash import min_seconds  # noqa: F401


def call(experts_touched, routed_rows, hidden, width, itemsize=2):
    return {"flops": 6.0 * routed_rows * hidden * width,
            "bytes": 3.0 * experts_touched * hidden * width * itemsize}
