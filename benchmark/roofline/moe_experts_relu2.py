"""Required work of the expert matmuls of one layer of UNGATED experts in
one decode step (the two calls of the named kernel ``moe_grouped_matmul``
of an expert ``relu(h W_up)^2 W_down``): each expert some token was routed
to has its TWO ``hidden x width`` matrices read once, whatever the number
of its tokens, and each (token, expert) row costs two matmuls of ``2 x
hidden x width`` FLOPs. An expert nobody was routed to is not required
work. (``moe_experts.py`` counts the gated expert's three matrices: read
for this model it would call 1.5 times the bytes required, and a share
could pass 100%.)"""

from .flash import min_seconds  # noqa: F401


def call(experts_touched, routed_rows, hidden, width, itemsize=2):
    return {"flops": 4.0 * routed_rows * hidden * width,
            "bytes": 2.0 * experts_touched * hidden * width * itemsize}
