"""Required work of one fused AdamW update over n elements: it reads the
parameter, the gradient and both moments and writes the parameter and both
moments back (7 passes of n elements), and does about a dozen FLOPs per
element (two multiply-adds for the moments, bias corrections, a square root,
a divide, the decay)."""

from .flash import min_seconds  # noqa: F401  (same rule for every kernel)


def update(n, param_itemsize=2, moment_itemsize=2, grad_itemsize=2):
    return {"flops": 12.0 * n,
            "bytes": n * (2.0 * param_itemsize + 4.0 * moment_itemsize
                          + grad_itemsize)}
