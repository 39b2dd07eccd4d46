"""Required work of the Mamba-1 selective scan of one layer over one
admission's run of tokens (the named kernel ``mamba1_scan``), the same
whatever implements it: the row's float32 state (``N x E``) comes in once
and goes out once (the states handed out at cuts are the caller's wish and
not required), and each REAL token's ``x``, ``dt`` (in) and ``y`` (out),
``E`` float32 each, and ``B``, ``C``, ``N`` each, move once; per token and
state element seven FLOPs, as ``mamba1_step`` counts them. The bucket's
padding behind the last real token is not required work (the kernel walks
it all the same). The FLOPs are the vector unit's, and the table of peaks
has the matrix unit's rate alone: against it the memory bound binds (134 us
against 5 us for 1,792 tokens of 5,120 x 16), so the share says how far the
kernel is from moving its rows at the memory's rate, which a walk over
tokens on the vector unit does not reach by construction."""

from .flash import min_seconds  # noqa: F401


def call(real_tokens, rows, E, N, itemsize=4):
    """``real_tokens``: real tokens summed over the runs (and layers);
    ``rows``: runs summed over the layers."""
    return {"flops": 7.0 * real_tokens * E * N,
            "bytes": (1.0 * real_tokens * (3 * E + 2 * N)
                      + 2.0 * rows * E * N) * itemsize}
