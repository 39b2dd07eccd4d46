"""Required work of the chunked form of the delta rule with a decay a key
channel, in one layer over one piece of a prompt (``tokens`` real tokens
from a given state; the program runs it in prefill and extend, in chunks of
``C``): per token and head the two score rows against the chunk's keys with
the decay inside the sum (``sum_c k_t[c] k_s[c] G_t[c] / G_s[c]`` and the
same with ``q_t``: ``C x dk`` terms of two multiplies and an add each), its
row of the unit-triangular solve against ``dv + dk`` right-hand sides
(``C / 2`` multiply-adds each), the two products with the chunk's start
state (``dk x dv`` each), its row of the intra-chunk output (``C x dv``) and
its share of the state update (``dk x dv``); the exponentials are not
counted. The bytes are q, k, the decay (``dk`` each), v and the output
(``dv`` each) and the write strength of every token in float32, and the
state read and written once a piece. Padding behind a piece's last token is
not required work."""

from .flash import min_seconds  # noqa: F401


def call(tokens, pieces, H, dk, dv, C, itemsize=4):
    """``tokens`` / ``pieces``: summed over the layers that ran them."""
    per_token = 3.0 * 2 * C * dk + 2.0 * (C * (dv + dk) / 2 + 3 * dk * dv
                                          + C * dv)
    return {"flops": tokens * H * per_token,
            "bytes": (tokens * H * (3 * dk + 2 * dv + 1)
                      + pieces * 2 * H * dk * dv) * itemsize}
