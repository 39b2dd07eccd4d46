"""Required work of the chunked form of the gated delta rule in one layer
over one piece of a prompt (``tokens`` real tokens from a given state; the
program runs it in prefill and extend, in chunks of ``C``): per token and
head the two ``C x dk`` score rows (``K K^T``, ``Q K^T``), its row of the
unit-triangular solve against ``dv + dk`` right-hand sides (``C / 2``
multiply-adds each), the two products with the chunk's start state
(``dk x dv`` each), its row of the intra-chunk output (``C x dv``) and its
share of the state update (``dk x dv``); the bytes are q, k, v, the output,
decay and write strength of every token in float32, and the state read and
written once a piece. Padding behind a piece's last token is not required
work."""

from .flash import min_seconds  # noqa: F401


def call(tokens, pieces, H, dk, dv, C, itemsize=4):
    """``tokens`` / ``pieces``: summed over the layers that ran them."""
    per_token = 2.0 * (2 * C * dk + C * (dv + dk) / 2 + 3 * dk * dv + C * dv)
    return {"flops": tokens * H * per_token,
            "bytes": (tokens * H * (2 * dk + 2 * dv + 2)
                      + pieces * 2 * H * dk * dv) * itemsize}
