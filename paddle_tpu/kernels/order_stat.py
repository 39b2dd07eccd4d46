"""Exact order statistics without a sort: the k-th largest value of each row
by bisection over the bits of an order-preserving integer key, 32
compare-and-count passes over the row whatever ``k`` is.

Used by the sparse attention's selection (``sparse_attention.topk_mask``:
one static ``k``) and by the serving sampler's top-k filter
(``serving/sampling.py``: a ``k`` a row, read from a program operand).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

_SIGN = 0x80000000


def ordered_bits(scores, valid):
    """float32 scores -> uint32 keys in the same order (larger score, larger
    key); positions that are not ``valid`` get key 0, below every real
    score's key (a real key has its top bit set or flipped, never all
    zero except for -NaN payloads, which scores are not)."""
    b = lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.uint32)
    neg = (b >> 31) == 1
    u = jnp.where(neg, ~b, b | jnp.uint32(_SIGN))
    return jnp.where(valid, u, jnp.uint32(0))


def bits_to_float(u):
    """The inverse of ``ordered_bits`` on a real score's key: the float32
    whose key ``u`` is."""
    pos = (u >> 31) == 1
    b = jnp.where(pos, u ^ jnp.uint32(_SIGN), ~u)
    return lax.bitcast_convert_type(b, jnp.float32)


def kth_largest(u, k):
    """The k-th largest uint32 key of each row of ``u [..., L]`` (0 where a
    row has fewer than k non-zero keys): built bit by bit from the top, each
    bit one compare-and-count pass. ``k`` is an int or an int array of the
    rows' shape (a ``k`` a row)."""
    def body(i, t):
        cand = t | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        n = jnp.sum(u >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(n >= k, cand, t)

    return lax.fori_loop(0, 32, body, jnp.zeros(u.shape[:-1], jnp.uint32))
