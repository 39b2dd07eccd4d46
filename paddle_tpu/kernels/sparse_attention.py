"""Learned sparse attention for decode (DeepSeek-Sparse-Attention style):
exact top-k selection of cached positions by an indexer's scores, and a
Pallas kernel that attends over the selected rows of the paged K / V pools.

Selection is exact and sort-free. ``kth_largest`` finds each row's k-th
largest score by bisection over the score's bits (32 compare-and-count
passes over the row, where a sort of a 34k-wide row per query would cost a
long prefill about a second a layer); ``topk_mask`` turns it into the set
``lax.top_k`` would return (ties at the threshold go to the LOWER position,
as ``lax.top_k`` breaks them), and ``topk_indices`` compacts that set into
``k`` ascending positions with dense vector work only (block counts, one
gather of 128-wide mask rows; no scatter, which a TPU serialises).

The decode read gathers the selected rows, not pages: at 16-token pages a
walk over a 33k-token slot's 2,176 pages is 2,176 grid steps a slot (about
0.35 us each: 12 ms a layer at 16 slots) whatever it then skips, and 63% of
the pages hold a selected row anyway. The pools are token-major
(``[pages, 1, page, H_kv * D]``), so a row is one run of 1 KB; the rows come
out of each pool through the page table (``row_index``) as one XLA row
gather (``gather_rows``) and ``sparse_paged_decode`` streams them in blocks
of 512 with the same online softmax as ``paged_attention._decode_kernel``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..core.place import pallas_interpret
from .flash_attention import LANES, LOG2E, NEG_INF
from .mesh import shard_kernel

_LANE_BLOCK = 128   # positions per block of the compaction


# ------------------------------------------------------------- selection

def _ordered_bits(scores, valid):
    """float32 scores -> uint32 keys in the same order (larger score, larger
    key); positions that are not ``valid`` get key 0, below every real
    score's key (a real key has its top bit set or flipped, never all
    zero except for -NaN payloads, which scores are not)."""
    b = lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.uint32)
    neg = (b >> 31) == 1
    u = jnp.where(neg, ~b, b | jnp.uint32(0x80000000))
    return jnp.where(valid, u, jnp.uint32(0))


def kth_largest(u, k: int):
    """The k-th largest uint32 key of each row of ``u [..., L]`` (0 where a
    row has fewer than k non-zero keys): built bit by bit from the top, each
    bit one compare-and-count pass."""
    def body(i, t):
        cand = t | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        n = jnp.sum(u >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(n >= k, cand, t)

    return lax.fori_loop(0, 32, body, jnp.zeros(u.shape[:-1], jnp.uint32))


def topk_mask(scores, valid, k: int):
    """bool ``[..., L]``: the k valid positions of largest score in each row
    (all valid ones where a row has no more than k), ties at the threshold
    going to the lower position — the set ``lax.top_k`` returns on the row
    with invalid positions at -inf."""
    if scores.shape[-1] <= k:
        return valid
    u = _ordered_bits(scores, valid)
    t = kth_largest(u, k)[..., None]
    above = u > t
    at = (u == t) & valid
    room = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    return above | (at & (jnp.cumsum(at, axis=-1, dtype=jnp.int32) <= room))


def topk_indices(scores, valid, k: int):
    """(idx ``[B, k]`` int32 ascending, n ``[B]``): the positions
    ``topk_mask`` selects, compacted; entries past ``n[b]`` are 0."""
    B, L = scores.shape
    sel = topk_mask(scores, valid, k)
    pad = (-L) % _LANE_BLOCK
    if pad:
        sel = jnp.pad(sel, ((0, 0), (0, pad)))
    m3 = sel.reshape(B, -1, _LANE_BLOCK).astype(jnp.int32)
    cnt = m3.sum(-1)                                   # [B, nblk]
    cum = jnp.cumsum(cnt, axis=-1)                     # inclusive
    n = cum[:, -1]
    k = min(k, L)
    j = jnp.arange(k, dtype=jnp.int32)
    # block holding the j-th selected position: first block whose inclusive
    # count passes j
    blk = jnp.sum(cum[:, None, :] <= j[None, :, None], axis=-1)
    blk = jnp.minimum(blk, m3.shape[1] - 1)            # [B, k]
    before = jnp.take_along_axis(cum - cnt, blk, axis=1)
    rows = jnp.take_along_axis(m3, blk[:, :, None], axis=1)  # [B, k, 128]
    within = (j[None, :] - before)[:, :, None]
    # offset of the (within+1)-th set bit: how many inclusive counts are
    # still <= within
    off = jnp.sum(jnp.cumsum(rows, axis=-1) <= within, axis=-1)
    idx = blk * _LANE_BLOCK + jnp.minimum(off, _LANE_BLOCK - 1)
    return jnp.where(j[None, :] < n[:, None], idx, 0).astype(jnp.int32), n


def row_index(page_table, idx, page_size: int):
    """Where sequence positions ``idx [B, K]`` lie in a pool seen as rows
    ``[pages * page_size, W]``, through ``page_table [B, blocks]``.
    Sentinel table entries clamp to the trash page."""
    page = jnp.take_along_axis(page_table, idx // page_size, axis=1)
    return jnp.maximum(page, 0) * page_size + idx % page_size


def gather_rows(pool, rows):
    """Rows ``rows [B, K]`` (``row_index``) of a token-major pool
    ``[pages, 1, page, W]``: ``[B, K, W]``."""
    return pool.reshape(-1, pool.shape[-1])[rows]


# ---------------------------------------------------------------- kernel

def _sparse_decode_kernel(n_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                          acc_scr, *, block: int, num_blocks: int,
                          num_kv_heads: int, rep: int, head_dim: int):
    """Grid (B, K / block): the slot's selected rows stream through the
    trailing (sequential) dim; rows at or past ``n[b]`` are masked, blocks
    wholly past it skipped."""
    b = pl.program_id(0)
    i = pl.program_id(1)
    n = n_ref[b]

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(i * block < n)
    def _compute():
        q = q_ref[0]      # [Hq, D], pre-scaled
        k = k_ref[0]      # [block, Hkv * D]: a row is one token, all heads
        v = v_ref[0]
        D = head_dim
        s = jnp.concatenate([
            lax.dot_general(q[g * rep:(g + 1) * rep], k[:, g * D:(g + 1) * D],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
            for g in range(num_kv_heads)], axis=0) * jnp.float32(LOG2E)
        Hq = s.shape[0]
        row = i * block + lax.broadcasted_iota(jnp.int32, (Hq, block), 1)
        s = jnp.where(row < n, s, NEG_INF)
        m = m_scr[:, 0]
        l = l_scr[:, 0]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp2(s - m_new[:, None])
        alpha = jnp.exp2(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.concatenate([
            lax.dot_general(p[g * rep:(g + 1) * rep].astype(v.dtype),
                            v[:, g * D:(g + 1) * D], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
            for g in range(num_kv_heads)], axis=0)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + pv
        m_scr[...] = lax.broadcast_in_dim(m_new, m_scr.shape, (0,))
        l_scr[...] = lax.broadcast_in_dim(l_new, l_scr.shape, (0,))

    @pl.when(i == num_blocks - 1)
    def _epilogue():
        l = l_scr[:, 0]
        l_safe = jnp.where(l == 0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe[:, None]).astype(o_ref.dtype)


def _sparse_decode_call(n, qs, k_rows, v_rows):
    B, Hq, D = qs.shape
    K, W = k_rows.shape[1:]
    Hkv = W // D
    block = min(512, K)
    if K % block:
        raise ValueError(f"selected rows {K} not a multiple of {block}")
    nb = K // block
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, Hq, D), lambda b, i, n_: (b, 0, 0)),
            pl.BlockSpec((1, block, W), lambda b, i, n_: (b, i, 0)),
            pl.BlockSpec((1, block, W), lambda b, i, n_: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hq, D), lambda b, i, n_: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((Hq, LANES), jnp.float32),
                        pltpu.VMEM((Hq, LANES), jnp.float32),
                        pltpu.VMEM((Hq, D), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_sparse_decode_kernel, block=block, num_blocks=nb,
                          num_kv_heads=Hkv, rep=Hq // Hkv, head_dim=D),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), v_rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=pallas_interpret(),
        name="sparse_paged_decode",
    )(n, qs, k_rows, v_rows)


def sparse_paged_decode(q, k_pool, v_pool, page_table, idx, n):
    """One query token per slot over the selected positions of its cache.

    q            ``[B, H_q, D]``
    k/v_pool     ``[pages, 1, page_size, H_kv * D]`` token-major pools
    page_table   ``[B, blocks]`` int32 (-1 = unallocated)
    idx, n       ``[B, K]`` selected positions (``topk_indices``), of which
                 the first ``n[b]`` count

    Returns ``[B, H_q, D]`` in v's dtype: softmax over the selected
    positions only, numerics as ``paged_attention`` (q pre-scaled in its
    own dtype, float32 scores and statistics).
    """
    D = q.shape[-1]
    qs = q * jnp.asarray(1.0 / np.sqrt(D), q.dtype)
    rows = row_index(page_table, idx, k_pool.shape[2])
    k_rows = gather_rows(k_pool, rows)
    v_rows = gather_rows(v_pool, rows)
    return shard_kernel(_sparse_decode_call,
                        (n.astype(jnp.int32), qs, k_rows, v_rows),
                        (P(), P(), P(), P()), lambda f: P())
