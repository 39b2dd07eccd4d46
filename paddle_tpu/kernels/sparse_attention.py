"""Learned sparse attention for decode (DeepSeek-Sparse-Attention style):
exact top-k selection of cached positions by an indexer's scores, and a
Pallas kernel that attends over the selected rows of the paged K / V pools.

Selection is exact and sort-free. ``order_stat.kth_largest`` finds each row's k-th
largest score by bisection over the score's bits (32 compare-and-count
passes over the row, where a sort of a 34k-wide row per query would cost a
long prefill about a second a layer); ``topk_mask`` turns it into the set
``lax.top_k`` would return (ties at the threshold go to the LOWER position,
as ``lax.top_k`` breaks them), and ``selected_rows`` compacts that set into
the ``k`` pool rows it lies in, in ascending order of position. The
compaction carries each position's row address through the page table from
the start and is dense vector work around ONE gather, of 128-wide rows of
addresses: no scatter, which a TPU serialises, and no gather of single
elements, which costs this chip what a gather of as many 1 KB rows costs
(9.4 ns an element against 11 ns a row; PERF.md section 7).

The decode read gathers the selected rows, not pages: at 16-token pages a
walk over a 33k-token slot's 2,176 pages is 2,176 grid steps a slot (about
0.35 us each: 12 ms a layer at 16 slots) whatever it then skips, and 63% of
the pages hold a selected row anyway. The pools are token-major
(``[pages, 1, page, H_kv * D]``), so a row is one run of 1 KB; the rows come
out of each pool as one XLA row gather (``gather_rows``) and
``sparse_paged_decode`` streams them in blocks of 512 with the same online
softmax as ``paged_attention._decode_kernel``.
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
from .order_stat import kth_largest, ordered_bits

_LANE_BLOCK = 128   # positions per block of the compaction


# ------------------------------------------------------------- selection

def topk_mask(scores, valid, k: int):
    """bool ``[..., L]``: the k valid positions of largest score in each row
    (all valid ones where a row has no more than k), ties at the threshold
    going to the lower position — the set ``lax.top_k`` returns on the row
    with invalid positions at -inf."""
    if scores.shape[-1] <= k:
        return valid
    u = ordered_bits(scores, valid)
    t = kth_largest(u, k)[..., None]
    above = u > t
    at = (u == t) & valid
    room = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    return above | (at & (jnp.cumsum(at, axis=-1, dtype=jnp.int32) <= room))


def selected_rows(scores, valid, page_table, page_size: int, k: int):
    """(rows ``[B, K]`` int32, n ``[B]``), ``K = min(k, L)``: where the
    positions ``topk_mask`` selects lie in a pool seen as rows
    ``[pages * page_size, W]``, through ``page_table [B, blocks]``, in
    ascending order of position; of each slot's rows the first ``n[b]``
    count, the rest point at its position 0. Sentinel table entries clamp
    to the trash page.

    The compaction carries addresses, not positions: every position's pool
    row is laid out first (the table repeated along lanes), the block of
    the j-th selected row and the count before that block both come from
    one compare of the inclusive block counts with j, ONE gather fetches
    that block's 128 addresses (-1 where not selected), and the lane is the
    one whose running count of selected entries reaches the rest."""
    B, L = scores.shape
    sel = topk_mask(scores, valid, k)
    page = jnp.repeat(jnp.maximum(page_table, 0), page_size, axis=1)[:, :L]
    addr = page * page_size + jnp.arange(L, dtype=jnp.int32) % page_size
    a3 = jnp.where(sel, addr, -1)
    pad = (-L) % _LANE_BLOCK
    if pad:
        a3 = jnp.pad(a3, ((0, 0), (0, pad)), constant_values=-1)
    a3 = a3.reshape(B, -1, _LANE_BLOCK)
    cnt = jnp.sum(a3 >= 0, axis=-1, dtype=jnp.int32)   # [B, nblk]
    cum = jnp.cumsum(cnt, axis=-1, dtype=jnp.int32)    # inclusive
    n = cum[:, -1]
    j = jnp.arange(min(k, L), dtype=jnp.int32)
    # the blocks wholly before the j-th selected row: those whose inclusive
    # count does not pass j
    passed = cum[:, None, :] <= j[None, :, None]       # [B, K, nblk]
    blk = jnp.minimum(jnp.sum(passed, axis=-1, dtype=jnp.int32),
                      a3.shape[1] - 1)
    before = jnp.sum(jnp.where(passed, cnt[:, None, :], 0), axis=-1,
                     dtype=jnp.int32)
    lanes = jnp.take_along_axis(a3, blk[:, :, None], axis=1)  # [B, K, 128]
    live = lanes >= 0
    nth = jnp.cumsum(live, axis=-1, dtype=jnp.int32)
    hit = live & (nth == (j[None, :] - before + 1)[:, :, None])
    rows = jnp.sum(jnp.where(hit, lanes, 0), axis=-1, dtype=jnp.int32)
    return jnp.where(j[None, :] < n[:, None], rows, addr[:, :1]), n


def gather_rows(pool, rows):
    """Rows ``rows [B, K]`` (``selected_rows``) of a token-major pool
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


def sparse_paged_decode(q, k_pool, v_pool, rows, n):
    """One query token per slot over the selected positions of its cache.

    q            ``[B, H_q, D]``
    k/v_pool     ``[pages, 1, page_size, H_kv * D]`` token-major pools
    rows, n      ``[B, K]`` pool rows of the selected positions
                 (``selected_rows``), of which the first ``n[b]`` count

    Returns ``[B, H_q, D]`` in v's dtype: softmax over the selected
    positions only, numerics as ``paged_attention`` (q pre-scaled in its
    own dtype, float32 scores and statistics).
    """
    D = q.shape[-1]
    qs = q * jnp.asarray(1.0 / np.sqrt(D), q.dtype)
    k_rows = gather_rows(k_pool, rows)
    v_rows = gather_rows(v_pool, rows)
    return shard_kernel(_sparse_decode_call,
                        (n.astype(jnp.int32), qs, k_rows, v_rows),
                        (P(), P(), P(), P()), lambda f: P())
