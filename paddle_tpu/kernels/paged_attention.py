"""Ragged paged-decode attention, Pallas TPU (vLLM PagedAttention analog).

One decode step attends each slot's single query token against that slot's
live KV pages only. The pools are ``[num_pages, H_kv, page_size, D]`` (one
per layer); routing is a ``[B, num_blocks]`` int32 page table whose entries
are pool page ids (``-1`` sentinel pads unallocated blocks). Both the table
and the per-slot positions ride as SCALAR-PREFETCH operands
(``PrefetchScalarGridSpec``), so the grid's K/V ``index_map`` can gather the
b-th slot's i-th page directly out of the pool — the kernel never touches a
dense ``[B, S_max]`` view, and pages of finished requests are simply never
fetched.

Grid is ``(B, num_blocks)`` with the block dim sequential: per slot a
flash-style online softmax (exp2 domain, f32 stats in VMEM scratch —
same scheme as flash_attention.py) streams the live pages, skipping blocks
past ``positions[b] // page_size`` entirely and masking the tail of the
last live page with ``token_pos <= positions[b]``. Sentinel entries clamp
to page 0 — a reserved trash page the allocator never hands out — so the
gather stays in-bounds for empty slots and the mask keeps the math right.

GQA runs as a static per-KV-head-group loop: each group is a
``[rep, D] x [D, page]`` dot, so K/V are read once per group instead of
being materialized at query-head width.

Numerics mirror ``serving.kv_cache.decode_attend`` (the oracle): q
pre-scaled in its own dtype, f32 scores/softmax, output cast to v's dtype —
parity is asserted across ragged batches by tests/test_paged_kv.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..core.place import pallas_interpret
from .flash_attention import LANES, LOG2E, NEG_INF
from .mesh import shard_kernel


def _decode_kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, num_blocks: int, page_size: int,
                   num_kv_heads: int, rep: int):
    """Grid (B, num_blocks): pages STREAM through the trailing (sequential)
    dim; running (max, sum, acc) live in VMEM scratch across page
    iterations and the epilogue normalizes on the last block. Blocks at or
    past the slot's live count contribute nothing and are skipped whole."""
    b = pl.program_id(0)
    i = pl.program_id(1)
    pos = pos_ref[b]
    # pages [0, pos // page_size] hold written tokens (position pos is
    # written before the attend — see paged_write_kv)
    live_hi = pos // jnp.int32(page_size) + 1

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(i < live_hi)
    def _compute():
        q = q_ref[0]  # [Hq, D], pre-scaled by 1/sqrt(D) in q's dtype
        k = k_ref[0]  # [Hkv, page_size, D]
        v = v_ref[0]
        # GQA: one [rep, D] x [D, page] dot per KV-head group — K is read
        # at its stored width, never expanded to Hq
        s_groups = [
            jax.lax.dot_general(
                q[g * rep:(g + 1) * rep], k[g], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            for g in range(num_kv_heads)
        ]
        s = jnp.concatenate(s_groups, axis=0) * jnp.float32(LOG2E)
        Hq = s.shape[0]
        tok = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (Hq, page_size), 1)
        s = jnp.where(tok <= pos, s, NEG_INF)  # [Hq, page_size], log2-domain
        m = m_scr[:, 0]
        l = l_scr[:, 0]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp2(s - m_new[:, None])
        alpha = jnp.exp2(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.concatenate([
            jax.lax.dot_general(
                p[g * rep:(g + 1) * rep].astype(v.dtype), v[g],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            for g in range(num_kv_heads)
        ], axis=0)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + pv
        m_scr[...] = jax.lax.broadcast_in_dim(m_new, m_scr.shape, (0,))
        l_scr[...] = jax.lax.broadcast_in_dim(l_new, l_scr.shape, (0,))

    @pl.when(i == num_blocks - 1)
    def _epilogue():
        l = l_scr[:, 0]
        l_safe = jnp.where(l == 0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe[:, None]).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, page_table, positions):
    """Ragged paged-decode attention over block-paged KV pools.

    q            ``[B, H_q, 1, D]`` — one query token per slot
    k/v_pool     ``[P, H_kv, page_size, D]`` — this layer's page pools
    page_table   ``[B, num_blocks]`` int32 pool page ids (-1 = unallocated)
    positions    ``[B]`` int32 — each slot's current token index

    Returns ``[B, H_q, 1, D]`` in v's dtype — drop-in for
    ``decode_attend(q, dense_k, dense_v, positions)`` when the dense caches
    hold the same bytes the table maps (tests pin this parity).

    Under a mesh the heads split over ``mp`` (query and KV heads together,
    so each shard keeps whole GQA groups); pools, table and positions are
    otherwise replicated, as the engine's sharding contract declares.
    """
    B, Hq, T, D = q.shape
    if T != 1:
        raise ValueError(f"paged_attention decodes one token per slot, got T={T}")
    Hkv = k_pool.shape[1]
    qs = (q[:, :, 0, :] * jnp.asarray(1.0 / np.sqrt(D), q.dtype))  # [B, Hq, D]
    table = page_table.astype(jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (B,))
    mp = dict(jax.sharding.get_abstract_mesh().shape).get("mp", 1)
    heads = P(None, "mp") if Hkv % mp == 0 else P()
    out = shard_kernel(_decode_call, (table, pos, qs, k_pool, v_pool),
                       (P(), P(), heads, heads, heads), lambda f: f[2])
    return out[:, :, None, :]


def _decode_call(table, pos, qs, k_pool, v_pool):
    B, Hq, D = qs.shape
    _, Hkv, page_size, _ = k_pool.shape
    num_blocks = table.shape[1]
    rep = Hq // Hkv

    def _page_map(b, i, tbl, _pos):
        # sentinel entries clamp to the reserved trash page so the fetch
        # stays in-bounds; the live_hi bound keeps them out of the math
        return (jnp.maximum(tbl[b, i], 0), 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, num_blocks),
        in_specs=[
            pl.BlockSpec((1, Hq, D), lambda b, i, tbl, _pos: (b, 0, 0)),
            pl.BlockSpec((1, Hkv, page_size, D), _page_map),
            pl.BlockSpec((1, Hkv, page_size, D), _page_map),
        ],
        out_specs=pl.BlockSpec((1, Hq, D), lambda b, i, tbl, _pos: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hq, LANES), jnp.float32),
            pltpu.VMEM((Hq, LANES), jnp.float32),
            pltpu.VMEM((Hq, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, num_blocks=num_blocks,
                          page_size=page_size, num_kv_heads=Hkv, rep=rep),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), v_pool.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=pallas_interpret(),
        name="paged_decode",
    )(table, pos, qs, k_pool, v_pool)
