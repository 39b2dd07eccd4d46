"""Multi-head latent attention (MLA, arXiv:2412.19437 section 2.1) on the
TPU, two Pallas kernels: ``latent_paged_decode``, the ABSORBED form over a
paged pool of latent rows (decode), and ``latent_flash``, the EXPANDED form
over keys and values just expanded from such rows (prefill and extend).

``latent_paged_decode``, a ragged paged decode over a pool of latent rows.

What the cache keeps a token is one row ``[c | k_pe | idle lanes]`` (``W``
lanes: ``models/decoder.latent_pool_width``), the same for every head, keys
and values alike. With the keys' up-projection folded into the query
(``q~_h = q_nope_h W_uk_h^T``) a head's score against a token is one dot of
``[q~_h | q_pe_h | 0]`` with the row, and what it attends is the row's first
``value_width`` lanes (``c``); the values' up-projection is applied to the
result by the caller. So every head reads the SAME bytes: the ``H`` query
heads are the rows of one ``[H, W] x [W, tokens]`` matmul a chunk, and a row
is fetched once for scores and values.

The walk is ``paged_attention.py``'s: the pool ``[P, 1, page_size, W]``
stays in HBM, the page table and the positions ride as scalar-prefetch
operands, the grid is one step a slot, and inside it a ``fori_loop`` over
CHUNKS of the slot's own live pages, each page fetched by the kernel's own
``make_async_copy`` into one of two VMEM buffers, chunk ``c + 1`` in flight
while chunk ``c`` is computed. A chunk is ``_CHUNK_TOKENS`` tokens (not one
lane width as there: with one row a token the per-chunk overhead would lead,
and a 512 x 640 bfloat16 buffer is 0.6 MiB). Pages of a chunk past the live
count are not fetched and their rows zeroed; a dead slot makes no trip and
yields zeros.

Numerics mirror ``serving.kv_cache.latent_decode_attend``'s oracle: q
pre-scaled in its own dtype, float32 scores (exp2 domain), float32 online
softmax, probabilities cast to the pool's dtype for the second matmul.

``latent_flash``, causal flash attention for queries that sit BEHIND a
cached context, with a key in two parts and a value width of its own: a
head's score is ``q_nope . k_nope + q_pe . k_pe`` (``[G, T, dn]`` / ``[G, L,
dn]`` a head, and ``[G, T, dr]`` against ONE ``k_pe [B, L, dr]`` a sequence,
which all its heads share and no one copies), query row t at position
``start + t``, key row s at position s; values ``[G, L, Dv]``. 128 + 64 and
128 at the published widths, so ``kernels/flash_attention`` (square, one
operand a side, one width) does not serve. The scheme
is that kernel's forward: grid (G, query blocks, key blocks), keys streamed
through the trailing sequential dimension, float32 (max, sum, acc) in VMEM
scratch, exp2 domain; key blocks wholly behind a query block's last position
are neither computed nor fetched (their block index repeats the last one
that is). Without it the expanded form is XLA einsums whose float32 score
tiles go through HBM three times a key block: a 34,816-token prefill took
15.2 s of which 13.0 in those fusions (my chip run, PR 40).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.sharding import PartitionSpec as P

from ..core.place import pallas_interpret
from .flash_attention import LOG2E, NEG_INF
from .mesh import shard_kernel

#: tokens fetched and computed together
_CHUNK_TOKENS = 512


def _kernel(tbl_ref, pos_ref, q_ref, pool_hbm, o_ref, buf, sems, *,
            num_blocks: int, page_size: int, chunk: int, value_width: int):
    """Grid (B,): one step a slot. ``buf`` is ``[2, chunk, page_size, W]``
    VMEM, ``sems`` two DMA semaphores (one a buffer); the running (max, sum,
    acc) are the loop's carry."""
    b = pl.program_id(0)
    pos = pos_ref[b]
    live = jnp.where(tbl_ref[b, 0] < 0, 0,
                     jnp.minimum(pos // page_size + 1, num_blocks))
    chunk_tokens = chunk * page_size
    H, W = q_ref.shape[1:]

    def page_copy(i, slot, j):
        # a sentinel inside the live range clamps to the trash page
        page = jnp.maximum(tbl_ref[b, i], 0)
        return pltpu.make_async_copy(pool_hbm.at[page, 0], buf.at[slot, j],
                                     sems.at[slot])

    def fetch(c, slot):
        for j in range(chunk):
            i = c * chunk + j

            @pl.when(i < live)
            def _start():
                page_copy(i, slot, j).start()

            @pl.when(i >= live)
            def _blank():
                # never fetched: its weight is exactly 0, but 0 x whatever
                # the buffer held (it starts uninitialised) need not be
                buf[slot, j] = jnp.zeros(buf.shape[2:], buf.dtype)

    def wait(c, slot):
        for j in range(chunk):
            @pl.when(c * chunk + j < live)
            def _wait():
                page_copy(c * chunk + j, slot, j).wait()

    q = q_ref[0]                                   # [H, W], pre-scaled

    def body(c, carry):
        m, l, acc = carry
        slot = c % 2

        @pl.when((c + 1) * chunk < live)
        def _prefetch():
            fetch(c + 1, 1 - slot)

        wait(c, slot)
        rows = buf[slot].reshape(chunk_tokens, W)
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * jnp.float32(LOG2E)
        tok = c * chunk_tokens + jax.lax.broadcasted_iota(
            jnp.int32, (H, chunk_tokens), 1)
        s = jnp.where(tok <= pos, s, NEG_INF)      # [H, chunk_tokens], log2
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m - m_new)
        pv = jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :value_width],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return (m_new, l * alpha + jnp.sum(p, axis=-1, keepdims=True),
                acc * alpha + pv)

    @pl.when(live > 0)
    def _first():
        fetch(0, 0)

    _, l, acc = jax.lax.fori_loop(
        0, (live + chunk - 1) // chunk, body,
        (jnp.full((H, 1), NEG_INF, jnp.float32),
         jnp.zeros((H, 1), jnp.float32),
         jnp.zeros((H, value_width), jnp.float32)))
    o_ref[0] = (acc / jnp.where(l == 0, 1.0, l)).astype(o_ref.dtype)


def latent_paged_decode(q, pool, page_table, positions, value_width: int):
    """One query token a slot against the slot's live latent rows.

    q            ``[B, H, W]``, pre-scaled, as wide as a row (zeros in the
                 idle lanes)
    pool         ``[P, 1, page_size, W]``: this layer's latent pool
    page_table   ``[B, num_blocks]`` int32 pool page ids (-1 = unallocated)
    positions    ``[B]`` int32: each slot's current token index

    Returns ``[B, H, value_width]`` in the pool's dtype: per head the
    softmax-weighted sum of the rows' first ``value_width`` lanes."""
    call = functools.partial(_decode_call, value_width=value_width,
                             interpret=pallas_interpret())
    # every head reads the same rows: nothing to divide over a mesh
    return shard_kernel(call, (page_table.astype(jnp.int32),
                               jnp.asarray(positions, jnp.int32), q, pool),
                        (P(),) * 4, lambda fitted: P())


# jitted so that a model's layers share ONE trace and ONE Mosaic lowering in
# the program they are traced into (as ``paged_attention._decode_call``)
@functools.partial(jax.jit, static_argnames=("value_width", "interpret"))
def _decode_call(table, pos, q, pool, *, value_width: int, interpret: bool):
    B, H, W = q.shape
    _, _, page_size, _ = pool.shape
    chunk = max(1, _CHUNK_TOKENS // page_size)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, W), lambda b, _tbl, _pos: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, value_width),
                               lambda b, _tbl, _pos: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, chunk, page_size, W), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, num_blocks=table.shape[1],
                          page_size=page_size, chunk=chunk,
                          value_width=value_width),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, value_width), pool.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="latent_paged_decode",
    )(table, pos, q, pool)


# ------------------------------------------------- the expanded form

def _blocks(T: int, L: int):
    """(queries, keys) a block: the largest of the listed sizes that divide
    the length, else the whole of it (small test shapes)."""
    pick = lambda n, sizes: next((b for b in sizes if n % b == 0), n)
    return pick(T, (1024, 512, 256, 128)), pick(L, (512, 256, 128))


def _last_block(start, qi, block_q: int, block_k: int, num_kb: int):
    """Key blocks [0, this) hold a position some query of block ``qi``
    sees."""
    return jnp.minimum((start + (qi + 1) * block_q + block_k - 1) // block_k,
                       num_kb)


def _flash_kernel(start_ref, qn_ref, qp_ref, kn_ref, kp_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, heads: int, num_kb: int,
                  block_q: int, block_k: int):
    """Grid (G, T / block_q, L / block_k); ``start_ref [G / heads]`` the
    position of each sequence's first query."""
    qi, ki = pl.program_id(1), pl.program_id(2)
    start = start_ref[pl.program_id(0) // heads]
    kb_hi = _last_block(start, qi, block_q, block_k, num_kb)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ki < kb_hi)
    def _compute():
        v = v_ref[0]
        nt = lambda a, b: jax.lax.dot_general(       # a b^T, float32
            a, b, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = (nt(qn_ref[0], kn_ref[0]) + nt(qp_ref[0], kp_ref[0])) \
            * jnp.float32(LOG2E)                     # q pre-scaled
        qpos = start + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)
        # key 0 is behind every query and block 0 is walked first, so no
        # row's running maximum stays at the mask's value
        m, l = m_scr[:, :1], l_scr[:, :1]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m - m_new)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(
            l * alpha + jnp.sum(p, axis=-1, keepdims=True), l_scr.shape)

    @pl.when(ki == num_kb - 1)
    def _epilogue():
        o_ref[0] = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)


def latent_flash(qn, qp, kn, kp, v, starts, heads: int):
    """Causal attention of the queries ``qn [G, T, dn]`` / ``qp [G, T, dr]``
    (pre-scaled; row t of sequence ``g // heads`` at position ``starts[g //
    heads] + t``) over the keys ``kn [G, L, dn]`` / ``kp [G / heads, L,
    dr]`` (one rotary key a sequence for all its heads) and ``v [G, L,
    Dv]`` (row s at position s): ``[G, T, Dv]`` in v's dtype. float32
    scores and online softmax."""
    call = functools.partial(_flash_call, heads=heads,
                             interpret=pallas_interpret())
    return shard_kernel(call, (jnp.asarray(starts, jnp.int32), qn, qp, kn, kp,
                               v), (P(),) * 6, lambda fitted: P())


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _flash_call(starts, qn, qp, kn, kp, v, *, heads: int, interpret: bool):
    G, T, dn = qn.shape
    dr, L, Dv = qp.shape[2], v.shape[1], v.shape[2]
    bq, bk = _blocks(T, L)
    num_kb = L // bk

    def block(g, qi, ki, start_ref):
        # a block past the last one computed repeats it: nothing is fetched
        last = _last_block(start_ref[g // heads], qi, bq, bk, num_kb) - 1
        return jnp.minimum(ki, last)

    q_map = lambda g, qi, ki, _s: (g, qi, 0)
    k_map = lambda g, qi, ki, s: (g, block(g, qi, ki, s), 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G, T // bq, num_kb),
        in_specs=[
            pl.BlockSpec((1, bq, dn), q_map),
            pl.BlockSpec((1, bq, dr), q_map),
            pl.BlockSpec((1, bk, dn), k_map),
            pl.BlockSpec((1, bk, dr), lambda g, qi, ki, s: (
                g // heads, block(g, qi, ki, s), 0)),
            pl.BlockSpec((1, bk, Dv), k_map),
        ],
        out_specs=pl.BlockSpec((1, bq, Dv), q_map),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_flash_kernel, heads=heads, num_kb=num_kb,
                          block_q=bq, block_k=bk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, T, Dv), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="latent_flash",
    )(starts, qn, qp, kn, kp, v)
