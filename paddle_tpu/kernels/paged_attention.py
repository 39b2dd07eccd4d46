"""Ragged paged-decode attention, Pallas TPU (vLLM PagedAttention analog).

One decode step attends each slot's single query token against that slot's
live KV pages only. The pools are ``[num_pages, H_kv, page_size, D]`` (one
per layer); routing is a ``[B, num_blocks]`` int32 page table whose entries
are pool page ids (``-1`` sentinel pads unallocated blocks). The table and
the per-slot positions ride as SCALAR-PREFETCH operands
(``PrefetchScalarGridSpec``); the pools are handed over whole and stay in
HBM (``memory_space=pl.ANY``).

The grid is one step a slot, whatever the table's width. Inside a step the
kernel walks the slot's own live pages (``positions[b] // page_size + 1`` of
them; none for a slot whose first table entry is the sentinel) in a
``fori_loop`` over CHUNKS of pages: each page of a chunk — a contiguous
``[H_kv, page_size, D]`` block of its pool — is fetched by the kernel's own
``make_async_copy`` into one of two VMEM buffers a pool, so chunk ``c + 1``
is in flight while chunk ``c`` is computed. What the walk costs beside the
copies themselves is paid once a CHUNK: a chunk wholly inside the live range
(every chunk but a slot's last) starts its copies with no condition a page
and is awaited by ONE wait a pool, for the bytes of the whole buffer; only a
slot's last chunk counts its live pages, starts and awaits those a page at a
time and zeroes the V rows of the others, which are not fetched, so whatever
a buffer held before never reaches the sum. Pages of finished requests and
the rest of the table are never touched. Every copy is in bounds by
construction (the page id clamped into the pool, the destination a buffer's
own page), so the call turns off Mosaic's check of each one, which was two
thirds of a page's scalar work. A chunk's width comes from the shapes alone
(``_pages_per_chunk``): about half a MiB a buffer, at most 1,024 tokens and a
128 KiB score tile, whole lane widths of tokens: 64 pages of 16 tokens at 2
K/V heads, 16 at 8, 8 at 16 and more.

Per chunk a flash-style online softmax (exp2 domain, f32 statistics carried
through the loop — same scheme as flash_attention.py) masks the tail of the
last live page with ``token_pos <= positions[b]``. A dead slot runs no
iteration, fetches nothing and yields a row of zeros, which the engine never
reads.

GQA runs as a static per-KV-head-group loop: each group is a
``[rep, D] x [D, chunk_tokens]`` dot, so K/V are read once per group instead
of being materialized at query-head width.

Numerics mirror ``decode_attend``, the jnp reference below (the oracle): q
pre-scaled in its own dtype, f32 scores/softmax, output cast to v's dtype —
parity is asserted across ragged batches by tests/test_paged_kv.py.

``T`` queries a slot (a prefix hit's suffix, a verify's drafts) have a
kernel of their own, ``extend_flash`` (``window_extend_flash`` under a
window): a causal flash kernel over the GATHERED view of a slot's pages,
one K/V head's query heads side by side as the rows of a score matmul, the
scores in VMEM alone, key blocks no query sees neither fetched nor computed.

Behind the kernels stand their references over dense ``[B, H_kv, S_max, D]``
caches (``decode_attend``, and ``extend_attend`` for ``T`` queries a slot)
and the entries a model's layer calls, ``paged_decode_attend`` and
``paged_extend_attend``: each picks kernel or reference as
``tier.default_paged_impl`` says. The dense pair is also the lockstep
decode of ``GPTForCausalLM.generate`` and
``incubate.nn.FusedMultiTransformer``'s ``time_step``, so the cached
attention implementations cannot drift. Their numerics deliberately mirror
``nn.functional._sdpa_ref`` (pre-scaled q, f32 logits, -1e30 masking, f32
softmax), so cached decode logits match the full-prefix causal forward
within float tolerance (tests/test_serving.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..core.place import pallas_interpret
from .flash_attention import (LANES, LOG2E, NEG_INF, last_key_block,
                              online_softmax_init, online_softmax_step)
from .mesh import shard_kernel
from .pools import PAGE_SENTINEL, paged_gather
from .tier import default_paged_impl


# Both buffers of both pools have to sit well inside Mosaic's scoped VMEM
# (16 MiB on the smallest generation this runs on).
_CHUNK_VMEM_BYTES = 8 * 1024 * 1024
# What one buffer holds where the walk runs near its roofline (the chat
# shape's 8 pages of 64 KiB), the most tokens a chunk folds at once, and the
# most a chunk's [H_q, chunk_tokens] float32 score tile may take.
_CHUNK_BUFFER_BYTES = 512 * 1024
_CHUNK_TOKENS = 1024
_SCORE_TILE_BYTES = 128 * 1024
# A whole chunk's copies are issued this many pages a turn of a rolled loop.
_PAGES_A_TURN = 8


def _pages_per_chunk(num_kv_heads: int, num_q_heads: int, page_size: int,
                     head_dim: int, itemsize: int) -> int:
    """Pages fetched and computed together, from the shapes alone: as many
    as fill one buffer of ``_CHUNK_BUFFER_BYTES``, at most ``_CHUNK_TOKENS``
    tokens and a score tile of ``_SCORE_TILE_BYTES``, in whole lane widths
    of tokens and never under one; fewer only where four buffers of that
    many pages (two a pool) would not fit the VMEM budget."""
    page_bytes = num_kv_heads * page_size * head_dim * itemsize
    lane_pages = -(-LANES // page_size)
    tokens = min(_CHUNK_TOKENS, _SCORE_TILE_BYTES // (4 * num_q_heads))
    pages = min(_CHUNK_BUFFER_BYTES // page_bytes, tokens // page_size)
    pages = max(pages // lane_pages, 1) * lane_pages
    return max(1, min(pages, _CHUNK_VMEM_BYTES // (4 * page_bytes)))


def _decode_kernel(tbl_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
                   k_buf, v_buf, sems, *, num_blocks: int, page_size: int,
                   num_kv_heads: int, rep: int, chunk: int, window=None):
    """Grid (B,): one step a slot. ``k_buf`` / ``v_buf`` are
    ``[2, chunk, H_kv, page_size, D]`` VMEM buffers, ``sems`` ``[2, 2]`` DMA
    semaphores (pool, buffer); the running (max, sum, acc) are the loop's
    carry. With ``window`` the walk starts at the block that holds the
    window's first token, ``pos - window + 1`` (``live`` then counts the
    pages from there), and that block's older tokens are masked: a page
    behind the window is never fetched."""
    b = pl.program_id(0)
    pos = pos_ref[b]
    if window is None:
        # pages [0, pos // page_size] hold written tokens (position pos is
        # written before the attend — see paged_write_kv); a slot with no
        # first page is dead
        first = 0
        live = jnp.where(tbl_ref[b, 0] < 0, 0,
                         jnp.minimum(pos // page_size + 1, num_blocks))
    else:
        # the blocks behind a live slot's window are sentinels, its first
        # among them: the block of its LAST token says whether it lives
        last = jnp.minimum(pos // page_size, num_blocks - 1)
        lo = jnp.maximum(pos - (window - 1), 0)
        first = lo // page_size
        live = jnp.where(tbl_ref[b, last] < 0, 0, last + 1 - first)
    chunk_tokens = chunk * page_size
    Hq, D = q_ref.shape[1:]
    last_page = k_hbm.shape[0] - 1

    def start_page(i, buf, j):
        # the page id is held inside the pool whatever the table holds (a
        # sentinel reads the reserved trash page), and the destination is a
        # buffer's own page: the copies are in bounds by construction, which
        # is what lets the call leave out Mosaic's check of each one
        page = jnp.clip(tbl_ref[b, first + i], 0, last_page)
        pltpu.make_async_copy(k_hbm.at[page], k_buf.at[buf, j],
                              sems.at[0, buf]).start()
        pltpu.make_async_copy(v_hbm.at[page], v_buf.at[buf, j],
                              sems.at[1, buf]).start()

    def wait_pages(buf, pages):
        # a wait takes the semaphore and the destination's size, not the
        # source: ``pages`` of a buffer (a slice of it, or all of it) wait
        # for as many page copies as were signalled on its semaphore
        for pool_buf, sem in ((k_buf, sems.at[0, buf]),
                              (v_buf, sems.at[1, buf])):
            dst = pool_buf.at[buf] if pages is None else pool_buf.at[buf, pages]
            pltpu.make_async_copy(dst, dst, sem).wait()

    turn = math.gcd(chunk, _PAGES_A_TURN)

    def live_in(c):
        """Live pages of chunk ``c``: ``chunk`` for every chunk of a slot
        but its last."""
        return jnp.clip(live - c * chunk, 0, chunk)

    def fetch(c, buf):
        """Start chunk ``c``'s copies into buffer ``buf``. A chunk wholly
        inside the live range (every chunk but a slot's last) issues them
        with no condition a page; the last one issues its live pages and
        zeroes the V rows of the others, which are never fetched: p is
        exactly 0 there, but 0 x whatever the buffer held (it starts
        uninitialised) need not be."""
        n = live_in(c)

        @pl.when(n == chunk)
        def _whole():
            @pl.loop(0, chunk // turn)
            def _(t):
                for j in range(turn):
                    start_page(c * chunk + t * turn + j, buf, t * turn + j)

        @pl.when(n < chunk)
        def _partial():
            @pl.loop(0, n)
            def _(j):
                start_page(c * chunk + j, buf, j)

            @pl.loop(n, chunk)
            def _(j):
                v_buf[buf, j] = jnp.zeros(v_buf.shape[2:], v_buf.dtype)

    def wait(c, buf):
        """One wait a pool for a whole chunk; a page at a time for the live
        pages of a partial one (a copy's size is static)."""
        n = live_in(c)

        @pl.when(n == chunk)
        def _whole():
            wait_pages(buf, None)

        @pl.when(n < chunk)
        def _partial():
            @pl.loop(0, n)
            def _(j):
                wait_pages(buf, j)

    q = q_ref[0]  # [Hq, D], pre-scaled by 1/sqrt(D) in q's dtype

    def body(c, carry):
        m, l, acc = carry
        buf = c % 2

        @pl.when((c + 1) * chunk < live)
        def _prefetch():
            fetch(c + 1, 1 - buf)

        wait(c, buf)
        # GQA: one [rep, D] x [D, chunk_tokens] dot per KV-head group — K is
        # read at its stored width, never expanded to Hq
        s = jnp.concatenate([
            jax.lax.dot_general(
                q[g * rep:(g + 1) * rep],
                k_buf[buf, :, g].reshape(chunk_tokens, D),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            for g in range(num_kv_heads)
        ], axis=0) * jnp.float32(LOG2E)
        tok = (c * chunk + first) * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (Hq, chunk_tokens), 1)
        if window is None:
            s = jnp.where(tok <= pos, s, NEG_INF)  # [Hq, chunk_tokens], log2
        else:
            s = jnp.where((tok <= pos) & (tok >= lo), s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.concatenate([
            jax.lax.dot_general(
                p[g * rep:(g + 1) * rep].astype(v_buf.dtype),
                v_buf[buf, :, g].reshape(chunk_tokens, D),
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            for g in range(num_kv_heads)
        ], axis=0)
        return m_new, l_new, acc * alpha + pv

    @pl.when(live > 0)
    def _first():
        fetch(0, 0)

    _, l, acc = jax.lax.fori_loop(
        0, (live + chunk - 1) // chunk, body,
        (jnp.full((Hq, 1), NEG_INF, jnp.float32),
         jnp.zeros((Hq, 1), jnp.float32), jnp.zeros((Hq, D), jnp.float32)))
    o_ref[0] = (acc / jnp.where(l == 0, 1.0, l)).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, page_table, positions, window=None,
                    scale=None):
    """Ragged paged-decode attention over block-paged KV pools.

    q            ``[B, H_q, 1, D]`` — one query token per slot
    k/v_pool     ``[P, H_kv, page_size, D]`` — this layer's page pools
    page_table   ``[B, num_blocks]`` int32 pool page ids (-1 = unallocated)
    positions    ``[B]`` int32 — each slot's current token index

    window       static; None: every token up to ``positions[b]``. An
                 int: the last ``window`` of them alone (a sliding layer):
                 the walk starts at the window's first block, whatever the
                 table holds before it (sentinels, once the engine has
                 freed those pages). A kernel of its own by NAME
                 (``window_decode``; ``paged_decode`` is the full one's)

    scale        the softmax scale; None: ``D^-1/2`` (a differential layer's
                 widened queries, ``diff_widen``, keep the scale of their
                 own head's width)

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
    qs = q[:, :, 0, :] * _scale(scale, q)                       # [B, Hq, D]
    table = page_table.astype(jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (B,))
    mp = dict(jax.sharding.get_abstract_mesh().shape).get("mp", 1)
    heads = P(None, "mp") if Hkv % mp == 0 else P()
    call = functools.partial(_decode_call, interpret=pallas_interpret())
    if window is not None:
        call = functools.partial(call, window=int(window))
    out = shard_kernel(call, (table, pos, qs, k_pool, v_pool),
                       (P(), P(), heads, heads, heads), lambda f: f[2])
    return out[:, :, None, :]


# jitted so that a model's layers, which call it with the same shapes, share
# ONE trace and ONE Mosaic lowering in the program they are traced into;
# ``interpret`` is the caller's reading of the platform, so it is part of the
# cache's key
@functools.partial(jax.jit, static_argnames=("interpret", "window"))
def _decode_call(table, pos, qs, k_pool, v_pool, *, interpret: bool,
                 window=None):
    B, Hq, D = qs.shape
    _, Hkv, page_size, _ = k_pool.shape
    chunk = _pages_per_chunk(Hkv, Hq, page_size, D, k_pool.dtype.itemsize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hq, D), lambda b, _tbl, _pos: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Hq, D), lambda b, _tbl, _pos: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, chunk, Hkv, page_size, D), k_pool.dtype),
            pltpu.VMEM((2, chunk, Hkv, page_size, D), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kernel = functools.partial(_decode_kernel, num_blocks=table.shape[1],
                               page_size=page_size, num_kv_heads=Hkv,
                               rep=Hq // Hkv, chunk=chunk)
    if window is not None:
        kernel = functools.partial(kernel, window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), v_pool.dtype),
        # the kernel holds every copy in bounds itself (``start_page``)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), disable_bounds_checks=True),
        interpret=interpret,
        name="paged_decode" if window is None else "window_decode",
    )(table, pos, qs, k_pool, v_pool)


# ---------------------------------------------------------------------------
# The extend: T queries a slot behind its cached context, over the gathered
# head-major view (``extend_flash`` / ``window_extend_flash``)
# ---------------------------------------------------------------------------

#: score rows of a grid step whose scores are one tile of the online softmax
#: (``latent_attention._FLASH_ROWS``'s twin: PERF.md section 6, PR 44), the
#: rows a grid step fills where the shapes allow (one K/V block is fetched
#: for all of them), and the keys a step folds at once
_EXTEND_CHUNK_ROWS = 128
_EXTEND_STEP_ROWS = 1024
_EXTEND_KEYS = 1024
#: a query block is whole sublane tiles of every dtype the pools take
_EXTEND_QUERY_TILE = 16


def _extend_blocks(rep: int, T: int, L: int):
    """(queries, keys) a grid step, from the shapes the call sees. Queries:
    the largest power of two that divides ``T`` (a multiple of
    ``_EXTEND_QUERY_TILE``) with ``rep`` times as many score rows inside
    ``_EXTEND_STEP_ROWS``: 64 at 16 query heads a K/V head, 1,024 at one.
    Keys: ``_EXTEND_KEYS`` or the largest halving of it down to 128 that
    divides ``L``, else the whole view (small test shapes;
    ``paged_extend_attend`` pads its table so that the first holds)."""
    bq = _EXTEND_QUERY_TILE
    while bq * 2 * rep <= _EXTEND_STEP_ROWS and T % (bq * 2) == 0:
        bq *= 2
    bk = _EXTEND_KEYS
    while bk > LANES and L % bk:
        bk //= 2
    return bq, bk if L % bk == 0 else L


def _key_blocks(rel, qi, block_q: int, block_k: int, num_kb: int, window):
    """Key blocks [lo, hi) of the view hold a row some query of block
    ``qi`` sees; ``rel`` is the view row of the sequence's first query.
    Under a window the walk starts at the block of the first query's
    oldest key."""
    hi = last_key_block(rel, qi, block_q, block_k, num_kb)
    if window is None:
        return 0, hi
    lo = jnp.maximum(rel + qi * block_q - (window - 1), 0) // block_k
    return jnp.minimum(lo, num_kb - 1), hi


def _extend_kernel(rel_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, num_kb: int, steps: int, block_q: int,
                   block_k: int, window=None):
    """Grid (B, H_kv, T / block_q, steps): one K/V head's ``rep`` query
    heads x ``block_q`` queries stand side by side as the score rows of a
    step (``q_ref [1, rep, block_q, D]``; row ``h * block_q + t``), against
    key block ``lo + step`` of the view. ``rel_ref [B]``: the view row at
    which each sequence's first query stands (row s of the view is visible
    to query t where ``s <= rel + t``, and under a window where also ``s >
    rel + t - window``)."""
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    rep, D = q_ref.shape[1], q_ref.shape[3]
    rel = rel_ref[b]
    lo, hi = _key_blocks(rel, qi, block_q, block_k, num_kb, window)

    pl.when(ki == 0)(lambda: online_softmax_init(m_scr, l_scr, acc_scr))

    # a chunk of the step's rows is whole heads' queries, or a part of one
    # head's; a block neither fits goes whole
    rows = rep * block_q
    if rows % _EXTEND_CHUNK_ROWS == 0 and (
            _EXTEND_CHUNK_ROWS % block_q == 0
            or block_q % _EXTEND_CHUNK_ROWS == 0):
        rows = _EXTEND_CHUNK_ROWS
    heads, parts = max(rows // block_q, 1), max(block_q // rows, 1)

    def scores(r):
        if parts == 1:
            q = q_ref[0, pl.ds(r * heads, heads)].reshape(rows, D)
            t = jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 0)
            t = t & (block_q - 1) if heads > 1 else t   # a power of two
        else:
            q = q_ref[0, r // parts, pl.ds(r % parts * rows, rows)]
            t = r % parts * rows \
                + jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 0)
        s = jax.lax.dot_general(                     # q k^T, float32
            q, k_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * jnp.float32(LOG2E)
        # how far behind its query a key stands: none before it, and under
        # a window none ``window`` or more behind
        behind = rel + qi * block_q - (lo + ki) * block_k + t \
            - jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 1)
        seen = behind >= 0 if window is None \
            else (behind >= 0) & (behind < window)
        return jnp.where(seen, s, NEG_INF)

    @pl.when(lo + ki < hi)
    def _compute():
        online_softmax_step(scores, lambda: v_ref[0, 0], m_scr, l_scr,
                            acc_scr, rows=rows,
                            chunks=rep * block_q // rows)

    @pl.when(ki == steps - 1)
    def _epilogue():
        o_ref[0] = (acc_scr[...] / l_scr[:, :1]).reshape(
            rep, block_q, D).astype(o_ref.dtype)


def extend_flash(q, k_view, v_view, starts, window=None, first=None):
    """Causal attention of ``T`` queries a sequence behind its cached
    context, over gathered head-major views: the Pallas tier of
    ``paged_extend_attend`` and the kernel ``extend_attend`` is the oracle
    of.

    q            ``[B, H_q, T, D]``, PRE-SCALED; query ``t`` of row ``b``
                 at position ``starts[b] + t``
    k/v_view     ``[B, H_kv, L, D]`` as ``pools.paged_gather`` returns
                 them: row ``s`` at position ``first[b] + s`` (``first``
                 None: position ``s``). A K/V head is read ONCE for its
                 ``H_q / H_kv`` query heads, whose rows share a score
                 matmul
    window       static; None: every key up to the query's own position.
                 An int: those less than ``window`` behind it alone (a
                 sliding layer's view of its window and the new tokens,
                 ``pools.window_blocks``). A kernel of its own by NAME
                 (``window_extend_flash``; ``extend_flash`` is the full
                 one's), as ``window_decode`` beside ``paged_decode``

    Returns ``[B, H_q, T, D]`` in v's dtype. float32 scores and online
    softmax in VMEM (``flash_attention.online_softmax_step``: 1,024 keys a
    fold, 128 score rows a tile); key blocks no query of a block sees are
    neither fetched nor computed (before the window's first, past the
    block's last query). ``T`` is padded to whole sublane tiles here; the
    views go in as they are (``_extend_blocks``)."""
    B, Hq, T, D = q.shape
    starts = jnp.asarray(starts, jnp.int32)
    rel = starts if first is None else starts - jnp.asarray(first, jnp.int32)
    pad = -T % _EXTEND_QUERY_TILE
    if pad:     # rows behind the real ones: they see more, and are dropped
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    mp = dict(jax.sharding.get_abstract_mesh().shape).get("mp", 1)
    heads = P(None, "mp") if k_view.shape[1] % mp == 0 else P()
    call = functools.partial(_extend_call, interpret=pallas_interpret())
    if window is not None:
        call = functools.partial(call, window=int(window))
    out = shard_kernel(call, (rel, q, k_view, v_view),
                       (P(), heads, heads, heads), lambda f: f[1])
    return out[:, :, :T] if pad else out


# jitted for the reason ``_decode_call`` is: a model's layers share ONE trace
# and ONE Mosaic lowering in the program they are traced into
@functools.partial(jax.jit, static_argnames=("interpret", "window"))
def _extend_call(rel, q, k_view, v_view, *, interpret: bool, window=None):
    B, Hq, T, D = q.shape
    Hkv, L = k_view.shape[1], k_view.shape[2]
    rep = Hq // Hkv
    bq, bk = _extend_blocks(rep, T, L)
    num_kb = L // bk
    # the blocks a query block's window and its own keys can lie across
    steps = num_kb if window is None else min(
        num_kb, (window + bq + bk - 3) // bk + 1)

    def block(b, qi, ki, rel_ref):
        # a step past the last block computed repeats it: nothing is fetched
        lo, hi = _key_blocks(rel_ref[b], qi, bq, bk, num_kb, window)
        return jnp.minimum(lo + ki, hi - 1)

    q_map = lambda b, g, qi, ki, _r: (b, g, qi, 0)
    k_map = lambda b, g, qi, ki, r: (b, g, block(b, qi, ki, r), 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, T // bq, steps),
        in_specs=[
            pl.BlockSpec((1, rep, bq, D), q_map),
            pl.BlockSpec((1, 1, bk, D), k_map),
            pl.BlockSpec((1, 1, bk, D), k_map),
        ],
        out_specs=pl.BlockSpec((1, rep, bq, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((rep * bq, LANES), jnp.float32),
            pltpu.VMEM((rep * bq, LANES), jnp.float32),
            pltpu.VMEM((rep * bq, D), jnp.float32),
        ],
    )
    kernel = functools.partial(_extend_kernel, num_kb=num_kb, steps=steps,
                               block_q=bq, block_k=bk)
    if window is not None:
        kernel = functools.partial(kernel, window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, T, D), v_view.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="extend_flash" if window is None else "window_extend_flash",
    )(rel, q, k_view, v_view)


# ---------------------------------------------------------------------------
# The jnp references (dense ``[B, H_kv, S, D]`` caches) and the entries a
# model's layer calls, which pick between kernel and reference (tier.py)
# ---------------------------------------------------------------------------


def _expand_kv_heads(t, rep: int):
    """GQA: broadcast [B, H_kv, S, D] -> [B, H_kv*rep, S, D]. A broadcast
    (insert group dim + reshape), not repeat: XLA keeps it fused into the
    attention einsums instead of materializing full-width K/V."""
    if rep == 1:
        return t
    B, Hkv, S, D = t.shape
    return jnp.broadcast_to(t[:, :, None], (B, Hkv, rep, S, D)).reshape(
        B, Hkv * rep, S, D)


def _scale(scale, q):
    """The softmax scale as a scalar of q's dtype: ``scale``, or ``D^-1/2``.
    (np.sqrt returns a STRONG f64 scalar, and under x64 ``q * f64`` upcasts
    the whole tensor to f64 before the cast back: found by the analysis
    dtype-f64 rule on serving_decode.)"""
    return jnp.asarray(1.0 / np.sqrt(q.shape[-1]) if scale is None else scale,
                       q.dtype)


def decode_attend(q, k_cache, v_cache, positions, window=None, scale=None):
    """Single-position cached attention: q ``[B, H_q, T, D]`` (T=1 in
    decode) against the full static cache ``[B, H_kv, S_max, D]``, masked to
    the valid prefix ``key_pos <= positions`` (scalar or per-row ``[B]``),
    with ``window`` to its last ``window`` keys (``key_pos > positions -
    window``).

    Matches _sdpa_ref numerics: q pre-scaled in its own dtype, f32 scores,
    f32 softmax, output cast back to v's dtype.
    """
    rep = q.shape[1] // k_cache.shape[1]
    k = _expand_kv_heads(k_cache, rep)
    v = _expand_kv_heads(v_cache, rep)
    qf = q * _scale(scale, q)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, k,
                   preferred_element_type=jnp.float32)
    pos = jnp.asarray(positions)
    key_pos = jnp.arange(k_cache.shape[2])
    if pos.ndim == 0:
        valid = key_pos[None, None, None, :] <= pos
    else:
        valid = key_pos[None, None, None, :] <= pos[:, None, None, None]
    if window is not None:
        valid = valid & (key_pos[None, None, None, :]
                         > jnp.reshape(pos, (-1, 1, 1, 1)) - window)
    s = jnp.where(valid, s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def extend_attend(q, k_cache, v_cache, positions, window=None, first=None,
                  scale=None):
    """Multi-query cached attention: q ``[B, H_q, T, D]`` where query ``t``
    of row ``b`` sits at absolute position ``positions[b] + t`` and may
    attend to ``key_pos <= positions[b] + t``, with ``window`` to those
    with ``key_pos > positions[b] + t - window`` alone — the suffix-prefill
    / speculative-verify generalization of ``decode_attend`` (T=1 reduces
    to it exactly), and the oracle of ``extend_flash`` /
    ``window_extend_flash``. Row ``s`` of the caches is key position ``s``,
    or ``first[b] + s`` where ``first [B]`` is given (a view of a slot's
    window). Same _sdpa_ref numerics: q pre-scaled in its own dtype, f32
    scores, -1e30 mask, f32 softmax."""
    rep = q.shape[1] // k_cache.shape[1]
    k = _expand_kv_heads(k_cache, rep)
    v = _expand_kv_heads(v_cache, rep)
    qf = q * _scale(scale, q)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, k,
                   preferred_element_type=jnp.float32)
    T = q.shape[2]
    qpos = jnp.asarray(positions)[:, None] + jnp.arange(T)[None, :]  # [B, T]
    key_pos = jnp.arange(k_cache.shape[2])[None, None, None, :]
    if first is not None:
        key_pos = key_pos + jnp.asarray(first)[:, None, None, None]
    valid = key_pos <= qpos[:, None, :, None]
    if window is not None:
        valid = valid & (key_pos > qpos[:, None, :, None] - window)
    s = jnp.where(valid, s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def paged_decode_attend(q, k_pool, v_pool, page_table, positions,
                        window=None, scale=None):
    """Single-position cached attention over block-paged pools — the paged
    twin of ``decode_attend``, in the tier ``tier.default_paged_impl`` says
    (``window``: a sliding layer's, both tiers the same lower bound).
    ``oracle`` reconstructs the dense caches (``pools.paged_gather``) and
    runs the einsum reference above; ``pallas`` runs the ragged kernel
    (``paged_attention``) which touches only live pages. Both tiers
    read the identical pool bytes, so they agree within float tolerance on
    ragged batches and GQA; an empty slot's row, which no caller reads, is
    the trash page's first token here and zeros there
    (tests/test_paged_kv.py)."""
    if default_paged_impl() == "oracle":
        k = paged_gather(k_pool, page_table)
        v = paged_gather(v_pool, page_table)
        return decode_attend(q, k, v, positions, window, scale)
    return paged_attention(q, k_pool, v_pool, page_table, positions, window,
                           scale)


def paged_extend_attend(q, k_pool, v_pool, page_table, positions,
                        window=None, first=None, scale=None):
    """Multi-query cached attention over block-paged pools — the paged twin
    of ``extend_attend`` (an admission's new tokens behind a prefix hit, a
    speculative verify), in the tier ``tier.default_paged_impl`` says.
    ``q [B, H_q, T, D]`` not yet scaled; ``window`` a sliding layer's, with
    ``page_table`` and ``first`` then ``pools.window_blocks``'s: the table
    entries of the window and the new tokens, and the position of the
    view's first row. Both tiers reconstruct the dense view
    (``pools.paged_gather``: the gather is a small part of an extend);
    ``oracle`` runs the einsum reference over it, whose float32 scores
    stand in memory whole; ``pallas`` runs ``extend_flash``, whose scores
    never leave VMEM and which neither fetches nor computes the key blocks
    no query sees. For it the table is first widened with sentinels (the
    trash page, behind every query) to whole key blocks."""
    if default_paged_impl() == "oracle":
        k = paged_gather(k_pool, page_table)
        v = paged_gather(v_pool, page_table)
        return extend_attend(q, k, v, positions, window, first, scale)
    ps = k_pool.shape[2]
    L = page_table.shape[1] * ps
    unit = _EXTEND_KEYS if L > _EXTEND_KEYS else LANES
    more = (-L % unit) // ps if unit % ps == 0 else 0
    if more:
        page_table = jnp.pad(page_table, ((0, 0), (0, more)),
                             constant_values=PAGE_SENTINEL)
    qs = q * _scale(scale, q)
    return extend_flash(qs, paged_gather(k_pool, page_table),
                        paged_gather(v_pool, page_table), positions, window,
                        first)


# ---------------------------------------------------------------------------
# Differential attention (arXiv:2410.05258) over PAIR-head pools: K pair r is
# ``[k_2r | k_2r+1]`` and V pair r ``[v_2r | v_2r+1]``, 2 D lanes a pair, so a
# pool of H_kv / 2 pair-heads is read by the kernels above as they are
# ---------------------------------------------------------------------------


def diff_widen(q):
    """Queries ``[B, H_q, T, D]`` as rows over a K PAIR's ``2 D`` lanes: head
    ``2 p`` keeps its lanes and is zero over the pair's second key, head ``2
    p + 1`` the other way round, so ``q_wide . [k_1 | k_2]`` is the head's
    own ``q . k`` and ``softmax(.) [v_1 | v_2]`` its attention over the
    pair's whole value: ``[B, H_q, T, 2 D]``. The softmax scale stays
    ``D^-1/2`` (the entries' ``scale``)."""
    B, Hq, T, D = q.shape
    half = jnp.eye(2, dtype=q.dtype)[None, None, :, None, :, None]
    return (q.reshape(B, Hq // 2, 2, T, 1, D) * half).reshape(B, Hq, T, 2 * D)


def diff_combine(o, lam, gamma, lam_init: float, eps: float):
    """What a pair of query heads gives: ``o [B, H_q, T, 2 D]`` (head ``2 p``
    then ``2 p + 1`` of pair ``p``), ``lam`` the layer's float32 scalar:
    ``RMSNorm_2D(o_1 - lam o_2; gamma) (1 - lam_init)``, ``[B, H_q / 2, T,
    2 D]`` float32 (the caller casts)."""
    B, Hq, T, W = o.shape
    o = o.astype(jnp.float32).reshape(B, Hq // 2, 2, T, W)
    d = o[:, :, 0] - lam * o[:, :, 1]
    d = d * jax.lax.rsqrt(jnp.mean(d * d, axis=-1, keepdims=True) + eps)
    return d * gamma.astype(jnp.float32) * jnp.float32(1.0 - lam_init)


def diff_decode_attend(q, k_pool, v_pool, page_table, positions, window=None):
    """One query a slot of a differential layer over its PAIR-head pools
    (``[P, H_kv / 2, page, 2 D]``): ``q [B, H_q, 1, D]`` not yet scaled, ``[B,
    H_q, 1, 2 D]`` out, each head's attention over its pair's whole value
    (``diff_combine`` takes the difference). Kernel (``paged_decode`` /
    ``window_decode``) or oracle as ``tier.default_paged_impl`` says."""
    return paged_decode_attend(diff_widen(q), k_pool, v_pool, page_table,
                               positions, window,
                               scale=float(q.shape[-1]) ** -0.5)


def diff_extend_attend(q, k_pool, v_pool, page_table, positions, window=None,
                       first=None):
    """``T`` queries a slot of a differential layer behind its cached
    context, over the pair-head pools (``paged_extend_attend``'s operands):
    ``[B, H_q, T, 2 D]`` out. Kernel (``extend_flash`` /
    ``window_extend_flash``) or oracle likewise."""
    return paged_extend_attend(diff_widen(q), k_pool, v_pool, page_table,
                               positions, window, first,
                               scale=float(q.shape[-1]) ** -0.5)
