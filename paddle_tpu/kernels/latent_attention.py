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

The walk is ``paged_attention.py``'s, a tile of slots at a time: the pool
``[P, 1, page_size, W]`` stays in HBM, the page table, the positions and a
PLAN ride as scalar-prefetch operands, and pages come in by the kernel's own
``make_async_copy`` into one of two VMEM buffers, chunk ``c + 1`` in flight
while chunk ``c`` is computed. A chunk is ``_CHUNK_TOKENS`` tokens (not one
lane width as there: with one row a token the per-chunk overhead would lead,
and a 512 x 640 bfloat16 buffer is 0.6 MiB).

Slots whose tables begin with the same physical pages (sessions on one
document that the prefix cache holds once) need not each fetch those pages
and multiply them by 128 rows of their own. ``shared_walk_plan`` reads from
the table and the positions alone which slots those are: slots with the same
first page are a group, a group is cut into TILES of at most
``_TILE_MEMBERS`` slots, and a tile's ``shared`` pages are the leading table
entries that are equal for all its members and lie wholly at or below every
member's position, in whole chunks. The grid has one step a PLACE of the
plan's order (a tile's members stand side by side in it); the step at a
tile's first place does the tile's whole walk, in two phases on ONE running
float32 (max, sum, acc) a row, kept in VMEM scratch:

1. the shared pages, each fetched once: a chunk's rows are scored by
   ``[_BLOCK_MEMBERS x H, W] x [W, chunk]`` matmuls, a block of members'
   heads the rows of one, with no mask (every member sees every token) and
   no branch around a copy (every page is live);
2. each member's own pages behind them, on that member's ``H`` rows: a chunk
   whose pages are all live is fetched as in phase one, the member's last
   one page by page (pages past its live count are not fetched and their
   rows zeroed), every chunk masked at the member's position.

So there is no partial softmax to merge and nothing of the attention outside
the kernel; a row's chunks are the ones a walk of its own would have made.
Every step then hands its own slot's rows to its output block. With nothing
shared every tile has one member and phase one makes no trip; a dead slot is
in no tile and yields zeros. The plan is a few dozen small XLA ops: a model
makes it once a decode step for all its layers (``latent_decode_plan``) and
hands it in. ``latent_decode_attend`` is the entry a layer calls: this
kernel, or its jnp reference (written out there) where ``tier`` says so.

Numerics mirror that reference: q pre-scaled in its own dtype, float32
scores (exp2 domain), float32 online softmax, probabilities cast to the
pool's dtype for the second matmul.

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
that is). A step walks up to 1,024 keys (``_blocks``), its queries
``_FLASH_ROWS`` (128) at a time against ALL of them: one running maximum,
one sum and one rescale of the accumulator a 1,024 keys, half the grid
steps. The chunks of queries share nothing, and the body writes chunk
r + 1's two score products down BEFORE chunk r's softmax: the step is one
basic block, and the scheduler then runs the matrix unit's phase of one
chunk under the vector unit's phase of the other (a whole tile's products,
then a whole tile's softmax, leave each unit idle through the other's
phase). Every block is masked: a second body without the mask for the
blocks wholly behind a query block's first position (all but one or two of
35 behind 33k cached tokens) was measured and gave nothing, the mask's
passes ride in slots the schedule leaves empty; so were one 192-wide score
product through a VMEM copy of ``[k_nope | k_pe]`` (slower: on a 128-deep
matrix unit it is two passes either way) and ``log2(e)`` folded into the
queries (PERF.md section 6, PR 44).

Without the kernel the expanded form is XLA einsums whose float32 score
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
from .flash_attention import (LOG2E, NEG_INF, last_key_block,
                              online_softmax_init, online_softmax_step)
from .mesh import shard_kernel
from .pools import paged_gather
from .tier import default_paged_impl

#: tokens fetched and computed together
_CHUNK_TOKENS = 512
#: slots a tile holds at most: the members whose shared pages are walked once
_TILE_MEMBERS = 16
#: members a matmul of the shared walk scores together (their heads its rows)
_BLOCK_MEMBERS = 4

#: the rows of a plan (``shared_walk_plan``)
_ORDER, _TILE, _COUNT, _SHARED = range(4)


def shared_walk_plan(page_table, positions, page_size: int):
    """Which slots walk which leading pages TOGETHER, from the page table
    and the positions alone: ``[4, B]`` int32, indexed by a slot's place
    ``r`` in the ORDER the kernel's grid runs.

    Slots whose tables begin with the same physical page form a group (the
    prefix cache maps a shared prefix to the same page ids from entry 0);
    the group's slots stand side by side in the order, those that share
    most first, and every ``_TILE_MEMBERS`` of them are a TILE. Rows:
    ``order`` (the slot at place r; live slots first), ``tile`` (the place
    of r's tile's first member), ``count`` (the tile's members; 0 for a
    dead slot) and ``shared`` (pages, in whole chunks: the leading table
    entries that are the same for all the tile's members and lie wholly at
    or below every member's position, so the shared walk needs no mask; 0
    for a tile of one)."""
    table = page_table.astype(jnp.int32)
    B, nb = table.shape
    chunk = max(1, _CHUNK_TOKENS // page_size)
    idx = jnp.arange(B, dtype=jnp.int32)
    alive = table[:, 0] >= 0
    same = (table[:, :1] == table[None, :, 0]) & alive[:, None] & alive[None]
    # a group's leader is its lowest slot; a slot may share with the others
    # what it shares with the leader, whole pages at or below its position
    leader = jnp.where(alive, jnp.argmax(same, axis=1).astype(jnp.int32), idx)
    entry = jnp.arange(nb, dtype=jnp.int32)[None]
    agree = jnp.min(jnp.where(table != table[leader], entry, nb), axis=1)
    lim = jnp.minimum(agree,
                      (jnp.asarray(positions, jnp.int32) + 1) // page_size)
    lim = jnp.where(alive, lim // chunk * chunk, 0)
    # places: by group (dead slots behind all), then most shared first, then
    # by slot; counted, not sorted (B x B compares)
    group = jnp.where(alive, leader, B)
    before = (group[None] < group[:, None]) | (
        (group[None] == group[:, None]) & (
            (lim[None] > lim[:, None]) | (
                (lim[None] == lim[:, None]) & (idx[None] < idx[:, None]))))
    place = jnp.sum(before, axis=1, dtype=jnp.int32)
    order = jnp.argmax(place[None] == idx[:, None], axis=1).astype(jnp.int32)
    group, lim, alive = group[order], lim[order], alive[order]
    first = jnp.argmax(group[None] == group[:, None], axis=1).astype(jnp.int32)
    tile = jnp.where(alive, idx - (idx - first) % _TILE_MEMBERS, idx)
    mates = (tile[None] == tile[:, None]) & alive[None] & alive[:, None]
    count = jnp.sum(mates, axis=1, dtype=jnp.int32)
    shared = jnp.min(jnp.where(mates, lim[None], nb), axis=1)
    shared = jnp.where(count > 1, shared, 0)
    return jnp.stack([order, tile, count, shared])


def shared_walk_tokens(plan, page_size: int):
    """Context tokens a plan's tiles of two members or more score in their
    shared walk, summed over the members (one layer)."""
    return jnp.sum(plan[_SHARED]) * page_size


def _kernel(tbl_ref, pos_ref, plan_ref, q_hbm, pool_hbm, o_ref, qbuf, buf,
            m_scr, l_scr, acc_scr, out_scr, sems, qsem, *, num_blocks: int,
            page_size: int, chunk: int, value_width: int, block: int):
    """Grid (B,): one step a PLACE of the plan's order, run one after the
    other. The step at a tile's first place does the tile's whole walk into
    ``out_scr [_TILE_MEMBERS, H, value_width]``; every step hands its own
    slot's rows to the output block (``order[r]``). ``qbuf [_TILE_MEMBERS *
    H, W]`` holds the members' queries, ``buf [2, chunk, page_size, W]`` the
    pages in flight, ``sems`` two DMA semaphores (one a buffer), ``qsem``
    the queries'; the running (max, sum, acc) of all the members' rows are
    ``m_scr``, ``l_scr``, ``acc_scr``, updated in place by both phases."""
    r = pl.program_id(0)
    tile, count = plan_ref[_TILE, r], plan_ref[_COUNT, r]
    H, W = q_hbm.shape[1:]
    chunk_tokens = chunk * page_size

    def page_copy(b, i, slot, k):
        # a sentinel inside the live range clamps to the trash page
        page = jnp.maximum(tbl_ref[b, i], 0)
        return pltpu.make_async_copy(pool_hbm.at[page, 0], buf.at[slot, k],
                                     sems.at[slot])

    def whole_chunk(b, c, slot, do: str):
        """Start (or wait for) the copy of every page of slot b's chunk c,
        all of them live: one basic block, so the copies' scalar work is
        scheduled together."""
        for k in range(chunk):
            getattr(page_copy(b, c * chunk + k, slot, k), do)()

    def q_copies(do: str):
        def one(j, done):
            getattr(pltpu.make_async_copy(
                q_hbm.at[plan_ref[_ORDER, tile + j]],
                qbuf.at[pl.ds(pl.multiple_of(j * H, H), H)], qsem), do)()
            return done

        jax.lax.fori_loop(0, count, one, 0)

    def scores(q, rows):
        return jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * jnp.float32(LOG2E)

    def fold(rs, s, rows):
        """One chunk's scores ``s`` (log2 domain) of the state's rows ``rs``
        into their running (max, sum, acc)."""
        m = m_scr[rs]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m - m_new)
        pv = jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :value_width],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[rs] = m_new
        l_scr[rs] = l_scr[rs] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[rs] = acc_scr[rs] * alpha + pv

    @pl.when((tile == r) & (count > 0))
    def _walk():
        q_copies("start")

        def reset(i, done):
            rs = pl.ds(pl.multiple_of(i * H, H), H)
            m_scr[rs] = jnp.full((H, 1), NEG_INF, jnp.float32)
            l_scr[rs] = jnp.zeros((H, 1), jnp.float32)
            acc_scr[rs] = jnp.zeros((H, value_width), jnp.float32)
            return done

        # whole blocks: a block's rows past the tile's members are computed
        # and never read
        jax.lax.fori_loop(0, (count + block - 1) // block * block, reset, 0)
        q_copies("wait")

        # ---- phase one, the shared pages: each fetched once (through the
        # first member's table), a block of members' heads the rows of one
        # matmul
        lead = plan_ref[_ORDER, tile]
        shared = plan_ref[_SHARED, r] // chunk              # chunks

        @pl.when(shared > 0)
        def _first_shared():
            whole_chunk(lead, 0, 0, "start")

        def shared_body(c, done):
            slot = c % 2

            @pl.when(c + 1 < shared)
            def _prefetch():
                whole_chunk(lead, c + 1, 1 - slot, "start")

            whole_chunk(lead, c, slot, "wait")

            def rows_block(i, done):
                rs = pl.ds(pl.multiple_of(i * block * H, block * H),
                           block * H)
                rows = buf[slot].reshape(chunk_tokens, W)
                fold(rs, scores(qbuf[rs], rows), rows)
                return done

            return jax.lax.fori_loop(0, (count + block - 1) // block,
                                     rows_block, done)

        jax.lax.fori_loop(0, shared, shared_body, 0)

        # ---- phase two, each member's own pages behind them, on its rows
        # of the same running state
        def member(j, done):
            b = plan_ref[_ORDER, tile + j]
            pos = pos_ref[b]
            live = jnp.minimum(pos // page_size + 1, num_blocks)
            rs = pl.ds(pl.multiple_of(j * H, H), H)
            q = qbuf[rs]                                   # [H, W], pre-scaled

            def fetch(c, slot):
                @pl.when((c + 1) * chunk <= live)
                def _whole():
                    whole_chunk(b, c, slot, "start")

                @pl.when((c + 1) * chunk > live)
                def _last():
                    for k in range(chunk):
                        i = c * chunk + k

                        @pl.when(i < live)
                        def _start():
                            page_copy(b, i, slot, k).start()

                        @pl.when(i >= live)
                        def _blank():
                            # never fetched: its weight is exactly 0, but
                            # 0 x whatever the buffer held need not be
                            buf[slot, k] = jnp.zeros(buf.shape[2:],
                                                     buf.dtype)

            def wait(c, slot):
                @pl.when((c + 1) * chunk <= live)
                def _whole():
                    whole_chunk(b, c, slot, "wait")

                @pl.when((c + 1) * chunk > live)
                def _last():
                    for k in range(chunk):
                        @pl.when(c * chunk + k < live)
                        def _wait():
                            page_copy(b, c * chunk + k, slot, k).wait()

            def body(c, done):
                slot = c % 2

                @pl.when((c + 1) * chunk < live)
                def _prefetch():
                    fetch(c + 1, 1 - slot)

                wait(c, slot)
                rows = buf[slot].reshape(chunk_tokens, W)
                s = scores(q, rows)
                tok = c * chunk_tokens + jax.lax.broadcasted_iota(
                    jnp.int32, (H, chunk_tokens), 1)
                fold(rs, jnp.where(tok <= pos, s, NEG_INF), rows)
                return done

            @pl.when(shared * chunk < live)
            def _first():
                fetch(shared, shared % 2)

            jax.lax.fori_loop(shared, (live + chunk - 1) // chunk, body, 0)
            l = l_scr[rs]
            out_scr[j] = (acc_scr[rs] / jnp.where(l == 0, 1.0, l)).astype(
                out_scr.dtype)
            return done

        jax.lax.fori_loop(0, count, member, 0)

    @pl.when(count > 0)
    def _live():
        o_ref[0] = out_scr[r - tile]

    @pl.when(count == 0)
    def _dead():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)


def latent_paged_decode(q, pool, page_table, positions, value_width: int,
                        plan=None):
    """One query token a slot against the slot's live latent rows.

    q            ``[B, H, W]``, pre-scaled, as wide as a row (zeros in the
                 idle lanes)
    pool         ``[P, 1, page_size, W]``: this layer's latent pool
    page_table   ``[B, num_blocks]`` int32 pool page ids (-1 = unallocated)
    positions    ``[B]`` int32: each slot's current token index
    plan         ``shared_walk_plan`` of the table and the positions, where
                 the caller has it already (a model computes it once a step
                 for all its layers)

    Returns ``[B, H, value_width]`` in the pool's dtype: per head the
    softmax-weighted sum of the rows' first ``value_width`` lanes."""
    table = page_table.astype(jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)
    if plan is None:
        plan = shared_walk_plan(table, pos, pool.shape[2])
    call = functools.partial(_decode_call, value_width=value_width,
                             interpret=pallas_interpret())
    # every head reads the same rows: nothing to divide over a mesh
    return shard_kernel(call, (table, pos, plan, q, pool), (P(),) * 5,
                        lambda fitted: P())


# jitted so that a model's layers share ONE trace and ONE Mosaic lowering in
# the program they are traced into (as ``paged_attention._decode_call``)
@functools.partial(jax.jit, static_argnames=("value_width", "interpret"))
def _decode_call(table, pos, plan, q, pool, *, value_width: int,
                 interpret: bool):
    B, H, W = q.shape
    _, _, page_size, _ = pool.shape
    chunk = max(1, _CHUNK_TOKENS // page_size)
    members = _TILE_MEMBERS
    block = min(_BLOCK_MEMBERS, members)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (1, H, value_width),
            lambda r, _tbl, _pos, plan: (plan[_ORDER, r], 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((members * H, W), q.dtype),
            pltpu.VMEM((2, chunk, page_size, W), pool.dtype),
            pltpu.VMEM((members * H, 1), jnp.float32),
            pltpu.VMEM((members * H, 1), jnp.float32),
            pltpu.VMEM((members * H, value_width), jnp.float32),
            pltpu.VMEM((members, H, value_width), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, num_blocks=table.shape[1],
                          page_size=page_size, chunk=chunk,
                          value_width=value_width, block=block),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, value_width), pool.dtype),
        compiler_params=pltpu.CompilerParams(
            # a tile's later places read what its first one left in scratch
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="latent_paged_decode",
    )(table, pos, plan, q, pool)


def latent_decode_plan(page_table, positions, page_size: int):
    """What a decode step's ``latent_decode_attend`` calls share, computed
    once for all the model's layers: in the ``pallas`` tier the kernel's
    shared-walk plan (``shared_walk_plan``: which slots map the same
    leading pages and score them together), read from the table and the
    positions alone; None in the ``oracle`` tier, which gathers every
    slot's own view."""
    if default_paged_impl() != "pallas":
        return None
    return shared_walk_plan(page_table, positions, page_size)


def latent_decode_attend(q, pool, page_table, positions, value_width: int,
                         plan=None):
    """Single-position attention over a pool of LATENT rows (one row a
    token, keys and values the same bytes: ``models/decoder``'s latent
    layer in its absorbed form), in the tier ``tier.default_paged_impl``
    says. ``q [B, H, W]`` is pre-scaled and as wide as the pool's rows ``[P,
    1, ps, W]``; a row's first ``value_width`` lanes are what is attended:
    ``[B, H, value_width]`` out, in the pool's dtype. ``oracle`` gathers the
    dense view and runs the einsums below, the kernel's reference (float32
    scores, -1e30 mask, float32 softmax); ``pallas`` is
    ``latent_paged_decode``, which fetches the leading pages that slots
    share ONCE for all of them and scores them in one matmul (``plan``:
    ``latent_decode_plan``'s, where the caller has it; the kernel's wrapper
    computes it otherwise), then each slot's own pages. An empty slot's row,
    which no caller reads, is the trash page's first token here and zeros
    there."""
    if default_paged_impl() == "pallas":
        return latent_paged_decode(q, pool, page_table, positions,
                                   value_width, plan)
    rows = paged_gather(pool, page_table)[:, 0]                # [B, L, W]
    s = jnp.einsum("bhw,blw->bhl", q, rows,
                   preferred_element_type=jnp.float32)
    valid = jnp.arange(rows.shape[1])[None, :] <= positions[:, None]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1).astype(rows.dtype)
    return jnp.einsum("bhl,blv->bhv", probs, rows[..., :value_width])


# ------------------------------------------------- the expanded form

#: queries of a grid step whose scores are one tile of the online softmax
_FLASH_ROWS = 128


def _blocks(T: int, L: int):
    """(queries, keys) a grid step: the largest of the listed sizes that
    divide the length, else the whole of it (small test shapes). A step
    folds ALL its keys into a query's running maximum, sum and accumulator
    at once, so those are paid once a 1,024 keys where the lengths allow
    (35,840 = 35 x 1,024)."""
    sizes = (1024, 512, 256, 128)
    pick = lambda n: next((b for b in sizes if n % b == 0), n)
    return pick(T), pick(L)


def _flash_kernel(start_ref, qn_ref, qp_ref, kn_ref, kp_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, heads: int, num_kb: int,
                  block_q: int, block_k: int):
    """Grid (G, T / block_q, L / block_k); ``start_ref [G / heads]`` the
    position of each sequence's first query."""
    qi, ki = pl.program_id(1), pl.program_id(2)
    start = start_ref[pl.program_id(0) // heads]
    kb_hi = last_key_block(start, qi, block_q, block_k, num_kb)

    pl.when(ki == 0)(lambda: online_softmax_init(m_scr, l_scr, acc_scr))

    # the step's queries go _FLASH_ROWS at a time against ALL its keys (a
    # block they do not divide goes whole), in ``online_softmax_step``'s order
    rows = _FLASH_ROWS if block_q % _FLASH_ROWS == 0 else block_q

    def scores(r):
        qr = pl.ds(r * rows, rows)
        nt = lambda a, b: jax.lax.dot_general(       # a b^T, float32
            a, b, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = (nt(qn_ref[0, qr], kn_ref[0]) + nt(qp_ref[0, qr], kp_ref[0])) \
            * jnp.float32(LOG2E)                     # q pre-scaled
        qpos = start + qi * block_q + r * rows \
            + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        return jnp.where(qpos >= kpos, s, NEG_INF)

    @pl.when(ki < kb_hi)
    def _compute():
        # key 0 is behind every query and block 0 is walked first, so no
        # row's running maximum stays at the mask's value
        online_softmax_step(scores, lambda: v_ref[0], m_scr, l_scr, acc_scr,
                            rows=rows, chunks=block_q // rows)

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
        last = last_key_block(start_ref[g // heads], qi, bq, bk, num_kb) - 1
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
