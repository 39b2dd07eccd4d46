"""Static-shape KV cache: the serving engine's HBM-resident decode state.

The engine's cache is ``PagedKVCache``: per layer and pool one
``[num_pages, heads, page_size, width]`` buffer preallocated at engine
construction, plus a host page table, so every prefill and every decode
step runs at a FIXED shape: XLA compiles the prefill once per prompt bucket
and the decode step exactly once, no matter how many tokens or requests
flow through. ``paged_write_kv`` writes it a page at a time; the attend
over it is the Pallas kernel or the oracle, and ``default_paged_impl`` is
the one function that says which.

The dense helpers (``write_kv`` / ``decode_attend`` / ``extend_attend``
over ``[B, H_kv, S_max, D]`` buffers) are the oracle of the paged attends
(over ``paged_gather``) and the SHARED lockstep decode path: both
``GPTForCausalLM.generate`` (serving/engine.py ``cached_generate``) and
``incubate.nn.FusedMultiTransformer``'s ``time_step`` decode route through
them, so the two cached-attention implementations cannot drift.

Numerics deliberately mirror ``nn.functional._sdpa_ref`` (pre-scaled q,
f32 logits, -1e30 masking, f32 softmax) so cached decode logits match the
full-prefix causal forward within float tolerance — asserted by
tests/test_serving.py.
"""

from __future__ import annotations

import contextlib
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.place import on_tpu

_NEG_INF = -1e30  # a plain float: a jnp constant here would initialize the backend at import

#: page-table entry marking an unallocated block. Device code never branches
#: on it — lookups clamp sentinels to page 0, the reserved TRASH page the
#: allocator never hands out, so gathers/scatters stay in-bounds and the
#: decode mask (``key_pos <= position``) keeps trash bytes out of the math.
PAGE_SENTINEL = -1


def write_kv(cache, new, positions):
    """Write new K (or V) entries into a ``[B, H_kv, S_max, D]`` cache.

    ``positions`` scalar: contiguous write of ``new [B, H_kv, T, D]``
    starting at that sequence index (the prefill / shared-step case —
    ``lax.dynamic_update_slice``, batch must match the cache's).
    ``positions`` ``[B]``: per-row single-token scatter of
    ``new [B, H_kv, 1, D]`` at each row's own index (the continuous-batching
    decode case, where slots sit at different sequence positions).
    """
    new = new.astype(cache.dtype)
    positions = jnp.asarray(positions)
    if positions.ndim == 0:
        zero = jnp.zeros((), positions.dtype)
        return lax.dynamic_update_slice(cache, new, (zero, zero, positions, zero))
    # one row per (slot, head), indexed on the two LEADING dimensions of the
    # [B*H_kv, S_max, D] view: the form XLA scatters into a donated cache
    # where it lies. Indexed on (slot, position) of the 4-D cache, around
    # the head dimension, XLA transposes the whole cache to put the indexed
    # dimensions first, and back, every step.
    B, Hkv, S, D = cache.shape
    flat = cache.reshape(B * Hkv, S, D)
    flat = flat.at[jnp.arange(B * Hkv), jnp.repeat(positions, Hkv), :].set(
        new[:, :, 0, :].reshape(B * Hkv, D))
    return flat.reshape(B, Hkv, S, D)


def _expand_kv_heads(t, rep: int):
    """GQA: broadcast [B, H_kv, S, D] -> [B, H_kv*rep, S, D]. A broadcast
    (insert group dim + reshape), not repeat: XLA keeps it fused into the
    attention einsums instead of materializing full-width K/V."""
    if rep == 1:
        return t
    B, Hkv, S, D = t.shape
    return jnp.broadcast_to(t[:, :, None], (B, Hkv, rep, S, D)).reshape(
        B, Hkv * rep, S, D)


def decode_attend(q, k_cache, v_cache, positions, window=None):
    """Single-position cached attention: q ``[B, H_q, T, D]`` (T=1 in
    decode) against the full static cache ``[B, H_kv, S_max, D]``, masked to
    the valid prefix ``key_pos <= positions`` (scalar or per-row ``[B]``),
    with ``window`` to its last ``window`` keys (``key_pos > positions -
    window``).

    Matches _sdpa_ref numerics: q pre-scaled in its own dtype, f32 scores,
    f32 softmax, output cast back to v's dtype.
    """
    D = q.shape[-1]
    rep = q.shape[1] // k_cache.shape[1]
    k = _expand_kv_heads(k_cache, rep)
    v = _expand_kv_heads(v_cache, rep)
    # scale as a q-dtype scalar: np.sqrt returns a STRONG f64 scalar, and
    # under x64 `q * f64` upcasts the whole tensor to f64 before the cast
    # back (found by the analysis dtype-f64 rule on serving_decode)
    qf = q * jnp.asarray(1.0 / np.sqrt(D), q.dtype)
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
    s = jnp.where(valid, s, _NEG_INF)
    probs = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _layer_buffers(num_layers: int, shape, dtype) -> Tuple[jax.Array, ...]:
    """One zeroed device buffer per layer. Separate buffers, not one
    stacked array: a compiled program that takes the tuple donated and
    returns each layer's updated buffer writes every layer where it lies
    (XLA aliases a donated parameter to the output it is scattered into),
    which a slice of a stacked array can never be."""
    return tuple(jnp.zeros(shape, dtype) for _ in range(num_layers))


def _tuple_nbytes(*pools) -> int:
    return int(sum(a.size * a.dtype.itemsize for p in pools for a in p))


# ---------------------------------------------------------------------------
# Block-paged cache (vLLM PagedAttention layout, static-shape edition)
# ---------------------------------------------------------------------------

_PAGED_IMPL = None  # the tier a test pinned (use_paged_attention_impl)
_PAGED_IMPLS = ("oracle", "pallas")


def default_paged_impl() -> str:
    """Which paged-attend implementation a trace bakes in — the ONE place
    that says: ``pallas`` (the ragged kernels — compiled Mosaic on TPU, the
    Pallas interpreter on cpu) on TPU, the ``oracle`` (gather + dense
    ``decode_attend`` einsum) elsewhere, unless a test pinned the tier with
    ``use_paged_attention_impl``."""
    if _PAGED_IMPL is not None:
        return _PAGED_IMPL
    return "pallas" if on_tpu() else "oracle"


@contextlib.contextmanager
def use_paged_attention_impl(impl: str):
    """Pin the paged-attend implementation for traces entered under the
    context: the seam by which a CPU test runs the kernels under the
    interpreter and ``chip_smoke.py`` runs the oracle on the chip. The
    choice is baked in at TRACE time, so wrap the engine's construction
    and its first ``generate`` / ``compile_programs`` (programs already
    compiled are unaffected)."""
    global _PAGED_IMPL
    if impl not in _PAGED_IMPLS:
        raise ValueError(f"paged impl {impl!r}; want one of {_PAGED_IMPLS}")
    prev, _PAGED_IMPL = _PAGED_IMPL, impl
    try:
        yield
    finally:
        _PAGED_IMPL = prev


def paged_write_kv(pool, new, page_table, positions):
    """Write ``T`` tokens' K (or V) per slot into a ``[P, H_kv, ps, D]``
    page pool: token ``t`` of row ``b`` of ``new [B, H_kv, T, D]`` lands in
    page ``page_table[b, (positions[b]+t) // ps]`` at offset
    ``(positions[b]+t) % ps``. ``T`` is static (1 for plain decode, ``k+1``
    for speculative verify, a bucket for suffix prefill).

    The update is made a PAGE at a time: gather the pages the ``T``
    positions of each row can touch, lay the new rows into them, scatter
    whole pages back — one gather and one scatter whatever ``T`` is, both
    indexed on the pool's leading dimension only. That is the form XLA
    applies in place to a donated pool in the layout the pool is stored in
    (a scatter indexed on page AND offset makes the TPU compiler transpose
    the whole pool to a layout of its own and back, every step).

    Sentinel entries clamp to the trash page (slots without a live request
    all write identical token-0 state there, so the race is benign), and
    writes past the table's capacity ``num_blocks * ps`` route to the trash
    page too — a verify step near the end of a sequence can draft past
    ``S_max`` without going out of bounds; the host caps how many of those
    tokens it accepts. A touched page in which no token lands is written
    back as it was read."""
    ps = pool.shape[2]
    nb = page_table.shape[1]
    pos = jnp.asarray(positions)
    T = new.shape[2]
    new = new.astype(pool.dtype)
    nblk = (T + ps - 2) // ps + 1  # pages T consecutive positions can span
    block = (pos // ps)[:, None] + jnp.arange(nblk)            # [B, nblk]
    pages = jnp.take_along_axis(page_table, jnp.minimum(block, nb - 1),
                                axis=1)
    pages = jnp.where(block < nb, jnp.maximum(pages, 0), 0)
    # which token, if any, lands in offset s of touched block j of row b
    t = block[:, :, None] * ps + jnp.arange(ps) - pos[:, None, None]
    rows = jnp.take_along_axis(                       # [B, nblk, H_kv, ps, D]
        new[:, None], jnp.clip(t, 0, T - 1)[:, :, None, :, None], axis=3)
    lands = ((t >= 0) & (t < T))[:, :, None, :, None]
    merged = jnp.where(lands, rows, pool[pages])
    return pool.at[pages].set(merged)


def write_state_rows(buf, new, rows):
    """``new [n, ...]`` onto rows ``rows [n]`` (run-time values) of the state
    buffer ``buf [rows, ...]``, one after the other where it lies: where two
    name the same row, the later one stands."""
    for i in range(new.shape[0]):
        buf = lax.dynamic_update_slice_in_dim(
            buf, new[i:i + 1].astype(buf.dtype), rows[i], axis=0)
    return buf


def paged_gather(pool, page_table):
    """Materialize the dense ``[B, H_kv, num_blocks*ps, D]`` view of a page
    pool under a table — the oracle path's cache reconstruction (sentinels
    clamp to trash, so dense position ``j`` of an unallocated block holds
    trash bytes that the decode mask never admits)."""
    g = pool[jnp.maximum(page_table, 0)]        # [B, nb, Hkv, ps, D]
    B, nb, Hkv, ps, D = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, nb * ps, D)


def window_blocks(page_table, start, page_size: int, window: int, T: int):
    """What an extend of ``T`` tokens at ``start [B]`` reads of a sliding
    layer's pools: ``(first [B], sub [B, n])``, the sequence position of
    the view's first token and the table entries of the blocks from the one
    that holds ``start - window + 1`` to the one that holds ``start + T -
    1`` (``n`` is static: blocks past the table's end read as sentinels).
    ``paged_gather(pool, sub)`` is then the window and the new tokens, not
    the whole table's view."""
    nb = page_table.shape[1]
    back = (window + page_size - 2) // page_size
    n = back + (T + page_size - 2) // page_size + 1
    fb = jnp.maximum(start // page_size - back, 0)
    blocks = fb[:, None] + jnp.arange(n, dtype=fb.dtype)[None, :]
    sub = jnp.take_along_axis(page_table, jnp.minimum(blocks, nb - 1), axis=1)
    return fb * page_size, jnp.where(blocks < nb, sub, PAGE_SENTINEL)


def paged_decode_attend(q, k_pool, v_pool, page_table, positions,
                        window=None):
    """Single-position cached attention over block-paged pools — the paged
    twin of ``decode_attend``, in the tier ``default_paged_impl`` says
    (``window``: a sliding layer's, both tiers the same lower bound).
    ``oracle`` reconstructs the dense caches (``paged_gather``) and runs
    the einsum oracle; ``pallas`` runs the Pallas ragged kernel
    (kernels/paged_attention.py) which touches only live pages. Both tiers
    read the identical pool bytes, so they agree within float tolerance on
    ragged batches and GQA; an empty slot's row, which no caller reads, is
    the trash page's first token here and zeros there
    (tests/test_paged_kv.py)."""
    if default_paged_impl() == "oracle":
        k = paged_gather(k_pool, page_table)
        v = paged_gather(v_pool, page_table)
        return decode_attend(q, k, v, positions) if window is None \
            else decode_attend(q, k, v, positions, window)
    from ..kernels.paged_attention import paged_attention

    if window is not None:
        return paged_attention(q, k_pool, v_pool, page_table, positions,
                               window)
    return paged_attention(q, k_pool, v_pool, page_table, positions)


def latent_decode_plan(page_table, positions, page_size: int):
    """What a decode step's ``latent_decode_attend`` calls share, computed
    once for all the model's layers: in the ``pallas`` tier the kernel's
    shared-walk plan (``kernels/latent_attention.shared_walk_plan``: which
    slots map the same leading pages and score them together), read from
    the table and the positions alone; None in the ``oracle`` tier, which
    gathers every slot's own view."""
    if default_paged_impl() != "pallas":
        return None
    from ..kernels.latent_attention import shared_walk_plan

    return shared_walk_plan(page_table, positions, page_size)


def latent_decode_attend(q, pool, page_table, positions, value_width: int,
                         plan=None):
    """Single-position attention over a pool of LATENT rows (one row a
    token, keys and values the same bytes: ``models/decoder``'s latent
    layer in its absorbed form), in the tier ``default_paged_impl`` says.
    ``q [B, H, W]`` is pre-scaled and as wide as the pool's rows ``[P, 1,
    ps, W]``; a row's first ``value_width`` lanes are what is attended:
    ``[B, H, value_width]`` out, in the pool's dtype. ``oracle`` gathers the
    dense view and runs the einsums (float32 scores, -1e30 mask, float32
    softmax); ``pallas`` is ``kernels/latent_attention.latent_paged_decode``,
    which fetches the leading pages that slots share ONCE for all of them
    and scores them in one matmul (``plan``: ``latent_decode_plan``'s, where
    the caller has it; the kernel's wrapper computes it otherwise), then each
    slot's own pages. An empty slot's row, which no caller reads, is the
    trash page's first token here and zeros there."""
    if default_paged_impl() == "pallas":
        from ..kernels.latent_attention import latent_paged_decode

        return latent_paged_decode(q, pool, page_table, positions,
                                   value_width, plan)
    rows = paged_gather(pool, page_table)[:, 0]                # [B, L, W]
    s = jnp.einsum("bhw,blw->bhl", q, rows,
                   preferred_element_type=jnp.float32)
    valid = jnp.arange(rows.shape[1])[None, :] <= positions[:, None]
    s = jnp.where(valid[:, None, :], s, _NEG_INF)
    probs = jax.nn.softmax(s, axis=-1).astype(rows.dtype)
    return jnp.einsum("bhl,blv->bhv", probs, rows[..., :value_width])


def extend_attend(q, k_cache, v_cache, positions):
    """Multi-query cached attention: q ``[B, H_q, T, D]`` where query ``t``
    of row ``b`` sits at absolute position ``positions[b] + t`` and may
    attend to ``key_pos <= positions[b] + t`` — the suffix-prefill /
    speculative-verify generalization of ``decode_attend`` (T=1 reduces to
    it exactly). Same _sdpa_ref numerics: q pre-scaled in its own dtype,
    f32 scores, -1e30 mask, f32 softmax."""
    D = q.shape[-1]
    rep = q.shape[1] // k_cache.shape[1]
    k = _expand_kv_heads(k_cache, rep)
    v = _expand_kv_heads(v_cache, rep)
    qf = q * jnp.asarray(1.0 / np.sqrt(D), q.dtype)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, k,
                   preferred_element_type=jnp.float32)
    T = q.shape[2]
    qpos = jnp.asarray(positions)[:, None] + jnp.arange(T)[None, :]  # [B, T]
    key_pos = jnp.arange(k_cache.shape[2])
    valid = key_pos[None, None, None, :] <= qpos[:, None, :, None]
    s = jnp.where(valid, s, _NEG_INF)
    probs = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def paged_extend_attend(q, k_pool, v_pool, page_table, positions):
    """Multi-query cached attention over block-paged pools — the paged twin
    of ``extend_attend``. The Pallas ragged kernel is single-query, so
    every tier reconstructs the dense view (``paged_gather``) and runs the
    einsum path. Verify steps are rare next to decode steps (one per k+1
    emitted tokens), so the gather cost is amortized."""
    k = paged_gather(k_pool, page_table)
    v = paged_gather(v_pool, page_table)
    return extend_attend(q, k, v, positions)


class PagedKVCache:
    """Block-paged pools, one ``[num_pages, heads, page_size, width]`` device
    buffer per layer and pool, plus the per-slot page table and the slot
    bookkeeping of the continuous-batching scheduler.

    Which pools there are is the MODEL's declaration (``pools``: ``[(name,
    heads, width)]``); the default is the pair every attention needs, ``k``
    and ``v`` of ``num_kv_heads x head_dim``. A model whose attention keeps
    more per token (an indexer's keys) or lays a token's heads side by side
    (``heads = 1``, ``width = H_kv * D``) declares that, and everything a
    page lives through — the page-at-a-time write, the table row spliced on
    a prefix hit, copy-on-write, freeing, eviction — covers every pool,
    because it is all done by page id. ``.pools`` is the tuple (by pool) of
    tuples (by layer) of buffers; ``.k`` / ``.v`` name the first two.

    The pools are donated device buffers: the engine rebinds ``.pools`` to
    the tuples each compiled step returns, and every layer's pool is
    updated where it lies. Slot allocation is host-side: a freed slot is
    immediately reusable because its next prefill maps fresh pages before
    any decode reads them. The page table
    is HOST state (numpy) that only this class's methods write
    (``assign_pages``, ``repoint``, ``clear_slot``; ``page_table`` is a
    read-only view): each write marks the device's copy changed, and
    ``table_device()`` puts the table again only then — between two writes
    every executable is handed the SAME kept device array as runtime data.
    Table CONTENTS change on an admission, a finish, a page crossing or a
    copy-on-write, but its ``[B_max, num_blocks]`` int32 shape never does,
    which is what keeps decode at one compile.

    Page 0 is reserved as the trash page (see ``PAGE_SENTINEL``); a
    default-sized pool therefore holds ``B_max * S_max/page_size + 1``
    pages — capacity for every slot at full length. Serving the same
    envelope at a FRACTION of that HBM is the point: pass a smaller
    ``num_pages`` and admission backpressure + ragged allocation take over.

    A pool may belong to SOME layers only (a fourth entry of its
    declaration names them; a model whose layers are of several kinds), and
    a layer may keep, instead of pages of keys, STATE that is not a
    function of position: ``state_pools`` declares ``(name, per-slot shape,
    dtype, layers)``, one ``[B_max + num_snapshots, *shape]`` buffer a
    layer. Rows ``[0, B_max)`` are the slots' (the decode program advances
    them all, prefill and extend write one), the rows behind them hold
    SNAPSHOTS: a slot's state as it stood at a block boundary of its
    prompt, which the prefix trie keeps beside that block's page
    (``prefix_cache.py``). No row is ever copied: the extend program of an
    admission reads its start state from the snapshot's row and writes the
    snapshots it takes to their rows itself, beside the slot's
    (``Engine._state_arg``). ``.pools`` holds the state buffers behind
    the paged ones, each pool a tuple over ITS layers; ``layer_entries`` /
    ``pools_from_layers`` go between that and what one layer is handed.

    Pools stand in GROUPS: a group is the set of pools that share a page
    count, an allocator and a page table. A pool's declaration names its
    group as a fifth entry, ``(group name, window)``; without one it
    stands in the group "global" (``window`` None), which is first in
    ``.groups`` where it exists, and a model that declares no group builds
    exactly what it always did. A group with a ``window`` belongs to layers
    that attend the last ``window`` tokens alone: the engine unmaps and
    frees such a group's pages behind a slot's window as the slot moves on
    (``Engine._slide``), so its pool holds a window a slot, not a context.
    ``num_pages`` sizes the first group, ``group_pages`` ``{name: pages}``
    the others (default: the full budget); every table writer and reader
    below takes ``group`` (an index into ``.groups``; default the first),
    and ``layer_entries`` hands each layer its own group's table.
    """

    def __init__(self, num_layers: int, max_batch_size: int,
                 num_kv_heads: int, max_seq_len: int, head_dim: int,
                 dtype="float32", page_size: int = 16,
                 num_pages: Optional[int] = None, pools=None,
                 state_pools=(), num_snapshots: int = 0, group_pages=None):
        if max_seq_len % page_size:
            raise ValueError(
                f"max_seq_len {max_seq_len} not divisible by page_size "
                f"{page_size}")
        self.num_layers = num_layers
        self.max_batch_size = max_batch_size
        self.num_kv_heads = num_kv_heads
        self.max_seq_len = max_seq_len
        self.head_dim = head_dim
        self.page_size = page_size
        self.num_blocks = max_seq_len // page_size
        full = max_batch_size * self.num_blocks + 1
        if num_pages is None:
            num_pages = full
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (trash page + 1)")
        if pools is None:
            pools = [("k", num_kv_heads, head_dim), ("v", num_kv_heads, head_dim)]
        # the groups, "global" first: [(name, window, pages)]
        declared = [tuple(p[4]) if len(p) > 4 else ("global", None)
                    for p in pools]
        names = sorted(dict.fromkeys(declared), key=lambda g: g[0] != "global")
        self.groups = [(str(n), None if w is None else int(w),
                        num_pages if i == 0
                        else int((group_pages or {}).get(n, full)))
                       for i, (n, w) in enumerate(names)]
        #: the group (index into ``groups``) of each paged pool
        self.pool_group = [names.index(g) for g in declared]
        self.num_pages = num_pages
        every = tuple(range(num_layers))
        self.pool_specs = [(str(p[0]), int(p[1]), int(p[2])) for p in pools]
        self.state_specs = [(str(n), tuple(shape), str(dt))
                            for n, shape, dt, _ in state_pools]
        #: the layers that hold each pool, paged pools first
        self.pool_layers = [tuple(p[3]) if len(p) > 3 else every
                            for p in list(pools) + list(state_pools)]
        self.num_snapshots = int(num_snapshots)
        rows = max_batch_size + self.num_snapshots
        self._pools = tuple(
            _layer_buffers(len(layers),
                           (self.groups[g][2], h, page_size, w), dtype)
            for (_, h, w), layers, g in zip(self.pool_specs, self.pool_layers,
                                            self.pool_group)
        ) + tuple(
            _layer_buffers(len(layers), (rows,) + shape, dt)
            for (_, shape, dt), layers in zip(
                self.state_specs, self.pool_layers[len(self.pool_specs):]))
        # per layer, (pool, index of the layer's buffer in it) of the pools
        # it holds, in the order it is handed them
        self._of_layer = [
            [(j, layers.index(l)) for j, layers in enumerate(self.pool_layers)
             if l in layers] for l in range(num_layers)]
        # the group whose table a layer is handed: its first paged pool's
        self._layer_group = [
            self.pool_group[held[0][0]] if held and held[0][0] < len(
                self.pool_specs) else 0 for held in self._of_layer]
        # one table a group; ``page_table`` is the first group's
        self._tables = [np.full((max_batch_size, self.num_blocks),
                                PAGE_SENTINEL, np.int32) for _ in self.groups]
        self.page_tables = [t.view() for t in self._tables]
        for view in self.page_tables:
            view.flags.writeable = False
        self.page_table = self.page_tables[0]
        # the tables as the device holds them; None once a writer below has
        # changed the host's since it was put
        self._table_devs: List[Optional[jax.Array]] = [None] * len(self.groups)
        self._free: List[int] = list(range(max_batch_size))[::-1]
        self._copy_exes = {}

    @property
    def pools(self):
        return self._pools

    @pools.setter
    def pools(self, value):
        self._pools = tuple(value)

    @property
    def k(self):
        return self.pools[0]

    @k.setter
    def k(self, value):
        self.pools = (value,) + self.pools[1:]

    @property
    def v(self):
        return self.pools[1]

    @v.setter
    def v(self, value):
        self.pools = self.pools[:1] + (value,) + self.pools[2:]

    @property
    def nbytes(self) -> int:
        return _tuple_nbytes(*self.pools)

    @property
    def table_changed(self) -> int:
        """How many tables the next ``tables_device()`` transfers."""
        return sum(t is None for t in self._table_devs)

    def table_device(self, group: int = 0) -> jax.Array:
        """A group's page table as the device operand the compiled decode /
        verify executables consume: the array kept from the last put while
        no writer has changed the host table since. The put takes a copy,
        so a later host write never reaches an array a program may still
        be reading."""
        if self._table_devs[group] is None:
            self._table_devs[group] = jax.device_put(
                self._tables[group].copy())
        return self._table_devs[group]

    def tables_device(self) -> Tuple[jax.Array, ...]:
        """Every group's table, in ``groups``' order."""
        return tuple(self.table_device(g) for g in range(len(self.groups)))

    # -- host-side table bookkeeping (the scheduler's allocators own page
    #    ids; the cache only records who maps where). Every writer drops
    #    the kept device copy --
    def assign_pages(self, slot: int, pages: List[int], start_block: int = 0,
                     group: int = 0):
        self._tables[group][slot, start_block:start_block + len(pages)] = pages
        self._table_devs[group] = None

    def assign_at(self, slot: int, blocks: List[int], pages: List[int],
                  group: int = 0):
        """Map ``blocks[i]`` of ``slot`` to ``pages[i]`` (blocks that need
        not be neighbours: a window group's tails)."""
        if len(blocks):
            self._tables[group][slot, blocks] = pages
            self._table_devs[group] = None

    def repoint(self, slot: int, block: int, page: int, group: int = 0):
        """Map ``block`` of ``slot`` to ``page`` instead (copy-on-write: the
        slot's private copy replaces the shared page)."""
        self._tables[group][slot, block] = page
        self._table_devs[group] = None

    def unmap_before(self, slot: int, block: int, group: int = 0) -> List[int]:
        """Reset ``slot``'s blocks before ``block`` to sentinels (a window
        group's pages behind the slot's window); returns the page ids that
        were mapped there, for the caller to hand back to the allocator."""
        row = self._tables[group][slot, :block]
        pages = [int(p) for p in row[row != PAGE_SENTINEL]]
        if pages:
            row[:] = PAGE_SENTINEL
            self._table_devs[group] = None
        return pages

    def copy_page_exe(self, group: int = 0):
        """The compiled copy-on-write program of a group: ``(*pools, src,
        dst) -> pools`` over the donated pool tuples, page ids as runtime
        scalars, so ONE executable serves every copy and each layer's page
        moves inside its own buffer, in every pool of the group (another
        group's pools pass through). Compiled on first use; a caller that
        must not compile later (the engine, when pages can be shared) asks
        for it up front."""
        if group not in self._copy_exes:
            n = len(self.pool_specs)
            mine = [g == group for g in self.pool_group]

            def copy_page_fn(*a):
                src, dst = a[n:]

                def one(pool):
                    zero = jnp.zeros((), jnp.int32)
                    page = lax.dynamic_slice(
                        pool, (src, zero, zero, zero), (1,) + pool.shape[1:])
                    return lax.dynamic_update_slice(
                        pool, page, (dst, zero, zero, zero))
                return tuple(tuple(map(one, pool)) if m else tuple(pool)
                             for pool, m in zip(a[:n], mine))

            self._copy_exes[group] = jax.jit(
                copy_page_fn, donate_argnums=tuple(range(n))) \
                .lower(*self.pools[:n], jnp.int32(0), jnp.int32(0)).compile()
        return self._copy_exes[group]

    def copy_page(self, src: int, dst: int, group: int = 0):
        """Copy-on-write: duplicate page ``src``'s bytes into page ``dst``
        in every layer of every pool of the group. The caller then repoints
        its table entry at ``dst`` and drops its reference on ``src`` — the
        sharer still mapping ``src`` never observes the write that motivated
        the copy."""
        n = len(self.pool_specs)
        self.pools = tuple(self.copy_page_exe(group)(
            *self.pools[:n], np.int32(src), np.int32(dst))) + self.pools[n:]

    # -- slot state and its snapshots --
    def snapshot_row(self, snapshot: int) -> int:
        """The state buffers' row of snapshot id ``snapshot`` (ids run
        ``[1, num_snapshots]``, as a ``PageAllocator`` hands them out)."""
        return self.max_batch_size + snapshot - 1

    def slot_pages(self, slot: int, group: int = 0) -> List[int]:
        row = self.page_tables[group][slot]
        return [int(p) for p in row if p != PAGE_SENTINEL]

    def clear_slot(self, slot: int, group: int = 0) -> List[int]:
        """Reset a slot's table row (of one group) to sentinels; returns the
        page ids the caller must hand back to that group's allocator."""
        pages = self.slot_pages(slot, group)
        if pages:
            self._tables[group][slot, :] = PAGE_SENTINEL
            self._table_devs[group] = None
        return pages

    # -- slot free list --
    def alloc_slot(self) -> Optional[int]:
        """Lowest free slot index, or None when the batch is full."""
        return self._free.pop() if self._free else None

    def free_slot(self, slot: int):
        self._free.append(slot)
        self._free.sort(reverse=True)

    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def active_slots(self) -> int:
        return self.max_batch_size - len(self._free)

    def layer_entries(self, pools, table, rows=None):
        """Per-layer ``(pool_0, ..., pool_n, where)`` entries of the pool
        tuples, in the order the model declared its pools: ``where`` is the
        page ``table`` for a layer of paged pools, and for a layer of state
        the ``rows`` its state lives in (``None``: rows ``[0, B)``; else
        ``(the row read, the rows written)`` of a one-slot extend).
        ``table`` is one table, or a tuple of them, one a group."""
        n = len(self.pool_specs)
        # one table for all, or one a group (in ``groups``' order)
        of = (lambda l: table[self._layer_group[l]]) \
            if isinstance(table, (tuple, list)) else (lambda l: table)
        return [tuple(pools[j][i] for j, i in held)
                + ((of(l),) if held[0][0] < n else (rows,))
                for l, held in enumerate(self._of_layer)]

    def pools_from_layers(self, per_layer):
        """The pool tuples (by pool, then by its layers) of what every
        layer handed back (its buffers, in ``layer_entries``' order)."""
        out = [[None] * len(layers) for layers in self.pool_layers]
        for held, bufs in zip(self._of_layer, per_layer):
            for (j, i), buf in zip(held, bufs):
                out[j][i] = buf
        return tuple(map(tuple, out))
