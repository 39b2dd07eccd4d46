"""The host's page manager: ``PagedKVCache``, the serving engine's
HBM-resident decode state and who maps what of it.

Per layer and pool one ``[num_pages, heads, page_size, width]`` buffer
preallocated at engine construction, plus a host page table a group, so
every prefill and every decode step runs at a FIXED shape: XLA compiles the
prefill once per prompt bucket and the decode step exactly once, no matter
how many tokens or requests flow through. Only the engine holds one.

What runs on the device over these pools is below the model, in
``kernels/``: a layer writes a pool and views it through a table with
``kernels/pools.py`` (``paged_write_kv``, ``paged_gather``), and attends
over it with ``kernels/paged_attention.py`` / ``latent_attention.py``, each
kernel beside its jnp reference; ``kernels/tier.py`` says which of the two a
trace bakes in. The one program built here is the copy-on-write page copy
(``copy_page_exe``).
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..kernels.pools import PAGE_SENTINEL


def _layer_buffers(num_layers: int, shape, dtype) -> Tuple[jax.Array, ...]:
    """One zeroed device buffer per layer. Separate buffers, not one
    stacked array: a compiled program that takes the tuple donated and
    returns each layer's updated buffer writes every layer where it lies
    (XLA aliases a donated parameter to the output it is scattered into),
    which a slice of a stacked array can never be."""
    return tuple(jnp.zeros(shape, dtype) for _ in range(num_layers))


def _tuple_nbytes(*pools) -> int:
    return int(sum(a.size * a.dtype.itemsize for p in pools for a in p))


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
    A pool may be READ by layers that hold none (a sixth entry names the
    readers: a decoder-hybrid-decoder's cross layers over one layer's K/V);
    that changes nothing here but ``pool_readers``: such a layer is handed
    an empty entry, and the model carries the holder's written pool to it.
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
        #: the layers that READ each paged pool: its holders, or what the
        #: declaration's sixth entry names (a pool that layers without one
        #: of their own attend to: ONE buffer, one page a block, however
        #: many read it)
        self.pool_readers = [tuple(p[5]) if len(p) > 5 else layers
                             for p, layers in zip(pools, self.pool_layers)]
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
        ``(the row read, the rows written)`` of a one-slot extend); a layer
        that holds no pool at all gets ``()``. ``table`` is one table, or a
        tuple of them, one a group."""
        n = len(self.pool_specs)
        # one table for all, or one a group (in ``groups``' order)
        of = (lambda l: table[self._layer_group[l]]) \
            if isinstance(table, (tuple, list)) else (lambda l: table)
        # (a layer that holds no pool of either kind, an FFN alone, is
        # handed an empty entry)
        return [tuple(pools[j][i] for j, i in held)
                + ((of(l),) if held[0][0] < n else (rows,)) if held else ()
                for l, held in enumerate(self._of_layer)]

    def pools_from_layers(self, per_layer):
        """The pool tuples (by pool, then by its layers) of what every
        layer handed back (its buffers, in ``layer_entries``' order)."""
        out = [[None] * len(layers) for layers in self.pool_layers]
        for held, bufs in zip(self._of_layer, per_layer):
            for (j, i), buf in zip(held, bufs):
                out[j][i] = buf
        return tuple(map(tuple, out))
