"""Radix prefix cache: block-granular KV reuse across requests.

Serving traffic from many users repeats itself — system prompts, few-shot
preambles, multi-turn histories. With the KV cache block-paged (PR 13),
that repetition has a physical unit: two requests whose prompts agree on
the first ``page_size * b`` tokens can map the SAME ``b`` physical pages
and prefill only the differing suffix. This module is the index that finds
the agreement: a radix trie keyed on page-sized token blocks whose nodes
hold page ids (the SGLang RadixAttention idea, reduced to the static-shape
engine's host-side page table).

Sharing is safe because of two invariants enforced elsewhere:

* ``PageAllocator`` refcounts pages — the trie holds one reference per
  cached node, every splice adds one per shared page, and a page returns
  to the free list only when its LAST reference drops (scheduler.py).
* The engine never writes a shared page: matching is FULL blocks only and
  capped at ``(len(prompt) - 1) // page_size``, so the suffix prefill is
  always >= 1 token and starts exactly at a block boundary; decode then
  appends strictly after the prompt. A defensive copy-on-write hook
  (``Engine._ensure_writable`` + ``PagedKVCache.copy_page``) backs the
  invariant up: any write that WOULD land on a shared page gets a private
  copy first.

Eviction is LRU over trie leaves: releasing a leaf drops only the trie's
reference, so a page still spliced into a live request survives eviction
and is reclaimed when that request finishes.

A model some of whose layers keep RECURRENT STATE (``PagedKVCache``'s state
pools) cannot resume at any block the pages reach: the state exists only
where a snapshot of it was taken. A node may therefore carry a snapshot id
(a row of the state buffers, handed out by a second ``PageAllocator``):
``deepest_snapshot`` finds the deepest one on a matched path, which is
where the engine resumes; ``attach_snapshot`` hands a node the one the
engine took at its block. A snapshot is freed with its node, and when the
snapshot pool itself is short ``reserve_snapshots`` takes them from the
nodes whose snapshot was least recently used (the nodes and their pages
stay: they still say where prompts part).

A model whose pools stand in several page GROUPS (``PagedKVCache``: a
group of its own for the layers that attend a sliding window) gives a node
a page of EACH group, a window group's only where it is still live: such a
group's pages behind a slot's window are freed as the slot moves on, and
the trie keeps the ones it was handed at ``insert`` (the last window of a
prompt, the window before the point where prompts parted). A cached prefix
can be RESUMED only at a depth whose preceding window still has its pages
in every window group (``match_groups``: a document's end, a turn's end);
a match that reaches deeper is cut back to the deepest such depth and the
rest recomputed. Evicting a node frees its page in every group.

Flag-gated metrics: the engine counts ``serving.prefix.hits`` /
``serving.prefix.misses`` per ADMISSION (a blocked head request peeks the
trie every step; counting in ``match`` would inflate hits), and this
module gauges ``serving.prefix.pages_shared`` — how many physical pages
currently have more than one reference.
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Optional, Sequence, Tuple

from ..observability import metrics as _metrics
from .scheduler import PageAllocator

_OWNER = "prefix-cache"


class _Node:
    """One cached block: ``key`` (its page_size-token tuple, kept for
    repr/debugging), the physical ``page`` holding that block's K/V, and an
    LRU stamp. Children are keyed by the NEXT block's token tuple."""

    __slots__ = ("key", "page", "more", "last_used", "children", "parent",
                 "snapshot", "snapshot_used")

    def __init__(self, key: Tuple[int, ...], page: int, parent: "_Node"):
        self.key = key
        self.page = page
        # the block's page in each further group (None where it has none:
        # a window group's page the trie was never handed); None where
        # there is one group
        self.more: Optional[List[Optional[int]]] = None
        self.snapshot: Optional[int] = None  # id of the state as of this block
        self.snapshot_used = 0  # when it was attached or last resumed from
        self.last_used = 0
        self.children: Dict[Tuple[int, ...], _Node] = {}
        self.parent = parent


class PrefixCache:
    """Radix/trie index from block-aligned token prefixes to page ids.

    The trie owns one allocator reference per node (taken at ``insert``,
    dropped at eviction/``clear``); callers own their own references per
    splice (``match`` returns page ids, the engine ``retain``s them for the
    admitted slot). Block granularity means partial-block matches are
    ignored — a block is shareable only if ALL ``page_size`` of its tokens
    match, which is exactly the unit the page table can splice.
    """

    def __init__(self, page_size: int, allocator: PageAllocator,
                 snapshots: Optional[PageAllocator] = None,
                 more: Sequence[Tuple[PageAllocator, Optional[int]]] = ()):
        if page_size < 1:
            raise ValueError(f"page_size {page_size}")
        self.page_size = page_size
        self.allocator = allocator
        #: the further page groups' (allocator, window in tokens or None)
        self.more = list(more)
        #: the snapshot rows' allocator (a model with recurrent state), and
        #: the nodes that carry one
        self.snapshots = snapshots
        self._with_snapshot = set()
        self.snapshots_dropped = 0  # snapshots evicted so far
        self._root = _Node((), -1, None)  # sentinel; holds no page
        self._clock = itertools.count(1)
        self.num_nodes = 0
        # the nodes without children, kept as nodes come and go: eviction
        # looks at these only. A long shared document is a chain of
        # thousands of nodes and ONE leaf; walking the whole trie for every
        # node dropped cost 58 ms an admission at 19,000 nodes
        self._leaf_set = set()

    # ------------------------------------------------------------- lookup

    def _blocks(self, tokens: Sequence[int],
                limit: Optional[int] = None) -> List[Tuple[int, ...]]:
        """The whole blocks of ``tokens`` as keys, the first ``limit`` only
        where one is given."""
        ps = self.page_size
        nfull = len(tokens) // ps
        if limit is not None:
            nfull = min(nfull, limit)
        return [tuple(int(t) for t in tokens[j * ps:(j + 1) * ps])
                for j in range(nfull)]

    def match(self, prompt: Sequence[int]) -> Tuple[int, List[int]]:
        """Longest shareable prefix of ``prompt`` already in the cache:
        ``(hit_blocks, pages)`` where ``pages[j]`` backs block ``j``.

        Capped at ``(len(prompt) - 1) // page_size`` blocks — when the
        prompt is block-aligned and FULLY cached, the last block is
        deliberately left to the suffix prefill so the engine always has
        >= 1 suffix token to run (the prefill programs produce the first
        token's logits) and never maps a shared page it would write.
        """
        path = self._matched(prompt)
        return len(path), [node.page for node in path]

    def _matched(self, prompt: Sequence[int]) -> List[_Node]:
        """The nodes of ``match``'s prefix, each stamped as used now."""
        cap = max(0, (len(prompt) - 1) // self.page_size)
        node, path = self._root, []
        stamp = next(self._clock)
        for key in self._blocks(prompt, cap):
            node = node.children.get(key)
            if node is None:
                break
            node.last_used = stamp
            path.append(node)
        return path

    def match_groups(self, prompt: Sequence[int]
                     ) -> Tuple[int, int, List[List[int]]]:
        """``match`` for several page groups: ``(hit_blocks, resume_blocks,
        pages)``. ``hit_blocks`` is how far the prompt's blocks are cached
        (the first group's pages reach that far); ``resume_blocks <=
        hit_blocks`` the deepest depth a request can RESUME at: in every
        window group each of the blocks that hold the ``window - 1`` tokens
        before it still has its page (depth 0 always can: nothing precedes
        it). Where the model keeps recurrent state too (``snapshots``), a
        depth can resume only if its node carries a snapshot BESIDES.
        ``pages[g][j]`` backs block ``j`` in group ``g`` (``g = 0`` the
        first group, as far as ``hit_blocks``; a further group as far as
        ``resume_blocks``, -1 where the node has none: before the window)."""
        path = self._matched(prompt)
        hit, first = len(path), [node.page for node in path]
        ps = self.page_size
        # the depths that can resume: all, or beside recurrent state those
        # that carry a snapshot
        can = [self.snapshots is None or node.snapshot is not None
               for node in path]
        for g, (_, window) in enumerate(self.more):
            if window is None:
                continue
            back = (window + ps - 2) // ps    # blocks a depth looks back on
            run = 0         # consecutive nodes with a page, up to here
            for d, node in enumerate(path, 1):
                run = run + 1 if node.more[g] is not None else 0
                can[d - 1] = can[d - 1] and run >= min(d, back)
        windows = any(w is not None for _, w in self.more)
        resume = max((d for d, ok in enumerate(can, 1) if ok), default=0) \
            if windows else hit
        return hit, resume, [first] + [
            [-1 if n.more[g] is None else n.more[g] for n in path[:resume]]
            for g in range(len(self.more))]

    def _path(self, prompt: Sequence[int], depth: int) -> List[_Node]:
        """The nodes of ``prompt``'s first ``depth`` blocks, as far as the
        trie has them."""
        node, path = self._root, []
        for key in self._blocks(prompt, depth):
            node = node.children.get(key)
            if node is None:
                break
            path.append(node)
        return path

    # ---------------------------------------------------------- snapshots

    def deepest_snapshot(self, prompt: Sequence[int],
                         hit_blocks: int) -> Tuple[int, Optional[int]]:
        """``(blocks, snapshot id)`` of the deepest node within ``prompt``'s
        first ``hit_blocks`` matched blocks that carries a snapshot;
        ``(0, None)`` where none does (a cold prefill)."""
        path = self._path(prompt, hit_blocks)
        for depth in range(len(path), 0, -1):
            node = path[depth - 1]
            if node.snapshot is not None:
                node.snapshot_used = next(self._clock)
                return depth, node.snapshot
        return 0, None

    def reserve_snapshots(self, n: int,
                          owner: Optional[str] = None) -> Optional[List[int]]:
        """``n`` free snapshot ids for the caller to fill, taken from the
        nodes whose snapshot was least recently USED (attached or resumed
        from: a match stamps every node on its path, so a chat's earlier
        prompt ends look as fresh as its last one by ``last_used``, while
        only the deepest is ever resumed from again) where the pool is
        short; None (and nothing allocated) where that is not enough — an
        id somebody else still holds a reference on is not freed by
        leaving its node."""
        while self.snapshots.num_free < n and self._with_snapshot:
            self._drop_snapshot(min(self._with_snapshot,
                                    key=lambda nd: nd.snapshot_used), True)
        return self.snapshots.alloc(n, owner=owner)

    def attach_snapshot(self, prompt: Sequence[int], depth: int,
                        snapshot: int, owner: Optional[str] = None) -> bool:
        """Hand the node of ``prompt``'s block ``depth`` (1-based: the state
        after ``depth`` whole blocks) the snapshot ``snapshot``, the
        caller's reference with it. Where there is no such node, or it
        carries a snapshot already, the caller's is freed; False then. The
        snapshot it supersedes, if any, is freed (not counted as
        evicted)."""
        path = self._path(prompt, depth)
        ok = len(path) == depth and path[-1].snapshot is None
        if ok:
            self.snapshots.retain([snapshot], owner=_OWNER)
            path[-1].snapshot = snapshot
            path[-1].snapshot_used = next(self._clock)
            self._with_snapshot.add(path[-1])
            # the nearest snapshot above it on an UNBRANCHED chain is
            # superseded: whatever matches that far matches down to here
            # (a chat's earlier prompt end). Where prompts part (a shared
            # system prompt's last block) the node has several children
            # and its snapshot stays
            for j in range(depth - 2, -1, -1):
                above = path[j]
                if above.snapshot is not None:
                    if len(above.children) == 1:
                        self._drop_snapshot(above)
                        self._free_windows(path, j)
                    break
        self.snapshots.free([snapshot], owner=owner)
        return ok

    def _free_windows(self, path: List[_Node], j: int):
        """``path[j]`` has lost its snapshot to the deeper end of ``path``
        on an unbranched chain: the window groups' pages that only a resume
        THERE looked back on go (beside recurrent state no other depth can
        resume): those of ``path[j]`` and the unbranched nodes before it
        without a snapshot, as far as a window reaches, but for the ones the
        new depth's window still holds."""
        ps = self.page_size
        for g, (alloc, window) in enumerate(self.more):
            if window is None:
                continue
            back = (window + ps - 2) // ps
            for i in range(j, max(j - back, -1), -1):
                node = path[i]
                if i != j and (node.snapshot is not None
                               or len(node.children) > 1):
                    break
                if i < len(path) - back and node.more[g] is not None:
                    alloc.free([node.more[g]], owner=_OWNER)
                    node.more[g] = None

    def _drop_snapshot(self, node: _Node, evicted: bool = False):
        self.snapshots.free([node.snapshot], owner=_OWNER)
        self.snapshots_dropped += evicted
        node.snapshot = None
        self._with_snapshot.discard(node)

    # ------------------------------------------------------------- insert

    def insert(self, prompt: Sequence[int], pages: Sequence[int],
               more: Sequence[Sequence[int]] = ()) -> int:
        """Record that ``pages[j]`` holds block ``j`` of ``prompt``'s K/V.
        Blocks already present keep their existing page (the inserting
        request's duplicate stays private to it and frees at its finish);
        new nodes take a trie-owned reference on their page. ``more[g][j]``
        is the block's page in further group ``g`` (-1: the request holds
        none there): a node, new or not, that has none yet takes it, with a
        reference of the trie's own. Returns the number of NEW nodes
        created."""
        blocks = self._blocks(prompt)
        n = min(len(blocks), len(pages))
        node, created = self._root, 0
        stamp = next(self._clock)
        for j in range(n):
            key = blocks[j]
            child = node.children.get(key)
            if child is None:
                page = int(pages[j])
                self.allocator.retain([page], owner=_OWNER)
                child = _Node(key, page, node)
                if self.more:
                    child.more = [None] * len(self.more)
                node.children[key] = child
                self._leaf_set.discard(node)
                self._leaf_set.add(child)
                self.num_nodes += 1
                created += 1
            for g, row in enumerate(more):
                if child.more[g] is None and j < len(row) and row[j] >= 0:
                    self.more[g][0].retain([int(row[j])], owner=_OWNER)
                    child.more[g] = int(row[j])
            child.last_used = stamp
            node = child
        self._export_gauges()
        return created

    # ----------------------------------------------------------- eviction

    def _leaves(self) -> List[_Node]:
        return list(self._leaf_set)

    def _evict_node(self, node: _Node):
        parent = node.parent
        del parent.children[node.key]
        self._leaf_set.discard(node)
        if parent is not self._root and not parent.children:
            self._leaf_set.add(parent)
        self.num_nodes -= 1
        self.allocator.free([node.page], owner=_OWNER)
        for (alloc, _), page in zip(self.more, node.more or ()):
            if page is not None:
                alloc.free([page], owner=_OWNER)
        if node.snapshot is not None:
            self._drop_snapshot(node, True)

    def evict_lru(self, need_free: int, group: int = 0) -> int:
        """Release least-recently-used leaves until the allocator (of page
        group ``group``: 0 the first, ``g + 1`` further group ``g``) has
        ``need_free`` free pages or nothing evictable remains. Evicting a
        node drops only the TRIE's reference — a page still mapped by a
        live request stays allocated until that request finishes — so this
        keeps going past still-shared pages. Returns nodes evicted."""
        evicted = 0
        allocator = self.more[group - 1][0] if group else self.allocator
        while allocator.num_free < need_free:
            leaves = self._leaves()
            if not leaves:
                break
            self._evict_node(min(leaves, key=lambda n: n.last_used))
            evicted += 1
        if evicted:
            self._export_gauges()
        return evicted

    def clear(self) -> int:
        """Drop every node (and the trie's page references). Pages spliced
        into live requests stay allocated; everything else returns to the
        free list. Returns nodes dropped."""
        dropped = 0
        for leaf in sorted(self._leaves(), key=lambda n: -n.last_used):
            node = leaf
            while node is not self._root and not node.children:
                parent = node.parent
                self._evict_node(node)
                dropped += 1
                node = parent
        self._export_gauges()
        return dropped

    def _export_gauges(self):
        if not _metrics.enabled():
            return
        _metrics.gauge("serving.prefix.pages_shared",
                       self.allocator.num_shared)
