"""Radix/prefix tree mapping token prefixes to cached KV pages.

Keyed at PAGE granularity: every edge is one page's worth of tokens
(``page_tokens`` ids), so a node == one cached page and longest-prefix
match returns whole shared pages — a cache hit skips prefill for
exactly the tokens those pages cover, the "RPC Considered Harmful"
(arXiv:1805.08430) lesson applied to attention state: never recompute
(or re-ship) what the device already holds.

Refcount contract with :class:`~brpc_tpu.kvcache.pages.PagePool`:
the tree holds ONE ref on every page it retains.  Active sequences
hold their own refs, so an evictable page has ``refs == 1`` (tree
only) — eviction can NEVER free a page a live or forked sequence
still references, which is the safety property the chaos suite
asserts under injected pool exhaustion.

Eviction is LRU-by-leaf: leaves are the only removable nodes (an
interior node's pages are a prefix of its children's cached
sequences), ordered by a deterministic logical clock bumped on every
match — no wall-time in the decision, so seeded chaos runs replay.
"""
from __future__ import annotations

import itertools
import threading
from brpc_tpu.butil.lockprof import InstrumentedLock
from typing import Optional, Sequence

from brpc_tpu import fault


class _Node:
    __slots__ = ("chunk", "page", "children", "parent", "last_used",
                 "snapshot")

    def __init__(self, chunk: tuple, page, parent: Optional["_Node"]):
        self.chunk = chunk              # page_tokens token ids
        self.page = page                # the KVPage holding their KV
        self.children: dict[tuple, _Node] = {}
        self.parent = parent
        self.last_used = 0
        # recurrent-state snapshot id (ISSUE 32): the state of the
        # sequence after exactly this node's prefix, where the cache
        # holds one; owned by the node, freed when the node is evicted
        self.snapshot = None


class RadixTree:
    """Prefix tree of cached KV pages (one page per node)."""

    def __init__(self, pagepool, *, name: str = "kv"):
        self.pagepool = pagepool
        self.page_tokens = pagepool.page_tokens
        self.name = name
        self._mu = InstrumentedLock("kvcache.radix")
        self._root = _Node((), None, None)
        self._clock = itertools.count(1)
        self._nodes = 0
        # called with a snapshot id when its node is evicted (the
        # layered cache frees the state row); None: no snapshots
        self.snapshot_free = None

    def _chunks(self, tokens: Sequence[int],
                max_chunks: Optional[int] = None):
        pt = self.page_tokens
        n = len(tokens) // pt
        if max_chunks is not None:
            n = min(n, max_chunks)
        return [tuple(int(t) for t in tokens[i * pt:(i + 1) * pt])
                for i in range(n)]

    # ---- lookup ----

    def match(self, tokens: Sequence[int], *,
              max_chunks: Optional[int] = None,
              snapshots: bool = False):
        """Longest cached prefix of `tokens`, in whole pages.  Returns
        the shared page handles in order; bumps LRU on the path.  The
        caller refs the pages it keeps — match itself takes none.
        ``snapshots=True`` returns ``(pages, snapshot ids)``, one id
        (or None) a matched node."""
        with self._mu:
            node = self._root
            pages, snaps = [], []
            now = next(self._clock)
            for chunk in self._chunks(tokens, max_chunks):
                child = node.children.get(chunk)
                if child is None:
                    break
                child.last_used = now
                pages.append(child.page)
                snaps.append(child.snapshot)
                node = child
            return (pages, snaps) if snapshots else pages

    def attach_snapshot(self, tokens: Sequence[int], n_chunks: int,
                        snapshot) -> bool:
        """Give the node that ends the first ``n_chunks`` chunks of
        `tokens` the state snapshot ``snapshot``.  False (the caller
        keeps the id) where the node is gone or already has one."""
        with self._mu:
            node = self._root
            for chunk in self._chunks(tokens, n_chunks):
                node = node.children.get(chunk)
                if node is None:
                    return False
            if node is self._root or node.snapshot is not None:
                return False
            node.snapshot = snapshot
            return True

    # ---- insert ----

    def insert(self, tokens: Sequence[int], pages: Sequence) -> int:
        """Cache `tokens`' full-page chunks backed by `pages` (aligned,
        one per chunk).  For each chunk not already cached the tree
        takes its own ref on the offered page; chunks already present
        keep their existing page (the caller's copy stays the
        caller's).  Returns how many pages the tree newly retained."""
        chunks = self._chunks(tokens, max_chunks=len(pages))
        retained = 0
        with self._mu:
            node = self._root
            now = next(self._clock)
            for chunk, page in zip(chunks, pages):
                child = node.children.get(chunk)
                if child is None:
                    self.pagepool.ref(page)
                    child = _Node(chunk, page, node)
                    node.children[chunk] = child
                    self._nodes += 1
                    retained += 1
                child.last_used = now
                node = child
        return retained

    # ---- eviction ----

    def evict(self, min_pages: int, span=None) -> int:
        """Free at least `min_pages` cached pages, LRU leaves first.
        Only pages with refcount 1 (tree-only) are candidates — a page
        an active/forked sequence still references is untouchable, as
        is every ancestor it pins.  Returns pages actually freed (may
        be < min_pages when the tree runs out of evictable leaves).
        ``span`` (the rpcz span of whoever forced the eviction — a
        page-alloc retry under pool pressure) gets the freed page ids
        annotated, so a timeline shows WHOSE cached prefixes paid."""
        if fault.ENABLED and fault.hit(
                "kvcache.evict", tree=self.name) is not None:
            raise MemoryError("injected KV eviction failure")
        freed = 0
        while freed < min_pages:
            # one DFS per ROUND collects every currently-evictable leaf
            # (LRU order), not one full scan per page — rounds only
            # repeat because evicting a leaf layer can expose its
            # parents as the next layer of leaves
            with self._mu:
                victims = []
                stack = [self._root]
                while stack:
                    n = stack.pop()
                    for c in n.children.values():
                        if c.children:
                            stack.append(c)
                        elif c.page.refs == 1:
                            victims.append(c)
                victims.sort(key=lambda v: v.last_used)
                victims = victims[: min_pages - freed]
                for v in victims:
                    del v.parent.children[v.chunk]
                self._nodes -= len(victims)
                pages = [v.page for v in victims]
                if self.snapshot_free is not None:
                    for v in victims:
                        if v.snapshot is not None:
                            self.snapshot_free(v.snapshot)
                            v.snapshot = None
            if not pages:
                break
            if span is not None and getattr(span, "trace_id", 0):
                pids = [p.pid for p in pages[:8]]
                span.annotate(
                    f"kv evict: freed {len(pages)} LRU cached pages "
                    f"(pids {pids}{'...' if len(pages) > 8 else ''})")
            # unref outside _mu: it may release whole blocks back to
            # the BlockPool (its own locking)
            for page in pages:
                self.pagepool.unref(page)
            freed += len(pages)
        return freed

    def evict_all(self) -> int:
        """Drop every evictable page (cache clear / shutdown): evict()
        already rounds until nothing is removable, so blocks pinned
        only by the cache return to the BlockPool baseline."""
        return self.evict(1 << 30)

    # ---- introspection ----

    def node_count(self) -> int:
        with self._mu:
            return self._nodes

    def cached_tokens(self) -> int:
        with self._mu:
            return self._nodes * self.page_tokens
