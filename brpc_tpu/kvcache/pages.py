"""Fixed-size KV pages carved from leased ICI BlockPool blocks.

The paper's north star is IOBuf blocks backed by HBM as the substrate
for zero-copy tensor serving; RDMAbox (arXiv:2104.12197) argues the
same discipline for RDMA — treat attention state as pooled,
reference-counted, pre-registered device memory.  This module is that
discipline for KV caches:

  * the :class:`PagePool` leases whole blocks from the per-device
    :class:`~brpc_tpu.ici.block_pool.BlockPool` and carves each into
    ``pages_per_block`` fixed-size pages (the block<->page table);
  * every page carries a refcount — sequences share pages
    copy-on-write, the radix tree holds one ref per cached page, and a
    page returns to the free list only at refcount zero;
  * a block whose pages are ALL free is released back to the BlockPool,
    so engine/chaos occupancy leak checks see the exact baseline
    discipline PR 2 established for raw slot leases.

Page layout: ``page_tokens`` slots of ``kv_bytes_per_token`` bytes.  A
token's slot holds EITHER its token id as a little-endian int32 in the
leading bytes (the pure-token harness stand-in) OR the token's real
packed K/V vectors (``write_slots`` — the ModelRunner path, ISSUE 10:
``[n_layers, 2, n_kv_heads, head_dim]`` f32 per slot, written by the
transformer and read back by the paged-attention kernel).  All page
writes and page-to-page copies are on-device ``dynamic_update_slice``
splices into the block buffer — sibling pages in the same block are
never clobbered and no full-block host bounce happens on the extend
path.

ARENA VIEW (ISSUE 10): the paged-attention kernel wants ONE fixed-shape
device array indexable by page, compiled once for the life of the
model.  Blocks come and go, so each leased block is pinned to a STABLE
row in ``[0, max_blocks)`` for its lifetime and every page gets a flat
arena index ``row * pages_per_block + page.index``; :meth:`arena`
stacks the live block buffers (zeros for unleased rows) into
``[max_blocks * pages_per_block, page_bytes]`` and :meth:`flat_ids`
translates the engine's pid page tables into arena indices.  The stack
is O(arena bytes) per call — on TPU a production path would pin one
arena buffer; the layout contract (stable flat index per live page) is
what the kernel compiles against either way.
"""
from __future__ import annotations

import itertools
import threading
from brpc_tpu.butil.lockprof import InstrumentedLock
from typing import Optional, Sequence

import numpy as np

from brpc_tpu import fault
from brpc_tpu.bvar import Adder

_page_ids = itertools.count(1)


class KVPage:
    """One fixed-size page: a (block, index) cell in the block<->page
    table plus a refcount.  Identity is the stable integer ``pid`` —
    page tables handed to a jitted step function are int32 arrays of
    pids."""

    __slots__ = ("pid", "block", "index", "refs")

    def __init__(self, block, index: int):
        self.pid = next(_page_ids)
        self.block = block           # leased BlockPool block
        self.index = index           # page slot within the block
        self.refs = 0

    def __repr__(self):
        return f"<KVPage {self.pid} blk={self.block.slot} " \
               f"idx={self.index} refs={self.refs}>"


class PagePool:
    """Carves BlockPool blocks into refcounted KV pages.

    ``max_blocks`` bounds how many blocks this pool may hold leased at
    once — the pool's own pressure signal (callers run eviction and
    retry) arrives before the shared device pool is drained under
    every other subsystem's feet.
    """

    def __init__(self, pool=None, device=None, *,
                 page_bytes: int = 1024, page_tokens: int = 16,
                 max_blocks: int = 8, name: str = "kv"):
        if pool is None:
            from brpc_tpu.ici.block_pool import get_block_pool
            pool = get_block_pool(device)
        if page_bytes % page_tokens:
            raise ValueError("page_bytes must be a multiple of page_tokens")
        self.kv_bytes_per_token = page_bytes // page_tokens
        if self.kv_bytes_per_token < 4:
            raise ValueError("need >= 4 bytes per token slot (int32 id)")
        self.pool = pool
        self.page_bytes = int(page_bytes)
        self.page_tokens = int(page_tokens)
        self.block_class = next(
            (c for c in pool.classes if c >= page_bytes), None)
        if self.block_class is None:
            raise ValueError(f"page_bytes {page_bytes} exceeds the largest "
                             f"block class {pool.classes[-1]}")
        self.pages_per_block = self.block_class // self.page_bytes
        self.max_blocks = int(max_blocks)
        self.name = name
        self._mu = InstrumentedLock("kvcache.pool")
        # serializes _splice's read-modify-write: two concurrent
        # splices into sibling pages of ONE block would otherwise each
        # rebuild the block buffer from the same base and the loser's
        # write would vanish
        self._io_mu = InstrumentedLock("kvcache.pool_io")
        # block<->page table: block key -> the pages carved from it
        self._blocks: dict[tuple, tuple] = {}   # key -> (block, [pages])
        self._free: list[KVPage] = []
        # stable arena rows (ISSUE 10): a leased block keeps one row in
        # [0, max_blocks) for its whole lease, so every live page's
        # flat arena index is constant and the paged-attention kernel
        # compiles once against the [max_blocks * pages_per_block]
        # layout
        self._row_of: dict[tuple, int] = {}     # block key -> arena row
        self._free_rows: list[int] = list(range(self.max_blocks))[::-1]
        self._pid_flat: dict[int, int] = {}     # pid -> flat arena index
        self._zero_row = None                   # cached empty-row buffer
        self.page_allocs = Adder()
        self.page_frees = Adder()
        self.block_leases = Adder()
        self.block_releases = Adder()
        self.batch_splices = Adder()

    @staticmethod
    def _bkey(block) -> tuple:
        return (block.size_class, block.slot)

    # ---- allocation / refcounting ----

    def alloc_page(self) -> KVPage:
        """A fresh exclusive page (refs=1 for the caller).  Leases and
        carves a new block when the free list is dry; raises
        MemoryError at ``max_blocks`` (callers evict and retry)."""
        if fault.ENABLED and fault.hit(
                "kvcache.page_alloc", pool=self.name) is not None:
            raise MemoryError("injected KV page exhaustion")
        with self._mu:
            if not self._free:
                if len(self._blocks) >= self.max_blocks:
                    raise MemoryError(
                        f"KV page pool at max_blocks={self.max_blocks} "
                        f"({self.pages_per_block} pages/block)")
                block = self.pool.alloc(self.block_class)
                self.block_leases.add(1)
                pages = [KVPage(block, i)
                         for i in range(self.pages_per_block)]
                key = self._bkey(block)
                self._blocks[key] = (block, pages)
                row = self._free_rows.pop()
                self._row_of[key] = row
                for p in pages:
                    self._pid_flat[p.pid] = \
                        row * self.pages_per_block + p.index
                self._free.extend(reversed(pages))
            page = self._free.pop()
            assert page.refs == 0, f"free-list page with refs: {page}"
            page.refs = 1
            self.page_allocs.add(1)
            return page

    def ref(self, page: KVPage) -> None:
        with self._mu:
            if page.refs <= 0:
                raise RuntimeError(f"ref on dead page {page}")
            page.refs += 1

    def refs(self, page: KVPage) -> int:
        with self._mu:
            return page.refs

    def unref(self, page: KVPage) -> None:
        """Drop one reference; at zero the page joins the free list and
        a fully-free block is released back to the BlockPool (the
        occupancy-baseline discipline the chaos suite leak-checks)."""
        release = None
        with self._mu:
            if page.refs <= 0:
                raise RuntimeError(f"unref on dead page {page} "
                                   f"(double free?)")
            page.refs -= 1
            if page.refs:
                return
            self.page_frees.add(1)
            key = self._bkey(page.block)
            entry = self._blocks.get(key)
            if entry is None:          # block already released (bug guard)
                raise RuntimeError(f"page {page} has no block entry")
            block, pages = entry
            if all(p.refs == 0 for p in pages):
                # whole block idle: return it to the device pool and
                # retire its pages (ids are never reused)
                del self._blocks[key]
                self._free = [p for p in self._free
                              if self._bkey(p.block) != key]
                self._free_rows.append(self._row_of.pop(key))
                for p in pages:
                    self._pid_flat.pop(p.pid, None)
                self.block_releases.add(1)
                release = block
            else:
                self._free.append(page)
        if release is not None:
            release.free()

    # ---- page I/O (on-device splices; see module docstring) ----

    def _offset(self, page: KVPage, slot: int = 0) -> int:
        return page.index * self.page_bytes + slot * self.kv_bytes_per_token

    def write(self, page: KVPage, slot: int,
              tokens: Sequence[int]) -> None:
        """Write token ids into consecutive slots of `page` starting at
        `slot`.  The int32 payload ships H2D once; the splice into the
        block buffer runs on device."""
        n = len(tokens)
        if slot < 0 or slot + n > self.page_tokens:
            raise ValueError(f"write [{slot},{slot + n}) exceeds "
                             f"page_tokens={self.page_tokens}")
        piece = np.zeros((n * self.kv_bytes_per_token,), np.uint8)
        ids = np.asarray(tokens, dtype="<i4").view(np.uint8)
        piece.reshape(n, self.kv_bytes_per_token)[:, :4] = \
            ids.reshape(n, 4)
        self._splice(page.block, piece, self._offset(page, slot))

    def write_slots(self, page: KVPage, slot: int, rows) -> None:
        """Write RAW per-token vector payloads (the ModelRunner path,
        ISSUE 10) into consecutive slots of `page` starting at `slot`:
        ``rows`` is ``[n, kv_bytes_per_token]`` uint8 — each row is one
        token's packed K/V vectors, spliced on device exactly like the
        stand-in :meth:`write` (one splice per contiguous run)."""
        rows = np.ascontiguousarray(rows, np.uint8)
        if rows.ndim != 2 or rows.shape[1] != self.kv_bytes_per_token:
            raise ValueError(
                f"write_slots rows must be [n, {self.kv_bytes_per_token}]"
                f" uint8, got {rows.shape}")
        n = rows.shape[0]
        if slot < 0 or slot + n > self.page_tokens:
            raise ValueError(f"write_slots [{slot},{slot + n}) exceeds "
                             f"page_tokens={self.page_tokens}")
        self._splice(page.block, rows.reshape(-1),
                     self._offset(page, slot))

    def write_slots_batch(self, runs) -> None:
        """Splice MANY per-token vector runs as ONE batch (ISSUE 11 —
        the decode-side write primitive): ``runs`` is a sequence of
        ``(page, slot, rows)`` triples with the :meth:`write_slots`
        shapes.  The whole batch ships host-to-device in ONE
        ``device_put`` of the concatenated payload and splices under
        ONE ``_io_mu`` acquisition — a verify-commit (or a plain decode
        step) pays one call across every slot instead of a lock +
        transfer round-trip per slot.  Runs are validated up front; a
        bad run fails the whole batch before any byte lands."""
        import jax
        staged = []
        for page, slot, rows in runs:
            rows = np.ascontiguousarray(rows, np.uint8)
            if rows.ndim != 2 or rows.shape[1] != self.kv_bytes_per_token:
                raise ValueError(
                    f"write_slots_batch rows must be "
                    f"[n, {self.kv_bytes_per_token}] uint8, "
                    f"got {rows.shape}")
            n = rows.shape[0]
            if slot < 0 or slot + n > self.page_tokens:
                raise ValueError(
                    f"write_slots_batch [{slot},{slot + n}) exceeds "
                    f"page_tokens={self.page_tokens}")
            staged.append((page, slot, rows))
        if not staged:
            return
        payload = np.concatenate([r.reshape(-1) for _, _, r in staged])
        dev = jax.device_put(payload, self.pool.device)
        self.batch_splices.add(1)
        off = 0
        with self._io_mu:
            for page, slot, rows in staged:
                nb = rows.size
                self._splice_locked(page.block, dev[off:off + nb],
                                    self._offset(page, slot))
                off += nb

    def flat_ids(self, pids) -> list:
        """Translate page ids (the engine's gathered page tables) into
        FLAT ARENA indices for :meth:`arena`; -1 (padding) and dead
        pids map to -1."""
        with self._mu:
            return [self._pid_flat.get(int(p), -1) for p in pids]

    def arena(self):
        """The whole pool as ONE fixed-shape device array
        ``[max_blocks * pages_per_block, page_bytes]`` uint8 — the
        paged-attention kernel's K/V substrate.  Row assignment is
        stable per leased block (see module docstring), unleased rows
        read as zeros, so the shape (and thus the kernel's compilation)
        never changes however blocks churn.  Every row of the stack is
        COMMITTED to the pool's device (the zero row, a block's initial
        buffer and every spliced buffer alike), so one stacking program
        serves every pattern of leased and unleased rows: after the
        first call, no lease, splice or release makes it compile
        again."""
        import jax.numpy as jnp
        nbytes = self.pages_per_block * self.page_bytes
        with self._mu:
            if self._zero_row is None:
                import jax
                # committed, like every spliced block buffer: a stack
                # that mixes committed and uncommitted rows is another
                # program for every pattern of leased rows
                self._zero_row = jax.device_put(
                    np.zeros((nbytes,), np.uint8), self.pool.device)
            by_row = {row: self._blocks[key][0]
                      for key, row in self._row_of.items()}
            # snapshot the slot buffers under the pool lock (Block.view
            # would retake it per row)
            with self.pool._lock:
                bufs = []
                for row in range(self.max_blocks):
                    blk = by_row.get(row)
                    if blk is None:
                        bufs.append(self._zero_row)
                    else:
                        buf = self.pool._slots[blk.size_class][blk.slot]
                        bufs.append(buf[:nbytes] if buf.shape[0] != nbytes
                                    else buf)
        return jnp.stack(bufs).reshape(
            self.max_blocks * self.pages_per_block, self.page_bytes)

    def read(self, page: KVPage, count: Optional[int] = None) -> np.ndarray:
        """Token ids stored in `page` (host read — test/debug path, the
        decode data path never calls this)."""
        if count is None:
            count = self.page_tokens
        from brpc_tpu.ici.block_pool import host_read_count
        host_read_count.add(1)
        raw = np.asarray(page.block.view())[
            self._offset(page):self._offset(page, count)]
        return raw.reshape(count, self.kv_bytes_per_token)[:, :4] \
            .copy().view("<i4").ravel()

    def page_slice(self, page: KVPage):
        """This page's raw bytes as a DEVICE array (uint8, page_bytes
        long) — the migration export path's zero-copy payload: sliced
        out of the block buffer on device, it rides the DCN transfer
        fabric without a host bounce."""
        from brpc_tpu.ici.block_pool import _slice_bytes
        return _slice_bytes(page.block.view(), self._offset(page),
                            self.page_bytes)

    def read_raw(self, page: KVPage) -> np.ndarray:
        """Host copy of the page's raw bytes (the migration FALLBACK
        payload when no transfer fabric exists, and the test oracle for
        splice round-trips)."""
        from brpc_tpu.ici.block_pool import host_read_count
        host_read_count.add(1)
        return np.asarray(self.page_slice(page)).copy()

    def write_raw(self, page: KVPage, data) -> None:
        """Splice a full page of raw bytes into `page` — the import
        half of page migration: whatever KV layout the source page
        held (token-id stand-ins today, real K/V vectors under a
        pallas kernel) lands bit-exact without this module
        interpreting it."""
        arr = np.asarray(data, np.uint8).ravel()
        if arr.shape[0] != self.page_bytes:
            raise ValueError(f"raw page payload is {arr.shape[0]}B, "
                             f"page_bytes={self.page_bytes}")
        self._splice(page.block, arr, self._offset(page))

    def copy_page(self, dst: KVPage, src: KVPage) -> None:
        """Device-to-device page copy — the copy half of copy-on-write.
        Slices the source page out of its block buffer and splices it
        into the destination's, entirely on device."""
        from brpc_tpu.ici.block_pool import _slice_bytes
        piece = _slice_bytes(src.block.view(), self._offset(src),
                             self.page_bytes)
        self._splice(dst.block, piece, self._offset(dst))

    def _splice(self, block, piece, off: int) -> None:
        """dynamic_update_slice `piece` into `block`'s buffer at byte
        `off` and swap the slot atomically under the block pool's lock
        (the same replace-wholesale discipline put()/install() use, so
        concurrent splices to different blocks never interfere).  The
        whole read-modify-write holds this pool's ``_io_mu`` — without
        it, concurrent splices into sibling pages of one block would
        silently drop one write."""
        import jax
        if not isinstance(piece, jax.Array):
            piece = jax.device_put(np.ascontiguousarray(piece),
                                   self.pool.device)
        with self._io_mu:
            self._splice_locked(block, piece, off)

    def _splice_locked(self, block, piece, off: int) -> None:
        """One read-modify-write splice; caller holds ``_io_mu``."""
        from brpc_tpu.ici.block_pool import _splice_bytes
        with self.pool._lock:
            buf = self.pool._slots[block.size_class][block.slot]
        out = _splice_bytes(buf, piece, off)
        with self.pool._lock:
            self.pool._slots[block.size_class][block.slot] = out

    # ---- introspection / invariants ----

    def pages_in_use(self) -> int:
        with self._mu:
            return sum(1 for _, pages in self._blocks.values()
                       for p in pages if p.refs > 0)

    def blocks_leased(self) -> int:
        with self._mu:
            return len(self._blocks)

    def assert_consistent(self) -> None:
        """Invariant check for tests/chaos: free-listed pages have no
        refs, every page belongs to a live block entry, and no block is
        simultaneously released and referenced."""
        with self._mu:
            for p in self._free:
                assert p.refs == 0, f"free page with refs: {p}"
                assert self._bkey(p.block) in self._blocks, \
                    f"free page of released block: {p}"
            free_ids = {p.pid for p in self._free}
            for block, pages in self._blocks.values():
                for p in pages:
                    assert p.refs >= 0, p
                    if p.refs == 0:
                        assert p.pid in free_ids, \
                            f"idle page missing from free list: {p}"

    def stats(self) -> dict:
        with self._mu:
            total = len(self._blocks) * self.pages_per_block
            in_use = sum(1 for _, pages in self._blocks.values()
                         for p in pages if p.refs > 0)
            return {
                "page_bytes": self.page_bytes,
                "page_tokens": self.page_tokens,
                "pages_per_block": self.pages_per_block,
                "blocks_leased": len(self._blocks),
                "max_blocks": self.max_blocks,
                "pages_total": total,
                "pages_in_use": in_use,
                "pages_free": total - in_use,
                "page_allocs": self.page_allocs.get_value(),
                "page_frees": self.page_frees.get_value(),
                "batch_splices": self.batch_splices.get_value(),
                "block_leases": self.block_leases.get_value(),
                "block_releases": self.block_releases.get_value(),
            }
