"""Per-layer-kind cache state beside the pages (ISSUE 32).

A model whose layers are not all softmax attention keeps more than one
kind of state, and ``page_bytes = all layers' K/V of a token`` no longer
describes its cache.  :class:`LayeredCache` holds what the
:class:`~brpc_tpu.kvcache.store.KVCacheStore`'s page ids, refcounts and
radix tree MANAGE, each kind in ONE persistent device array that the
runner's donated programs update in place (no per-call restack):

  ``kv``     ``[K/V layers, 2, Hkv, P, T, D]`` bfloat16: K and V of
             the attention layers only (learned-sparse and full
             attention alike), page ``p`` = flat arena index ``p`` of
             the store's :class:`PagePool` (the pool's block buffers
             shrink to a token-id stand-in of 4 bytes a token)
  ``kc``     ``[K/V layers, P, 4, Hkv, D]`` bfloat16: the compressed
             keys, an index beside the pages (a kernel is written when
             its last key is, into that key's page); no element where
             no layer selects blocks (``compressed`` false)
  ``state``  ``[rows + 2, recurrent layers, ...]`` float32: one row a
             live sequence and one a SNAPSHOT, holding whatever the
             model's recurrent layers keep (``state_layer_shape``): a
             lightning layer ``[H, D, D]``; a Mamba layer (ISSUE 38)
             ``[ssm_rows, channels]``, channels minor in whole 128-lane
             tiles: rows ``0 .. N-1`` the scan state, rows ``N .. N+2``
             the causal convolution's tail (its last three inputs,
             oldest first), the rest of the last 8-row tile zero
             (``ops.mamba``); a Mamba-2 layer (ISSUE 40) the same two
             numbers with another meaning, ``[8448, 128]`` at the
             published widths, ONE lane tile wide: rows ``0 .. 8191``
             the matrix state of 128 heads packed two heads a tile of
             ``N`` rows (row ``n``, lane ``(h % 2) 64 + p`` of tile
             ``h // 2`` is ``S^h[p, n]``), then the tail's three inputs
             of 10,240 channels as 80 rows each, padded to a block of
             256 rows that divides the state's (``ops.ssd`` owns the
             layout).  Whatever the kind, BOTH kinds of recurrent state
             a sequence lie in the one row, and so do its window
             layers' ring pages: what a ROW owns is its block of
             ``state`` and its ``R`` pages of ``ring``, so that restore,
             snapshot, scratch and zero row, the free list and a radix
             node's ownership are one mechanism over every array a row
             owns.  The last
             two rows are a scratch row (idle decode slots read and
             write it) and a row that stays zero (a cold sequence
             starts from it)
  ``latent`` ``[latent layers, P, T, C]`` bfloat16 (ISSUE 34): ONE row
             a token a layer of a latent-attention model, ``[c_kv;
             k_rope]`` and nothing per head; ``C`` is the row's width
             rounded up to whole 128-lane tiles (the chip's compiler
             gives an array whose minor dimension is not whole tiles
             another layout than the kernels read, and copies it whole
             around every call), the lanes past the row stay zero
  ``ring``   ``[window layers, 2, Hkv, (rows + 2) R, T, D]`` bfloat16:
             K and V of the sliding-window layers, a RING of ``R`` pages
             a state row (``ring_pages``): logical block ``b`` of the
             sequence in row ``r`` lies at ring page ``r R + b mod R``.
             ``R`` is the fewest pages that hold a window (the pages a
             decode query's window can span, less one) beside every
             page a prefill chunk writes before its queries read, so a
             window layer keeps a fixed few pages a sequence however
             long it grows, and its keys never take a page of ``kv``
             (whose first dimension is the full layers only, as
             ``page_bytes`` counts them).  The ring's layout is the K/V
             arena's, so ``sparse_attend`` and ``cache_write`` read and
             write it as they do ``kv``

A kind the model has no layer of is an array with no element and costs
nothing: no state row is allocated, restored or snapshot where
``n_linear`` and ``n_window`` are 0, and a hit is then whatever whole
pages the radix tree matches.  A live sequence's row comes before any snapshot's: an
admission that finds no free row has the store evict cached prefixes
until one is (``KVCacheStore._install_state``), and a snapshot that
finds none is not taken (``state_snapshot_no_row`` counts them).

A snapshot is the recurrent state (and the window layers' ring) after
exactly a whole number of pages: a restored ring is the one a cold
prefill of the same prefix leaves.  The radix node that ends that prefix owns it
(``_Node.snapshot``); a hit restores it into the sequence's row instead
of re-reading the prefix, and evicting the node frees the row.
"""
from __future__ import annotations

import functools
import threading
from dataclasses import dataclass

import numpy as np

from brpc_tpu import rpcz
from brpc_tpu.bvar import Adder


@dataclass(frozen=True)
class LayeredSpec:
    n_sparse: int            # attention layers that keep K/V pages
    n_kv_heads: int
    head_dim: int
    n_linear: int            # layers that keep a recurrent state
    state_rows: int          # live sequences + snapshots
    # what ONE recurrent layer keeps of a sequence (its kind's record in
    # ``models.layers``: a lightning layer's [H, D, D], a Mamba layer's
    # [rows, channels], Mamba-2's ops.ssd's packed rows, 128 wide)
    state_layer_shape: tuple = (0, 0, 0)
    n_latent: int = 0        # layers that keep one latent row a token
    latent_dim: int = 0      # its width: kv_lora_rank + the rope key's
    compressed: bool = True  # the K/V layers select blocks (keep ``kc``)
    n_window: int = 0        # sliding-window layers (their K/V in ``ring``)
    ring_pages: int = 0      # R: ring pages a state row holds of each

    @property
    def has_state(self) -> bool:
        """Whether a sequence owns a state row (recurrent state, or the
        window layers' ring)."""
        return self.n_linear > 0 or self.n_window > 0

    @property
    def latent_lanes(self) -> int:
        """The latent array's minor dimension: whole 128-lane tiles."""
        return -(-self.latent_dim // 128) * 128

    def state_row_bytes(self) -> int:
        return self.n_linear * int(np.prod(self.state_layer_shape)) * 4


@functools.cache
def _copy_row():
    import jax

    def kvcache_state_copy(state, dst, src):
        return state.at[dst].set(state[src])
    return jax.jit(kvcache_state_copy, donate_argnums=0)


@functools.cache
def _copy_ring():
    import jax

    def kvcache_ring_copy(ring, dst, src, *, pages):
        return jax.lax.dynamic_update_slice_in_dim(
            ring, jax.lax.dynamic_slice_in_dim(ring, src * pages, pages, 3),
            dst * pages, 3)
    return jax.jit(kvcache_ring_copy, donate_argnums=0,
                   static_argnames="pages")


@functools.cache
def _copy_page():
    import jax

    def kvcache_page_copy(kv, kc, latent, dst, src):
        return (kv.at[:, :, :, dst].set(kv[:, :, :, src]),
                kc.at[:, dst].set(kc[:, src]),
                latent.at[:, dst].set(latent[:, src]))
    return jax.jit(kvcache_page_copy, donate_argnums=(0, 1, 2))


class LayeredCache:
    """The device arrays and the state rows' free list (see module
    docstring).  The arrays are swapped by whoever ran a donated
    program over them (the runner on the engine thread, the copies
    here): ``lock`` serialises the swaps."""

    def __init__(self, spec: LayeredSpec, pages: int, page_tokens: int,
                 device=None, name: str = "kv"):
        import jax
        import jax.numpy as jnp
        if page_tokens % 4:
            raise ValueError("page_tokens must be a multiple of 4 "
                             "(four compression kernels start in a page)")
        self.spec = spec
        self.pages = int(pages)
        self.page_tokens = int(page_tokens)
        self.device = device or jax.devices()[0]
        from brpc_tpu.butil.lockprof import InstrumentedLock
        self.lock = InstrumentedLock("kvcache.layers", threading.RLock())
        s = spec

        def zeros(shape, dtype):
            # committed to the device (see PagePool.arena)
            return jax.device_put(np.zeros(shape, dtype), self.device)
        bf16 = jnp.bfloat16
        self.kv = zeros((s.n_sparse, 2, s.n_kv_heads, self.pages,
                         self.page_tokens, s.head_dim), bf16)
        self.kc = zeros((s.n_sparse if s.compressed else 0, self.pages, 4,
                         s.n_kv_heads, s.head_dim), bf16)
        self.latent = zeros((s.n_latent, self.pages, self.page_tokens,
                             s.latent_lanes), bf16)
        self.scratch_row = s.state_rows
        self.zero_row = s.state_rows + 1
        self.state = zeros((s.state_rows + 2, s.n_linear)
                           + s.state_layer_shape, np.float32)
        self.ring = zeros((s.n_window, 2, s.n_kv_heads,
                           (s.state_rows + 2) * s.ring_pages,
                           self.page_tokens, s.head_dim), bf16)
        # what a row owns: the layers and the bytes a restore or a
        # snapshot copies
        self._row_layers = s.n_linear + s.n_window
        self.row_bytes = s.state_row_bytes() + (
            self.ring.nbytes // (s.state_rows + 2) if s.n_window else 0)
        self._free_rows = list(range(s.state_rows))[::-1]
        safe = "".join(c if c.isalnum() else "_" for c in name)
        self.bvar_names = [f"kvcache_{safe}_state_{what}" for what in
                           ("snapshots", "restores", "restore_misses",
                            "snapshot_no_row")]
        # snapshot_no_row: snapshots refused for want of a free row (the
        # prefix is then not cached)
        (self.snapshots, self.restores, self.restore_misses,
         self.snapshot_no_row) = (Adder(n) for n in self.bvar_names)

    # ---- state rows ----

    def alloc_row(self) -> int:
        with self.lock:
            if not self._free_rows:
                raise MemoryError(
                    f"no free state row ({self.spec.state_rows} rows)")
            return self._free_rows.pop()

    def free_row(self, row) -> None:
        if row is None:
            return
        with self.lock:
            self._free_rows.append(int(row))

    def rows_free(self) -> int:
        with self.lock:
            return len(self._free_rows)

    def _copy(self, dst: int, src: int) -> None:
        """Row ``src`` -> row ``dst`` in every array a row owns."""
        with self.lock:
            self.state = _copy_row()(self.state, np.int32(dst),
                                     np.int32(src))
            if self.spec.n_window:
                self.ring = _copy_ring()(self.ring, np.int32(dst),
                                         np.int32(src),
                                         pages=self.spec.ring_pages)

    def reset_row(self, row: int) -> None:
        self._copy(row, self.zero_row)

    def restore(self, row: int, snapshot: int) -> None:
        """Snapshot -> a sequence's row: the radix hit's other half."""
        with rpcz.stage("kvcache.state.restore", layers=self._row_layers,
                        bytes=self.row_bytes):
            self._copy(row, snapshot)
        self.restores.add(1)

    def snapshot(self, row: int) -> int:
        """A sequence's row -> a fresh snapshot row; MemoryError where
        none is free."""
        snap = self.alloc_row()
        with rpcz.stage("kvcache.state.snapshot", layers=self._row_layers,
                        bytes=self.row_bytes):
            self._copy(snap, row)
        self.snapshots.add(1)
        return snap

    def copy_page(self, dst_flat: int, src_flat: int) -> None:
        """K/V, compressed keys and latent rows of one page, device to
        device (the copy half of copy-on-write)."""
        with self.lock:
            self.kv, self.kc, self.latent = _copy_page()(
                self.kv, self.kc, self.latent, np.int32(dst_flat),
                np.int32(src_flat))

    def nbytes(self) -> int:
        return int(self.kv.nbytes + self.kc.nbytes + self.state.nbytes
                   + self.latent.nbytes + self.ring.nbytes)

    def stats(self) -> dict:
        return {"pages": self.pages, "state_rows": self.spec.state_rows,
                "state_rows_free": self.rows_free(),
                "bytes": self.nbytes(),
                "snapshots": self.snapshots.get_value(),
                "restores": self.restores.get_value(),
                "restore_misses": self.restore_misses.get_value(),
                "snapshot_no_row": self.snapshot_no_row.get_value()}

    def close(self) -> None:
        from brpc_tpu.bvar.variable import find_exposed
        for n in self.bvar_names:
            v = find_exposed(n)
            if v is not None:
                v.hide()
        self.kv = self.kc = self.state = self.latent = self.ring = None
