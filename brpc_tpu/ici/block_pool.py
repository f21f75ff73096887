"""HBM BlockPool — the device-memory analog of rdma::BlockPool.

Reference (rdma/block_pool.cpp:52,69-70): large pinned regions registered
with the NIC, slab-allocated into 8KB/64KB/2MB blocks, wired in as IOBuf's
block allocator so payloads are *born registered* — zero copy end-to-end.

TPU build: the pool owns per-device jax buffers in the same size classes.
A block is a view (offset, length) into a device arena; tensors serialized
into blocks live in HBM and move chip-to-chip without host round-trips.
XLA owns physical allocation (there is no cudaMalloc-style API), so the
arena is a set of device arrays kept alive by the pool; blocks are views
with a free-list, and donation happens naturally when a transfer consumes
the arena slice.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from brpc_tpu import fault
from brpc_tpu.bvar import Adder, PassiveStatus

# Host-bounce counters for the rail's zero-host-copy proof
# (ici/rail.py host_copy_count): staging host bytes into a block and
# reading a block back to host are the only block-pool paths that touch
# host memory.
host_stage_count = Adder("blockpool_host_stages")
host_read_count = Adder("blockpool_host_reads")


# Each jitted function below carries a name of its own: a trace names a
# device program after the Python function (``jit_blockpool_stage``), and
# ``jit__stage`` would say nothing of whose it is.  The module-level
# names other modules import (``_stage``, ``_slice_bytes``...) stay.

def blockpool_stage(x, cls: int):
    """Reinterpret a tensor's bytes as uint8 and pad into a block-class
    buffer — entirely on device (no host bounce).  Runs on the source
    array's device; the output is always a fresh buffer."""
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    flat = x.ravel()
    if flat.dtype != jnp.uint8:
        flat = jax.lax.bitcast_convert_type(flat, jnp.uint8).ravel()
    out = jnp.zeros((cls,), jnp.uint8)
    return jax.lax.dynamic_update_slice(out, flat, (0,))


_stage = jax.jit(blockpool_stage, static_argnums=(1,))


def blockpool_unstage(buf, dtype_name: str, shape: tuple):
    """Rebuild a tensor from a block's byte buffer, on device."""
    dt = np.dtype(dtype_name)
    n = int(np.prod(shape, dtype=np.int64)) if shape else 1
    nbytes = n * (1 if dt == np.bool_ else dt.itemsize)
    raw = jax.lax.dynamic_slice(buf, (0,), (nbytes,))
    if dt == np.bool_:
        return raw.reshape(shape).astype(jnp.bool_)
    if dt.itemsize == 1:
        return jax.lax.bitcast_convert_type(raw, dt).reshape(shape)
    return jax.lax.bitcast_convert_type(
        raw.reshape(n, dt.itemsize), dt).reshape(shape)


_unstage = jax.jit(blockpool_unstage, static_argnums=(1, 2))


def blockpool_slice_bytes(buf, off, nbytes: int):
    """Read nbytes out of a block buffer at a dynamic byte offset, on
    device (the page-granularity read half of splice)."""
    return jax.lax.dynamic_slice(buf, (off,), (nbytes,))


_slice_bytes = jax.jit(blockpool_slice_bytes, static_argnums=(2,))


def blockpool_splice_bytes(buf, piece, off):
    """Write `piece` into a block buffer at a dynamic byte offset, on
    device — the rest of the buffer is untouched, so several sub-block
    regions (KV pages) can share one block without clobbering each
    other the way a wholesale put() would."""
    return jax.lax.dynamic_update_slice(buf, piece, (off,))


_splice_bytes = jax.jit(blockpool_splice_bytes)


# size classes, mirroring the reference's 8KB/64KB/2MB (block_pool.cpp:52)
BLOCK_CLASSES = (8 * 1024, 64 * 1024, 2 * 1024 * 1024)
_ARENA_BLOCKS_PER_CLASS = 64


@dataclass
class Block:
    """A view into a device arena: arena array index + slot."""
    pool: "BlockPool"
    size_class: int
    slot: int
    used: int = 0

    @property
    def nbytes(self) -> int:
        return self.size_class

    def view(self):
        """The device buffer of this slot (uint8[size_class])."""
        with self.pool._lock:
            return self.pool._slots[self.size_class][self.slot]

    def put(self, data) -> "Block":
        """Stage host/device bytes into this block's slot.  Device-resident
        sources are reinterpreted and padded entirely on device (`_stage`
        under jit — no host round-trip), then DMA'd to the pool's device if
        they live elsewhere; host bytes pad host-side and ship in a single
        device_put.  The slot buffer is replaced atomically under the pool
        lock — concurrent puts to different slots never interfere."""
        if isinstance(data, jax.Array):
            n = data.nbytes
            if n > self.size_class:
                raise ValueError(f"{n}B > block class {self.size_class}")
            dev = _stage(data, self.size_class)   # on the source device
            if dev.devices() != {self.pool.device}:
                dev = jax.device_put(dev, self.pool.device)
            self._src_meta = (str(data.dtype), tuple(data.shape))
        else:
            host_stage_count.add(1)
            buf = np.frombuffer(memoryview(data), dtype=np.uint8)
            n = buf.size
            if n > self.size_class:
                raise ValueError(f"{n}B > block class {self.size_class}")
            padded = np.zeros((self.size_class,), np.uint8)
            padded[:n] = buf
            dev = jax.device_put(padded, self.pool.device)
            self._src_meta = None
        self.used = n
        with self.pool._lock:
            self.pool._slots[self.size_class][self.slot] = dev
        return self

    def install(self, dev_array: jax.Array, used: int,
                meta: tuple | None = None) -> "Block":
        """Adopt an already-transferred device buffer as this block's
        contents — the receive half of the block pipe (no staging, no
        copy).  The buffer need not match the slot's class exactly (alloc
        falls through to a larger class when the preferred one is
        exhausted); it only has to cover the payload."""
        if used > dev_array.nbytes:
            raise ValueError(
                f"payload {used}B exceeds buffer {dev_array.nbytes}B")
        self.used = used
        self._src_meta = meta
        with self.pool._lock:
            self.pool._slots[self.size_class][self.slot] = dev_array
        return self

    def get(self) -> bytes:
        host_read_count.add(1)
        return bytes(np.asarray(self.view())[: self.used])

    def get_array(self, dtype=None, shape=None) -> jax.Array:
        """Rebuild the staged tensor on device.  dtype/shape default to the
        source tensor's (recorded by put)."""
        if dtype is None or shape is None:
            if getattr(self, "_src_meta", None) is None:
                raise ValueError("no recorded dtype/shape; pass them")
            dtype, shape = self._src_meta
        return _unstage(self.view(), str(np.dtype(dtype)), tuple(shape))

    def free(self) -> None:
        self.pool.free(self)


class BlockPool:
    """Per-device slab pool of HBM blocks.  ``classes`` x
    ``blocks_per_class`` is the pool's geometry: the per-device rail
    pools (:func:`get_block_pool`) use the reference's three classes;
    a KV cache whose page outgrows them brings a pool cut to its page
    (``models.runner.make_store_for``)."""

    def __init__(self, device=None, *, classes=BLOCK_CLASSES,
                 blocks_per_class: int = _ARENA_BLOCKS_PER_CLASS):
        from brpc_tpu.ici.mesh import ensure_compile_cache
        ensure_compile_cache()
        self.device = device or jax.devices()[0]
        self.classes = tuple(sorted(classes))
        self.blocks_per_class = int(blocks_per_class)
        self._lock = threading.Lock()
        # one device buffer per slot: replaced wholesale on put() so slots
        # are independent (XLA owns the physical pages; keeping per-slot
        # arrays alive is what pins the "arena")
        self._slots: dict[int, list] = {}
        self._free: dict[int, list[int]] = {}
        self._allocated = Adder()
        self._freed = Adder()
        for cls in self.classes:
            # committed to the device, as every buffer a put() or a
            # splice installs is: programs over slot buffers then see
            # one placement whether a slot was written yet or not
            zero = jax.device_put(np.zeros((cls,), np.uint8), self.device)
            self._slots[cls] = [zero] * self.blocks_per_class
            self._free[cls] = list(range(self.blocks_per_class))

    def alloc(self, nbytes: int) -> Block:
        """Smallest class that fits (AllocBlock, block_pool.h:76-88)."""
        if fault.ENABLED and fault.hit(
                "ici.alloc", device=self.device.id,
                nbytes=nbytes) is not None:
            # injected arena exhaustion: same shape as every class being
            # out of slots, so callers walk their real fallback paths
            raise MemoryError(
                f"injected HBM block exhaustion ({nbytes}B)")
        for cls in self.classes:
            if nbytes <= cls:
                with self._lock:
                    if self._free[cls]:
                        slot = self._free[cls].pop()
                        self._allocated.add(1)
                        return Block(self, cls, slot)
        raise MemoryError(
            f"no free HBM block for {nbytes}B "
            f"(classes {self.classes}, {self.blocks_per_class}/class)")

    def free(self, block: Block) -> None:
        with self._lock:
            self._free[block.size_class].append(block.slot)
            self._freed.add(1)

    def stats(self) -> dict:
        with self._lock:
            return {
                "device": str(self.device),
                "classes": {str(cls): {
                    "free": len(self._free[cls]),
                    "total": self.blocks_per_class,
                } for cls in self.classes},
                "allocated": self._allocated.get_value(),
                "freed": self._freed.get_value(),
            }


def stage_chunks(data, src_pool: "BlockPool"):
    """Yield `data` staged into src_pool Blocks in order, chunked by the
    largest block class.  The single staging path shared by
    IciEndpoint.send_bytes and TensorStream.write_bytes; caller frees each
    block once its transfer is dispatched."""
    view = memoryview(data)
    chunk = BLOCK_CLASSES[-1]
    for off in range(0, len(view), chunk):
        piece = view[off:off + chunk]
        blk = src_pool.alloc(len(piece))
        try:
            blk.put(piece)
        except BaseException:
            # a failed put must not leak the freshly-allocated block
            # (error-path discipline: the block is only the consumer's
            # once it has been yielded)
            blk.free()
            raise
        yield blk


_pools: dict[int, BlockPool] = {}
_pools_lock = threading.Lock()


def get_block_pool(device=None) -> BlockPool:
    device = device or jax.devices()[0]
    with _pools_lock:
        p = _pools.get(device.id)
        if p is None:
            p = BlockPool(device)
            _pools[device.id] = p
        return p
