"""TensorStream — StreamWrite as a zero-copy HBM→HBM tensor pipe.

The credit loop of rpc/stream.py (§5.7) applied to device arrays: writer
pushes tensors, each rides an async ICI transfer (IciEndpoint), consumer
callbacks run in submission order, the window bounds HBM held by in-flight
chunks.  Double buffering falls out of the async dispatch: chunk N+1's
transfer starts while N's consumer runs.
"""
from __future__ import annotations

import queue
import threading
from typing import Callable, Optional

import jax

from brpc_tpu.ici.endpoint import IciEndpoint, _collect_batch


class TensorStream:
    def __init__(self, device,
                 consumer: Optional[Callable[[jax.Array], None]] = None,
                 window_bytes: int = 64 * 1024 * 1024):
        self.endpoint = IciEndpoint(device, window_bytes)
        self._consumer = consumer
        self._write_mu = threading.Lock()
        self._q: "queue.Queue" = queue.Queue()
        self._error: Exception | None = None
        self._closed = threading.Event()
        self._drained = threading.Event()
        self._drainer = threading.Thread(target=self._drain, daemon=True,
                                         name=f"tensor-stream-{device.id}")
        self._drainer.start()

    def write(self, array: jax.Array) -> None:
        """Queue one tensor; transfer starts immediately (async), order is
        preserved for the consumer."""
        if self._closed.is_set():
            raise RuntimeError("stream closed")
        with self._write_mu:
            # dispatch + enqueue atomically so _q mirrors dispatch order:
            # the consumer is fed from it, and the drainer's batch
            # tail-sync depends on it.  This is the stream's own lock
            # (one pipe, one order); the endpoint under it takes none
            # across a dispatch and infers nothing from order
            out = self.endpoint.send(array)
            self._q.put(("tensor", out, 0, None))

    def write_many(self, arrays) -> list:
        """Queue a batch of tensors with ONE dispatch (endpoint.send_batch)
        — the amortized fast path for uniform chunk streams; consumer
        ordering is unchanged.  Returns the destination handles so callers
        can observe transfer completion directly (block_until_ready on the
        last handle) without waiting for consumer delivery."""
        if self._closed.is_set():
            raise RuntimeError("stream closed")
        if not arrays:
            return []
        with self._write_mu:
            outs = self.endpoint.send_batch(arrays)
            for out in outs:
                self._q.put(("tensor", out, 0, None))
        return outs

    def write_bytes(self, data, src_pool=None) -> None:
        """Stream a byte payload staged through BlockPool slots on the
        source side (HBM-born, like the reference's pool-allocated IOBuf
        blocks — block_pool.cpp:52); the consumer receives destination-pool
        Blocks in order.  Chunking follows the pool's largest class."""
        if self._closed.is_set():
            raise RuntimeError("stream closed")
        from brpc_tpu.ici.block_pool import get_block_pool, stage_chunks
        src_pool = src_pool or get_block_pool()
        for blk in stage_chunks(data, src_pool):
            with self._write_mu:
                out = self.endpoint.send(blk.view())
                self._q.put(("block", out, blk.used,
                             getattr(blk, "_src_meta", None)))
            # the dispatched transfer holds its own reference to the staged
            # buffer; the slot can go back to the free list immediately
            blk.free()

    def _drain(self) -> None:
        try:
            while True:
                try:
                    item = self._q.get(timeout=0.1)
                except queue.Empty:
                    if self._closed.is_set():
                        break
                    continue
                if item is None:
                    break
                # batch: sync the newest queued chunk once (one device
                # executes d2d copies in dispatch order, so the tail being
                # ready implies the earlier ones are) and feed the
                # consumer in order — N host syncs become 1
                batch, stop = _collect_batch(self._q, item)
                try:
                    batch[-1][1].block_until_ready()   # ordered completion
                except Exception:
                    # one failed transfer must not kill the drainer or
                    # swallow delivery of the batch's completed chunks
                    import traceback
                    traceback.print_exc()
                if self._consumer is not None:
                    for kind, arr, used, meta in batch:
                        # pipe-side work (dst-pool alloc/install) is NOT
                        # covered by the consumer-bug guard: its failure
                        # means data loss and must surface via close()
                        if kind == "block":
                            try:
                                from brpc_tpu.ici.block_pool import \
                                    get_block_pool
                                item = get_block_pool(
                                    self.endpoint.device).alloc(arr.nbytes)
                                item.install(arr, used, meta=meta)
                            except Exception as e:
                                import traceback
                                traceback.print_exc()
                                if self._error is None:
                                    self._error = e
                                continue
                        else:
                            item = arr
                        try:
                            self._consumer(item)
                        except Exception:  # consumer bug must not kill pipe
                            import traceback
                            traceback.print_exc()
                if stop:
                    break
        finally:
            self._drained.set()

    def close(self, wait: bool = True) -> None:
        if not self._closed.is_set():
            self._closed.set()
            self._q.put(None)
        if wait:
            self._drained.wait(30)
        self.endpoint.close()
        if self._error is not None:
            raise RuntimeError(
                "stream dropped data on the pipe side") from self._error
