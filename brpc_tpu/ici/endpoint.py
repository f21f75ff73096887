"""IciEndpoint — chip-to-chip transfer in RdmaEndpoint's socket slot.

Reference (rdma_endpoint.h; SURVEY.md §5.8): after a TCP-assisted handshake
the endpoint moves data on an RC queue pair with a credit window =
min(local SQ, remote RQ), completions surfacing through the dispatcher.

TPU build: the "queue pair" is XLA's device-to-device transfer engine —
`jax.device_put(x, device)` lowers to an ICI copy on hardware (no host
bounce), and dispatch is async, so starting a transfer and touching the
result later gives the same start/wait split as ibverbs post-send/poll-cq.
The credit window survives unchanged: in-flight bytes are bounded, and
"completion events" are jax futures observed via block_until_ready in a
drainer thread that feeds the same bvar counters the socket path uses.
No handshake is needed inside one process/slice; cross-host setup arrives
with the DCN path in a later round.

Senders do not wait on each other.  A send reserves credit under `_mu` (a
critical section of three statements, with no call in it), then calls into
the runtime with NO lock held, then hands a completion entry to the drainer
through a `SimpleQueue`.  The jit call and `device_put` release the
interpreter lock while the runtime works; a mutex held across them turned
ten busy threads into a convoy (8 ms of wall around 0.6 ms of CPU per
send, PERF.md PR 25/26).  The price is that the completion queue is in no
particular order, so nothing is inferred from order: every entry is
confirmed by itself, and credit goes back only for transfers observed
complete.  Confirming means `is_ready()` first: it does not give up the
interpreter lock, where `block_until_ready()` does even on a ready array
and then waits its turn for it behind every busy thread (9.8 ms a call
against 0.3 us on the v5e's host with four threads spinning, PERF.md PR
26).  And every sender confirms what is ready itself before it reserves:
the drainer, one more thread in the queue for the interpreter lock, gives
credit back milliseconds after the transfer is done, which at 17 GB/s of
credit flow filled the 256 MB window with copies long since complete.
The drainer stays for what no later send would see: it brings
`inflight_bytes` to 0 after the last send, and it parks on transfers
that are really in flight.
"""
from __future__ import annotations

import queue as queue_mod
import threading
import time
from typing import Optional

import jax

from brpc_tpu import fault, rpcz
from brpc_tpu.bvar import Adder, LatencyRecorder

_send_bytes = Adder("ici_send_bytes")
_send_count = Adder("ici_send_count")
_recv_bytes = Adder("ici_recv_bytes")
_same_device_copies = Adder("ici_same_device_copies")
_cross_device_moves = Adder("ici_cross_device_moves")
_transfer_latency = LatencyRecorder("ici_transfer")
# sends that entered dispatch while another send of the same endpoint was
# inside it: above 0 means senders really overlap in the runtime
_send_overlapped = Adder("ici_send_overlapped")

DEFAULT_WINDOW_BYTES = 64 * 1024 * 1024

# Compiled HBM->HBM copy for same-device "transfers".  jax forwards
# unmodified jit outputs to their input buffers, and device_put to the
# array's own device is a no-op alias — so a loopback send must go through
# an explicit copy primitive to actually exercise the memory system and
# yield a distinct destination buffer (the single-chip analog of
# RdmaEndpoint moving bytes through the NIC even on loopback).
# jnp.copy lowers to the copy HLO, which XLA may not alias without
# donation; tests assert unsafe_buffer_pointer() inequality.
import jax.numpy as _jnp

# The programs carry names of their own (``jit_rail_copy``,
# ``jit_rail_multi_copy``): a trace names a device program after the
# jitted Python function, and ``jit_copy`` says nothing of who ran it.


def rail_copy(x):
    return _jnp.copy(x)


def rail_multi_copy(*xs):
    return tuple(_jnp.copy(x) for x in xs)


_device_copy = jax.jit(rail_copy)

# Pre-compiled MULTI-chunk copy: one XLA program holding k copy HLOs, so a
# k-chunk batch costs ONE Python->PJRT dispatch instead of k (VERDICT r2
# task 2 — per-chunk dispatch was the pipe's bottleneck: ~ms of host work
# per chunk vs ~0.2ms of HBM time for a 64MB copy).  jit specializes and
# caches per (arity, shapes, dtypes), so this single definition is the
# whole "transfer program" cache.  No donation here: donating would let
# XLA alias outputs onto inputs and the copies must provably move bytes.
_multi_copy = jax.jit(rail_multi_copy)


def _collect_batch(q, first):
    """Drain everything already sitting in `q` behind `first` without
    blocking.  Returns (batch, stop) where stop means the None close
    sentinel was reached.  Shared by IciEndpoint and TensorStream so the
    two drain loops cannot diverge."""
    batch = [first]
    stop = False
    while True:
        try:
            nxt = q.get_nowait()
        except queue_mod.Empty:
            break
        if nxt is None:
            stop = True
            break
        batch.append(nxt)
    return batch, stop


class IciEndpoint:
    """Point-to-point ordered transfer pipe to one target device."""

    def __init__(self, device, window_bytes: int = DEFAULT_WINDOW_BYTES):
        self.device = device
        self.window_bytes = window_bytes
        self._mu = threading.Lock()
        self._cv = threading.Condition(self._mu)
        self._inflight = 0
        # senders parked on a full window (under _mu): nobody to wake is
        # the common case, and notify_all is Python run under the lock
        self._window_waiters = 0
        # idents of the threads between their credit reservation and the
        # end of their dispatch: set.add / discard / len are atomic, so
        # the overlap counter costs the send path no second lock
        self._dispatching: set = set()
        self._closed = False
        # single long-lived completion drainer (the "poll-cq" thread);
        # started lazily on the first send.  SimpleQueue: the sender's
        # put takes no Python-level mutex or condition
        self._completions = queue_mod.SimpleQueue()
        self._drainer: Optional[threading.Thread] = None

    def _ensure_drainer(self) -> None:
        if self._drainer is None:
            with self._mu:
                if self._drainer is None:
                    self._drainer = threading.Thread(
                        target=self._drain_completions, daemon=True,
                        name=f"ici-cq-{self.device.id}")
                    self._drainer.start()

    @staticmethod
    def _confirm_ready(batch) -> tuple[int, list]:
        """Confirm, without blocking, the entries of `batch` whose every
        transfer is complete.  Returns their bytes and the entries still
        in flight.  Nothing is inferred from an entry's place: senders
        dispatch and enqueue with no lock between them, so queue order
        says nothing of dispatch order."""
        freed = 0
        pending = []
        for entry in batch:
            outs, nbytes, _ = entry
            try:
                ready = all(out.is_ready() for out in outs)
            except Exception:  # transfer failure: free the window anyway
                ready = True
            if ready:
                _recv_bytes.add(nbytes)
                freed += nbytes
            else:
                pending.append(entry)
        return freed, pending

    def _release_window(self, nbytes: int) -> None:
        if nbytes <= 0:
            return
        with self._mu:
            self._inflight -= nbytes
            waiters = self._window_waiters
        if waiters:
            with self._cv:
                self._cv.notify_all()

    def _drain_completions(self) -> None:
        q = self._completions
        while True:
            item = q.get()
            if item is None:
                return
            # one cycle: confirm whatever is complete already and give
            # its credit back at once, then park on what is still in
            # flight, entry by entry.  One device completes same-engine
            # copies in order, so at most the first of them really parks.
            batch, stop = _collect_batch(q, item)
            freed, pending = self._confirm_ready(batch)
            self._release_window(freed)
            for outs, nbytes, _ in pending:
                for out in outs:
                    try:
                        out.block_until_ready()
                    except Exception:  # transfer failure: free the window anyway
                        pass
                _recv_bytes.add(nbytes)
                self._release_window(nbytes)
            # one latency sample per drain cycle (the newest entry's),
            # rather than charging every chunk the full batch duration
            _transfer_latency.add(
                int((time.monotonic() - batch[-1][2]) * 1e6))
            if stop:
                return

    def _reclaim_ready(self) -> int:
        """A sender's own look at the completion queue: confirm what is
        complete, put the rest (and a close sentinel) back.  Never
        blocks.  Returns the bytes confirmed; the caller gives them back
        to the window."""
        q = self._completions
        try:
            first = q.get_nowait()
        except queue_mod.Empty:
            return 0
        if first is None:
            q.put(None)
            return 0
        batch, stop = _collect_batch(q, first)
        freed, pending = self._confirm_ready(batch)
        for entry in pending:
            q.put(entry)
        if stop:
            q.put(None)
        return freed

    def _transfer(self, array: jax.Array) -> jax.Array:
        """One async transfer to self.device that provably produces a
        distinct destination buffer.  Cross-device: device_put (a real ICI
        DMA / host copy).  Same-device loopback: compiled copy kernel —
        device_put to the source device would alias, moving zero bytes."""
        try:
            src = array.devices()
        except Exception:  # uncommitted / non-jax input
            src = set()
        if src == {self.device}:
            _same_device_copies.add(1)
            return _device_copy(array)
        _cross_device_moves.add(1)
        return jax.device_put(array, self.device)

    def _reserve_window(self, nbytes: int, timeout_s: float, stg) -> None:
        """Reserve `nbytes` of credit, blocking while the window lacks
        them, and count the caller into the dispatch — the EAGAIN
        discipline of RdmaEndpoint's SQ/window check
        (rdma_endpoint.h:235-240).  Shared by send and send_batch so the
        credit protocol has exactly one implementation; every return is
        paired with one `_end_dispatch`.  The caller's stage ``stg`` is
        told how long the wait was (0 when credit was there) and how many
        other sends were inside the dispatch when this one entered."""
        waited_us = (0 if self._try_reserve(nbytes)
                     else self._wait_for_window(nbytes, timeout_s))
        overlap = len(self._dispatching)
        self._dispatching.add(threading.get_ident())
        if overlap:
            _send_overlapped.add(1)
        if stg is not rpcz.NOOP_STAGE:
            stg.set(waited_window_us=waited_us, overlap=overlap)

    def _try_reserve(self, nbytes: int) -> bool:
        # every sender first confirms what has completed since anyone
        # last looked (a few `is_ready()` calls, no lock, no blocking):
        # under ten busy threads the drainer waits milliseconds for its
        # turn at the interpreter lock, and credit that only it gives
        # back fills the window with transfers long since done
        freed = self._reclaim_ready()
        # the bare lock around plain statements: no call, so no point at
        # which the holder can be made to give up the interpreter lock
        # with every other sender queued behind it
        with self._mu:
            self._inflight -= freed
            room = self._inflight + nbytes <= self.window_bytes
            if room:
                self._inflight += nbytes
            wake = freed and self._window_waiters
        if wake:
            with self._cv:
                self._cv.notify_all()
        return room

    def _wait_for_window(self, nbytes: int, timeout_s: float) -> int:
        """The window is full of transfers not yet seen complete: park
        until a release makes room, and look at the completion queue
        again after every wake.  Returns the microseconds it took."""
        t_in = time.monotonic()
        deadline = t_in + timeout_s
        while True:
            with self._cv:
                if self._inflight + nbytes > self.window_bytes:
                    if self._closed:
                        raise RuntimeError("endpoint closed")
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise TimeoutError(
                            f"ICI window full ({self.window_bytes}B)")
                    self._window_waiters += 1
                    try:
                        self._cv.wait(min(remaining, 1.0))
                    finally:
                        self._window_waiters -= 1
            if self._try_reserve(nbytes):
                return max(1, int((time.monotonic() - t_in) * 1e6))

    def _end_dispatch(self, release: int = 0) -> None:
        """The caller is out of the runtime.  `release` is the credit no
        completion entry carries (a dispatch that failed): handed back
        here, or failed sends would shrink the window for good."""
        self._dispatching.discard(threading.get_ident())
        self._release_window(release)

    def send(self, array: jax.Array, timeout_s: float = 30.0) -> jax.Array:
        """Start an async transfer of `array` to this endpoint's device;
        returns the (not-yet-ready) destination array.  Blocks while the
        credit window is exhausted."""
        with rpcz.stage("ici.endpoint.send") as stg:
            return self._send(array, timeout_s, stg)

    def _send(self, array: jax.Array, timeout_s: float, stg) -> jax.Array:
        nbytes = array.nbytes
        self._reserve_window(nbytes, timeout_s, stg)
        t0 = time.monotonic()
        try:
            if fault.ENABLED and fault.hit(
                    "ici.send", device=self.device.id) is not None:
                # injected transfer failure BEFORE dispatch: the except
                # below must release the window reservation
                raise RuntimeError("injected ici transfer fault")
            # no lock from here to the put: other senders of this
            # endpoint dispatch alongside, and the drainer confirms each
            # entry by itself
            out = self._transfer(array)  # async ICI DMA / HBM copy
            self._completions.put(((out,), nbytes, t0))
        except Exception:
            self._end_dispatch(release=nbytes)
            raise
        self._end_dispatch()
        _send_bytes.add(nbytes)
        _send_count.add(1)
        self._ensure_drainer()
        return out

    def send_sync(self, array: jax.Array) -> jax.Array:
        out = self.send(array)
        out.block_until_ready()
        return out

    def send_batch(self, arrays, timeout_s: float = 30.0) -> list:
        """Transfer a batch of arrays with ONE dispatch per group: the
        same-device arrays ride a single pre-compiled multi-copy program
        (_multi_copy), the cross-device arrays one device_put of the whole
        list.  The window is reserved for the batch total, so size batches
        <= window_bytes (larger batches raise).

        This is the pipe's fast path: per-chunk Python dispatch and
        per-chunk completion records — the costs that capped r2's ladder
        at ~5 GB/s while the chip streams 670 — are amortized over the
        batch.  Like send, it holds no lock while the runtime dispatches."""
        arrays = list(arrays)
        if not arrays:
            return []
        with rpcz.stage("ici.endpoint.send") as stg:
            return self._send_batch(arrays, timeout_s, stg)

    def _send_batch(self, arrays: list, timeout_s: float, stg) -> list:
        total = sum(a.nbytes for a in arrays)
        if total > self.window_bytes:
            raise ValueError(
                f"batch of {total}B exceeds window {self.window_bytes}B; "
                f"split it or widen the window")
        self._reserve_window(total, timeout_s, stg)
        t0 = time.monotonic()
        # bytes whose completion entry is already queued: the drainer will
        # release their window share, so a partial-dispatch failure must
        # release only the remainder (releasing `total` would double-free
        # the queued share and drive the window counter negative)
        queued = 0
        try:
            if fault.ENABLED and fault.hit(
                    "ici.send", device=self.device.id) is not None:
                # nothing queued yet: the except releases the full total
                raise RuntimeError("injected ici transfer fault")
            same = []
            cross = []
            for i, a in enumerate(arrays):
                try:
                    is_same = a.devices() == {self.device}
                except Exception:
                    is_same = False
                (same if is_same else cross).append(i)
            outs = [None] * len(arrays)
            # each group's entry is queued as soon as the group is
            # dispatched, so a failure of the second group leaves the
            # first one's credit with the drainer, which observes it
            if same:
                copied = _multi_copy(*[arrays[i] for i in same])
                for i, c in zip(same, copied):
                    outs[i] = c
                _same_device_copies.add(len(same))
                same_bytes = sum(arrays[i].nbytes for i in same)
                # one program: its outputs become ready together, so one
                # of them stands for all
                self._completions.put(((copied[-1],), same_bytes, t0))
                queued += same_bytes
            if cross:
                moved = jax.device_put([arrays[i] for i in cross],
                                       self.device)
                for i, m in zip(cross, moved):
                    outs[i] = m
                _cross_device_moves.add(len(cross))
                cross_bytes = sum(arrays[i].nbytes for i in cross)
                # one DMA per array: every one is confirmed
                self._completions.put((tuple(moved), cross_bytes, t0))
                queued += cross_bytes
        except Exception:
            self._end_dispatch(release=total - queued)
            if queued:
                self._ensure_drainer()   # someone must observe the queued part
            raise
        self._end_dispatch()
        _send_bytes.add(total)
        _send_count.add(len(arrays))
        self._ensure_drainer()
        return outs

    # ------------------------------------------------------------------
    # Block pipe: BlockPool-staged byte transfers.  The analog of the
    # reference's RDMA path where IOBuf blocks come from the registered
    # BlockPool so payloads are born in NIC-visible memory
    # (rdma/block_pool.cpp:52 wired in at socket.cpp:1751) — here payloads
    # are staged into HBM arena slots on the source device, DMA'd to the
    # target device through the windowed send path, and installed into
    # destination-pool slots without a host bounce.
    # ------------------------------------------------------------------

    def send_blocks(self, blocks, timeout_s: float = 30.0) -> list:
        """Transfer the source Blocks' device buffers to this endpoint's
        device, installing results into blocks allocated from the target
        device's pool.  Returns the destination Blocks (caller frees).
        Blocks are grouped into window-sized batches so a multi-block
        payload costs one dispatch per window, not one per block."""
        from brpc_tpu.ici.block_pool import get_block_pool
        dst_pool = get_block_pool(self.device)
        out = []
        i = 0
        while i < len(blocks):
            batch = []
            views = []            # one view() (one pool-lock hit) per block
            batch_bytes = 0
            while i < len(blocks):
                v = blocks[i].view()
                if batch and batch_bytes + v.nbytes > self.window_bytes:
                    break
                batch.append(blocks[i])
                views.append(v)
                batch_bytes += v.nbytes
                i += 1
            moved = self.send_batch(views, timeout_s=timeout_s)
            for b, m in zip(batch, moved):
                # alloc by the transferred buffer's size (not b.used) so the
                # destination class always covers the source class, even
                # when either pool has fallen through to a larger class
                dst = dst_pool.alloc(m.nbytes)
                dst.install(m, b.used, meta=getattr(b, "_src_meta", None))
                out.append(dst)
        return out

    def send_bytes(self, data, src_pool, timeout_s: float = 30.0) -> list:
        """Chunk `data` into blocks from `src_pool` (staged into that
        device's HBM arena), move them over this endpoint, and return the
        destination Blocks.  Frees the staging blocks — INCLUDING on a
        mid-staging failure: blocks are collected as the generator yields
        them, so an alloc exhaustion on chunk k still frees chunks 1..k-1
        (with `staged = list(...)` the partial list was discarded and the
        already-staged blocks leaked; found by the chaos suite's injected
        block-pool exhaustion)."""
        from brpc_tpu.ici.block_pool import stage_chunks
        staged: list = []
        try:
            for blk in stage_chunks(data, src_pool):
                staged.append(blk)
            return self.send_blocks(staged, timeout_s=timeout_s)
        finally:
            for blk in staged:
                blk.free()

    @property
    def inflight_bytes(self) -> int:
        with self._mu:
            return self._inflight

    def close(self, join: bool = True) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        if self._drainer is not None:
            self._completions.put(None)
            if join:
                # joining matters: a daemon drainer killed at interpreter
                # exit while inside PJRT block_until_ready aborts the
                # process ("FATAL: exception not rethrown")
                self._drainer.join(timeout=30)


def link_stats() -> dict:
    """Exported on the /ici console page."""
    return {
        "send_bytes": _send_bytes.get_value(),
        "send_count": _send_count.get_value(),
        "recv_bytes": _recv_bytes.get_value(),
        "same_device_copies": _same_device_copies.get_value(),
        "cross_device_moves": _cross_device_moves.get_value(),
        "send_overlapped": _send_overlapped.get_value(),
        "transfer_avg_us": round(_transfer_latency.latency(), 1),
        "transfer_p99_us": round(_transfer_latency.latency_percentile(0.99), 1),
        "devices": [str(d) for d in jax.devices()],
    }
