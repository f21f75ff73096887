"""Streaming RPC — ordered message pipe with credit-window flow control.

Reference: stream.{h,cpp}, stream_impl.h, policy/streaming_rpc_protocol.cpp
(SURVEY.md §5.7): a stream piggybacks on an ordinary RPC (stream settings in
the request meta, accepted server-side), then DATA frames flow with a
sliding window — the writer blocks once `produced - remote_consumed` exceeds
the buffer; the consumer sends CONSUMED feedback frames that advance the
window.  Frames ARRIVE in order (one TCP socket per connection) but the
native core dispatches each parsed message onto the work-stealing executor,
so handler dispatch may be reordered — the stream_seq/reorder layer below
restores write order (the reference's per-stream ExecutionQueue).

ONE stream abstraction for host bytes AND device tensors: `write()` also
accepts jax device arrays.  When the peer has an ICI-reachable device, the
tensor payload slides under the socket exactly the way the reference
slides RDMA under Socket::StartWrite (socket.cpp:1751-1757, the
CutFromIOBufList swap): blocks stage on device, ride IciEndpoint's
credit-windowed transfer (brpc_tpu/ici/rail.py), and the DATA frame
carries only a claim ticket — CONSUMED feedback stays on the host socket
either way, and `rail.host_copy_count()` proves the zero-copy path.  A
peer without a reachable device gets the tensor-serializer fallback
(host bytes, still arrays at the far end).

Sizing max_buf_size: the window is a bandwidth-delay product.  Credit
releases cost one delivery round-trip (DATA frame -> claim -> handler ->
CONSUMED), so sustained throughput is capped at max_buf_size / RTT —
size the window to target_bandwidth x link RTT, and never below one
message (a message larger than the window can never be written).  The
rail's own credit window is fixed (rail._RAIL_WINDOW_BYTES).
"""
from __future__ import annotations

import itertools
import logging
import threading
from typing import Callable, Optional

from brpc_tpu import errors, fault, rpcz
from brpc_tpu.bvar import Adder
from brpc_tpu.rpc import meta as M
from brpc_tpu.rpc.transport import Transport

# hostile-peer shed events, on /vars next to EOVERCROWDED (a bound that
# fires silently is a bound operators can't see tripping)
reorder_replays_dropped = Adder("stream_reorder_replays_dropped")
reorder_overflow_closes = Adder("stream_reorder_overflow_closes")
# Bytes of dropped replayed/duplicate DATA frames (ADVICE r5): dropped
# duplicates are never acked, so their bytes permanently consume the
# SENDER's credit window.  Intentional for hostile peers on today's
# no-retransmit transport — but if transport-level redelivery is ever
# introduced, a wedged writer's credit shortfall must be explainable by
# this counter instead of being silent (the chaos drain test asserts
# exactly that).
reorder_replay_bytes_dropped = Adder("stream_reorder_replay_bytes_dropped")

DEFAULT_BUF_SIZE = 2 * 1024 * 1024

_stream_ids = itertools.count(1)


class StreamHandler:
    """Reference StreamInputHandler (stream.h:41-44)."""

    def on_received_messages(self, stream: "Stream", messages: list[bytes]) -> None:
        pass

    def on_idle_timeout(self, stream: "Stream") -> None:
        pass

    def on_closed(self, stream: "Stream") -> None:
        pass


class _FnHandler(StreamHandler):
    def __init__(self, fn, on_closed=None):
        self._fn = fn
        self._on_closed = on_closed

    def on_received_messages(self, stream, messages):
        for m in messages:
            self._fn(stream, m)

    def on_closed(self, stream):
        if self._on_closed is not None:
            self._on_closed(stream)


class Stream:
    """Each side owns a local id (registry key) and learns the peer's id —
    outgoing frames are addressed to the peer's local id, exactly how the
    reference exchanges stream ids through StreamSettings in the request/
    response meta (streaming_rpc_meta.proto)."""

    def __init__(self, stream_id: int, handler: Optional[StreamHandler],
                 max_buf_size: int = DEFAULT_BUF_SIZE, device=None):
        self.stream_id = stream_id               # local id
        self.remote_id: Optional[int] = None     # peer's local id
        self.handler = handler
        self.max_buf_size = max_buf_size
        # tensor rail endpoints: `device` is where WE receive tensor
        # payloads (advertised to the peer in the settings exchange,
        # F_SDEV); `peer_device` is where the PEER receives — learned
        # from its settings/rail map, None = host-serialize fallback
        self.device = device
        self.peer_device = None
        # The WRITER's window size, learned from the StreamSettings exchange:
        # feedback must fire well before the peer's window fills, regardless
        # of our own buffer size (a 2MB receiver facing a 256KB writer would
        # otherwise never send feedback and deadlock the writer).
        self.peer_buf_size: Optional[int] = None
        self._sid: Optional[int] = None          # bound host connection
        self._mu = threading.Lock()
        self._window_cv = threading.Condition(self._mu)
        self._produced = 0
        self._remote_consumed = 0
        self._consumed_local = 0                 # receiver side
        self._last_feedback = 0
        # writes before binding: (seq, "bytes"|"tensor", payload)
        self._pending: list[tuple[int, str, object]] = []
        self._closed = False
        self._close_sent = False
        # Ordered delivery (the reference's per-stream ExecutionQueue,
        # stream_impl.h:133): our native core dispatches each parsed message
        # onto the work-stealing executor, so DATA frames for one stream may
        # be PROCESSED out of order even though they ARRIVE in order.  The
        # writer numbers frames (stream_seq, 1-based) and the receiver
        # reorders + serializes handler delivery with a drain loop.
        self._send_seq = 1
        self._recv_next = 1
        self._reorder: dict[int, bytes] = {}
        self._reorder_bytes = 0
        self._close_seq: Optional[int] = None
        self._delivering = False
        # Tensor write coalescing: rail-bound writes go through a
        # per-stream sender thread that drains its queue in batches, so N
        # back-to-back stream.write(array) calls become ONE batched
        # device dispatch (rail.ship_many) instead of N — dispatch is
        # host work per program, and per-message shipping made it the
        # whole streaming-tensor cost.  Frames
        # still go out one per message (the receiver's seq-reorder layer
        # already tolerates any arrival order).
        self._tq = None
        self._tq_thread: Optional[threading.Thread] = None
        self._tq_closing = False

    # ---- binding (the RPC established the host connection) ----

    def bind(self, sid: int) -> None:
        with self._mu:
            self._sid = sid
        self._maybe_flush()

    def set_remote(self, remote_id: int) -> None:
        with self._mu:
            self.remote_id = remote_id
        self._maybe_flush()

    def _maybe_flush(self) -> None:
        with self._mu:
            if self._sid is None or self.remote_id is None:
                return
            pending, self._pending = self._pending, []
        for seq, kind, payload in pending:
            if kind == "bytes":
                self._send_data(payload, seq)
            else:
                self._send_tensor(payload, seq)

    @property
    def connected(self) -> bool:
        return self._sid is not None and self.remote_id is not None

    @property
    def closed(self) -> bool:
        return self._closed

    # ---- writer side (StreamWrite, stream.cpp:721/274) ----

    def write(self, data, timeout_s: float | None = 10.0) -> None:
        """Write one message: host bytes OR a jax device array (or a
        list/tuple of them).  Blocks while the window is full; raises
        RpcError(EAGAIN-like) on timeout, EEOF if closed.  Device
        payloads count their device nbytes against the same window."""
        if isinstance(data, (bytes, bytearray, memoryview)):
            kind, payload, nbytes = "bytes", bytes(data), len(data)
        else:
            from brpc_tpu.ici import rail
            if not rail.railable(data):
                raise TypeError(
                    "stream write takes bytes or jax device arrays, "
                    f"not {type(data).__name__}")
            arrays = data if isinstance(data, (list, tuple)) else [data]
            kind, payload = "tensor", data
            nbytes = sum(a.nbytes for a in arrays)
        if self._closed or self._close_sent:
            raise errors.RpcError(errors.EEOF, "stream closed")
        with rpcz.stage("stream.write") as stg:
            with self._window_cv:
                if (self._produced + nbytes - self._remote_consumed
                        > self.max_buf_size):
                    with rpcz.stage("stream.credit_wait"):
                        self._wait_credit(nbytes, timeout_s)
                self._produced += nbytes
                seq = self._send_seq
                self._send_seq += 1
                if self._sid is None or self.remote_id is None:
                    self._pending.append((seq, kind, payload))
                    return
            if stg is not rpcz.NOOP_STAGE:
                # the id the frame is addressed to: the receiver's stages
                # of this message carry the same one
                stg.set(cid=f"{self.remote_id}:{seq}", bytes=nbytes)
            if kind == "bytes":
                self._send_data(payload, seq)
            else:
                self._send_tensor(payload, seq)

    def _wait_credit(self, nbytes: int, timeout_s: float | None) -> None:
        """Park (``_window_cv`` held) until the peer's feedback leaves
        room for ``nbytes``."""
        import time
        deadline = float("inf") if timeout_s is None \
            else time.monotonic() + timeout_s
        while (self._produced + nbytes - self._remote_consumed
               > self.max_buf_size):
            if self._closed:
                raise errors.RpcError(errors.EEOF, "stream closed")
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise errors.RpcError(
                    errors.EOVERCROWDED,
                    f"stream window full ({self.max_buf_size}B)")
            self._window_cv.wait(min(remaining, 1.0))

    def _send_data(self, data: bytes, seq: int) -> None:
        rc = Transport.instance().write_frame(
            self._sid, M.RpcMeta.encode_stream_data(self.remote_id, seq),
            data)
        if rc != 0:
            self._on_closed_internal()

    def _send_tensor(self, obj, seq: int) -> None:
        """StreamWrite for device payloads — the RDMA slide-under
        (socket.cpp:1751-1757): with a reachable peer device the tensors
        move HBM→HBM through the rail and the socket frame carries only
        the claim ticket; otherwise the tensor serializer produces a host
        fallback frame that still rebuilds arrays at the far end.

        Rail-bound writes are queued to the per-stream sender thread so
        adjacent messages share one batched dispatch (ship_many); the
        no-device fallback serializes inline as before.  Enqueue order
        vs the close sentinel is serialized under _mu: a write that loses
        the race to close() sends inline instead of landing in a queue no
        thread will drain."""
        if self.peer_device is not None:
            self._ensure_tensor_sender()
            with self._mu:
                closing = self._tq_closing
                if not closing:
                    self._tq.put((seq, obj))
            if closing:
                self._send_tensor_fallback(obj, seq)
            return
        self._send_tensor_fallback(obj, seq)

    def _send_tensor_fallback(self, obj, seq: int) -> None:
        """Host-serialized tensor frame — the no-reachable-device shape,
        also the escape hatch when the rail or the sender queue is gone."""
        from brpc_tpu.ici import rail
        rail.rail_fallbacks.add(1)
        from brpc_tpu.rpc.serialization import get_serializer
        meta = M.RpcMeta(msg_type=M.MSG_STREAM_DATA,
                         stream_id=self.remote_id, stream_seq=seq)
        body, meta.tensor_header = get_serializer("tensor").encode(obj)
        rc = Transport.instance().write_frame(self._sid, meta.encode(), body)
        if rc != 0:
            self._on_closed_internal()

    def _ensure_tensor_sender(self) -> None:
        if self._tq is None:
            with self._mu:
                if self._tq is None:
                    import queue as _qm
                    import weakref
                    q = _qm.Queue()
                    # the thread must NOT keep the Stream alive: it holds
                    # only a weakref and exits when the stream is gone —
                    # an abandoned stream (no close(), no peer CLOSE) must
                    # stay garbage-collectable, not pin a thread forever
                    t = threading.Thread(
                        target=_tensor_send_loop,
                        args=(weakref.ref(self), q),
                        daemon=True, name=f"stream-tsend-{self.stream_id}")
                    self._tq = q
                    self._tq_thread = t
                    t.start()

    def _flush_tensor_sender(self) -> None:
        """Drain queued tensor writes and stop the sender — close() must
        not race CLOSE past data still sitting in the queue.  _tq_closing
        is set under _mu BEFORE the sentinel goes in, so any concurrent
        write either precedes the sentinel (flushed here) or observes
        _tq_closing and sends inline."""
        t = self._tq_thread
        if t is None or t is threading.current_thread():
            return
        with self._mu:
            self._tq_closing = True
            self._tq.put(None)
        t.join(timeout=30)
        self._tq_thread = None

    # ---- receiver side ----

    def _on_data(self, payload, nbytes: int, seq: int) -> None:
        if seq == 0:
            # unsequenced peer (pre-stream_seq wire format): deliver in
            # arrival order, mirroring the seq==0 CLOSE fallback
            if self.handler is not None:
                try:
                    with rpcz.stage("stream.handler"):
                        self.handler.on_received_messages(self, [payload])
                except Exception:
                    logging.exception("stream handler raised")
            self._ack(nbytes)
            return
        with self._mu:
            if seq < self._recv_next or seq in self._reorder:
                # replay of a delivered or in-flight seq: a sub-
                # _recv_next entry would park in the dict FOREVER (the
                # drain only pops forward), so a replaying peer could
                # grow it without bound — drop duplicates outright.
                # NOTE: dropped bytes are never acked, so they consume
                # the sender's credit window permanently — counted so a
                # credit shortfall under (future) redelivery is visible
                # on /vars rather than a silent writer wedge.
                reorder_replays_dropped.add(1)
                reorder_replay_bytes_dropped.add(nbytes)
                return
            self._reorder[seq] = (payload, nbytes)
            self._reorder_bytes += nbytes
            # a CORRECT peer can never have more unacked bytes in flight
            # than the WRITER's credit window (peer_buf_size, learned in
            # the settings exchange; our own max_buf_size when the peer
            # is bigger-bounded or unknown); a writer ignoring the
            # window (or spraying far-future seqs that can never drain)
            # is a protocol violation, not backpressure — close before
            # the buffer becomes a memory DoS (the h2 header-block/
            # frame-bound discipline, applied to the stream reorder
            # buffer).  2x allows device payloads whose nbytes
            # accounting straddles the window.
            window = max(self.max_buf_size, self.peer_buf_size or 0)
            overflow = self._reorder_bytes > 2 * window + (64 << 10)
        if overflow:
            reorder_overflow_closes.add(1)
            logging.warning("stream %d: reorder buffer exceeded 2x the "
                            "credit window; closing (protocol violation)",
                            self.stream_id)
            # tell the live peer (seq 0 = immediate close on receipt) so
            # its writer fails EEOF instead of blocking out its window
            # against a stream that no longer exists
            if self._sid is not None and self.remote_id is not None:
                try:
                    Transport.instance().write_frame(
                        self._sid,
                        M.RpcMeta(msg_type=M.MSG_STREAM_CLOSE,
                                  stream_id=self.remote_id).encode())
                except Exception:
                    pass
            self._on_closed_internal()
            return
        self._drain()

    def _on_close_frame(self, seq: int) -> None:
        if seq == 0:
            # pre-stream_seq peer compat — immediate close
            self._on_closed_internal()
            return
        with self._mu:
            # min(): a duplicate CLOSE with a higher seq must not raise the
            # latch past what data seqs can ever satisfy
            if self._close_seq is None or seq < self._close_seq:
                self._close_seq = seq
        self._drain()

    def _drain(self) -> None:
        """Deliver consecutive frames; only one thread drains at a time
        (per-stream ExecutionQueue semantics)."""
        with self._mu:
            if self._delivering:
                return
            self._delivering = True
        while True:
            with self._mu:
                ready: list = []
                ready_bytes = 0
                while self._recv_next in self._reorder:
                    payload, nbytes = self._reorder.pop(self._recv_next)
                    self._reorder_bytes -= nbytes
                    ready.append(payload)
                    ready_bytes += nbytes
                    self._recv_next += 1
                close_now = (self._close_seq is not None
                             and self._recv_next >= self._close_seq)
                if not ready and not close_now:
                    self._delivering = False
                    return
            if ready and self.handler is not None:
                try:
                    with rpcz.stage("stream.handler"):
                        self.handler.on_received_messages(self, ready)
                except Exception:
                    # a raising handler must not wedge the drain loop
                    # (_delivering would stay True forever)
                    logging.exception("stream handler raised")
            if ready:
                self._ack(ready_bytes)
            if close_now:
                with self._mu:
                    self._delivering = False
                self._on_closed_internal()
                return

    def _ack(self, nbytes: int) -> None:
        with rpcz.stage("stream.ack"):
            self._note_consumed(nbytes)

    def _note_consumed(self, nbytes: int) -> None:
        with self._mu:
            self._consumed_local += nbytes
            threshold = min(self.max_buf_size,
                            self.peer_buf_size or self.max_buf_size) // 2
            send_feedback = (self._consumed_local - self._last_feedback
                             >= max(1, threshold))
            if send_feedback:
                self._last_feedback = self._consumed_local
        if send_feedback and self._sid is not None and \
                self.remote_id is not None:
            if fault.ENABLED and fault.hit(
                    "stream.feedback", stream_id=self.stream_id) is not None:
                # injected feedback loss: the sender's credit stays
                # consumed until the NEXT threshold crossing — offsets
                # are cumulative, so one lost frame delays credit return
                # rather than leaking it
                return
            meta = M.RpcMeta(msg_type=M.MSG_STREAM_FEEDBACK,
                             stream_id=self.remote_id,
                             stream_offset=self._consumed_local)
            Transport.instance().write_frame(self._sid, meta.encode())

    def _on_feedback(self, consumed: int) -> None:
        with self._window_cv:
            self._remote_consumed = max(self._remote_consumed, consumed)
            self._window_cv.notify_all()

    def _on_closed_internal(self) -> None:
        with self._window_cv:
            already = self._closed
            self._closed = True
            self._window_cv.notify_all()
        if not already and self._tq is not None:
            self._tq.put(None)    # stop the tensor sender (it may be us)
        if not already and self.handler is not None:
            self.handler.on_closed(self)
        StreamRegistry.instance().remove(self.stream_id)

    def close(self) -> None:
        with self._mu:
            if self._closed or self._close_sent:
                return
            self._close_sent = True
        self._flush_tensor_sender()
        if self._sid is not None and self.remote_id is not None:
            with self._mu:
                seq = self._send_seq
                self._send_seq += 1
            # sequenced CLOSE: the peer closes only after delivering every
            # DATA frame written before close()
            meta = M.RpcMeta(msg_type=M.MSG_STREAM_CLOSE,
                             stream_id=self.remote_id, stream_seq=seq)
            Transport.instance().write_frame(self._sid, meta.encode())
        self._on_closed_internal()


def write_runs(runs: list) -> tuple:
    """Many small host messages to many streams without waiting for any
    of them: each entry of ``runs`` is ``(stream, messages)``, and the
    longest prefix of ``messages`` that fits the stream's window NOW is
    taken (credit, then a sequence number a message, in order, under the
    stream's lock: what ``write`` does for one).  The frames of every
    stream bound to one connection leave as ONE ``Transport.write_frames``
    call, in the order of ``runs``.  Returns ``(taken, writes)``:
    ``taken[i]`` is how many of entry i's messages went out (what is left
    stays the caller's, to offer again once feedback has come), or -1
    where the stream is closed or its connection refused the write
    (every stream of that run is then closed, as after a failed
    ``write``); ``writes`` counts the socket writes made.  For a sender
    that serves many streams from one thread (the decode engine's emit
    drainer); ``write`` stays the call for one that may wait."""
    taken = [0] * len(runs)
    by_sid: dict = {}        # sid -> (frames, entries that ride it)
    for i, (s, messages) in enumerate(runs):
        with s._mu:
            if s._closed or s._close_sent:
                taken[i] = -1
                continue
            if s._sid is None or s.remote_id is None:
                continue        # not bound yet: nothing fits
            n = 0
            frames, members = by_sid.setdefault(s._sid, ([], []))
            for m in messages:
                if s._produced + len(m) - s._remote_consumed \
                        > s.max_buf_size:
                    break
                s._produced += len(m)
                frames.append((M.RpcMeta.encode_stream_data(
                    s.remote_id, s._send_seq), m))
                s._send_seq += 1
                n += 1
            taken[i] = n
            if n:
                members.append(i)
    writes = 0
    for sid, (frames, members) in by_sid.items():
        if not frames:
            continue
        writes += 1
        if Transport.instance().write_frames(sid, frames) != 0:
            for i in members:
                taken[i] = -1
                runs[i][0]._on_closed_internal()
    return taken, writes


def _tensor_send_loop(wref, q) -> None:
    """Per-stream tensor sender (module-level: holds NO strong reference
    to the Stream between batches).  Exits on the close sentinel, when
    the stream dies, or when the weakref clears — whichever comes first."""
    import queue as _qm
    while True:
        try:
            item = q.get(timeout=5.0)
        except _qm.Empty:
            s = wref()
            if s is None or s._closed:
                return
            del s
            continue
        if item is None:
            return
        batch = [item]
        stop = False
        while True:
            try:
                nxt = q.get_nowait()
            except _qm.Empty:
                break
            if nxt is None:
                stop = True   # flush what's collected, then exit
                break
            batch.append(nxt)
        s = wref()
        if s is None or s._closed:
            # stream gone / transport dead: nothing was shipped yet for
            # this batch, so dropping it leaks no tickets
            return
        with rpcz.stage("stream.send") as stg:
            if stg is not rpcz.NOOP_STAGE:
                stg.set(cid=f"{s.remote_id}:{batch[0][0]}",
                        chunks=len(batch))
            if not _send_tensor_batch(s, batch):
                return
        if stop:
            return
        del s    # drop the strong ref while parked in q.get


def _send_tensor_batch(s: "Stream", batch: list) -> bool:
    """One turn of the tensor sender: ship the batch over the rail as
    one dispatch and write its ticket frames (or fall back to host
    frames).  False when the stream died under it."""
    from brpc_tpu.ici import rail
    tickets = None
    try:
        tickets = rail.ship_many([obj for _, obj in batch], s.peer_device)
    except Exception:
        logging.exception("stream rail ship failed; host fallback")
    if tickets is not None:
        # ticket frames are tiny (meta only, empty bodies): ship the
        # whole batch as ONE socket write — one ctypes crossing and
        # one write-stack push instead of len(batch), ordering
        # preserved.  Tiny frames can never trip the per-write
        # EOVERCROWDED bound the way coalesced big bodies would.
        frames = []
        for k, (seq, obj) in enumerate(batch):
            frames.append((M.RpcMeta.encode_stream_data(
                s.remote_id, seq, ticket=tickets[k],
                src_dev=str(rail.source_device(obj).id)), b""))
        if Transport.instance().write_frames(s._sid, frames) != 0:
            for t in tickets:       # atomic pops: no double-free
                rail.withdraw(t)
            s._on_closed_internal()
            return False
        return True
    # host fallback: bodies are full serialized tensors — write per
    # frame so each passes the overcrowded bound on its own and no
    # giant contiguous join is materialized
    from brpc_tpu.rpc.serialization import get_serializer
    for seq, obj in batch:
        meta = M.RpcMeta(msg_type=M.MSG_STREAM_DATA,
                         stream_id=s.remote_id, stream_seq=seq)
        rail.rail_fallbacks.add(1)
        body, meta.tensor_header = get_serializer("tensor").encode(obj)
        if Transport.instance().write_frame(
                s._sid, meta.encode(), body) != 0:
            s._on_closed_internal()
            return False
    return True


class StreamRegistry:
    _instance = None
    _lock = threading.Lock()

    @classmethod
    def instance(cls) -> "StreamRegistry":
        with cls._lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    def __init__(self):
        self._streams: dict[int, Stream] = {}
        self._mu = threading.Lock()

    def register(self, stream: Stream) -> None:
        with self._mu:
            self._streams[stream.stream_id] = stream

    def get(self, stream_id: int) -> Optional[Stream]:
        with self._mu:
            return self._streams.get(stream_id)

    def remove(self, stream_id: int) -> None:
        with self._mu:
            self._streams.pop(stream_id, None)

    def count(self) -> int:
        with self._mu:
            return len(self._streams)

    def on_socket_failed(self, sid: int) -> None:
        """The bound host connection died: every stream riding it is
        unrecoverable — DATA frames can neither arrive nor leave — so
        each one closes NOW and its handler's ``on_closed`` fires.
        Without this, a stream whose peer process died silently (no
        CLOSE frame) waits forever: the cluster router's failover
        (ISSUE 8) depends on learning about a dead replica at socket
        speed, not at application-timeout speed."""
        with self._mu:
            dead = [s for s in self._streams.values() if s._sid == sid]
        for s in dead:
            s._on_closed_internal()

    @staticmethod
    def _withdraw_ticket(meta: M.RpcMeta) -> None:
        """An undeliverable DATA frame's rail ticket must still be
        withdrawn, or its HBM blocks sit pinned until the registry TTL
        fires — shared by the dead-stream path and the injected-DROP
        path, so the discipline lives in one place."""
        if meta.msg_type == M.MSG_STREAM_DATA and meta.user_fields \
                and meta.user_fields.get(M.F_TICKET):
            from brpc_tpu.ici import rail
            rail.withdraw(meta.user_fields[M.F_TICKET])

    def on_frame(self, sid: int, meta: M.RpcMeta, body) -> None:
        # meta.stream_id addresses the RECEIVER's local stream.
        dup = False
        if fault.ENABLED:
            # ctx carries msg_type AND stream_seq so plans can scope
            # rules to the frames a kind is meaningful for — DUP in
            # particular only duplicates SEQUENCED data (the seq==0
            # compat branch delivers in arrival order with no dedup);
            # scope DUP rules with match=... on msg_type/stream_seq or
            # the firing is a counted no-op on other frames
            f = fault.hit("stream.frame", stream_id=meta.stream_id,
                          msg_type=meta.msg_type,
                          stream_seq=meta.stream_seq)
            if f is not None:
                if f.kind == fault.DROP:
                    self._withdraw_ticket(meta)
                    return
                dup = (f.kind == fault.DUP
                       and meta.msg_type == M.MSG_STREAM_DATA
                       and meta.stream_seq != 0)
        s = self.get(meta.stream_id)
        if s is None:
            self._withdraw_ticket(meta)
            return
        if s._sid is None:
            s.bind(sid)
        if meta.msg_type == M.MSG_STREAM_DATA:
            with rpcz.stage("stream.on_data") as stg:
                if stg is not rpcz.NOOP_STAGE:
                    stg.set(cid=f"{meta.stream_id}:{meta.stream_seq}")
                try:
                    payload, nbytes = _decode_data_frame(meta, body)
                except Exception:
                    # an expired ticket / corrupt tensor header poisons
                    # the SEQUENCE (a message is unrecoverably lost): close
                    logging.exception("stream data frame undecodable")
                    s._on_closed_internal()
                    return
                s._on_data(payload, nbytes, meta.stream_seq)
                if dup:
                    # injected transport-level redelivery: the duplicate
                    # must be dropped by the reorder layer and its bytes
                    # counted (reorder_replay_bytes_dropped), never
                    # delivered twice
                    s._on_data(payload, nbytes, meta.stream_seq)
        elif meta.msg_type == M.MSG_STREAM_FEEDBACK:
            with rpcz.stage("stream.on_feedback"):
                s._on_feedback(meta.stream_offset)
        elif meta.msg_type == M.MSG_STREAM_CLOSE:
            s._on_close_frame(meta.stream_seq)


def _decode_data_frame(meta: M.RpcMeta, body):
    """One DATA frame -> (payload, window_bytes).  Three wire shapes:
    rail ticket (device arrays HBM->HBM, zero host copies), tensor
    header (host-serialized arrays, the no-reachable-device fallback),
    plain bytes."""
    if meta.user_fields and meta.user_fields.get(M.F_TICKET):
        from brpc_tpu.ici import rail
        obj = rail.claim(meta.user_fields[M.F_TICKET])
        arrays = obj if isinstance(obj, list) else [obj]
        return obj, sum(a.nbytes for a in arrays)
    if meta.tensor_header:
        from brpc_tpu.rpc.serialization import get_serializer
        obj = get_serializer("tensor").decode(body.to_bytes(),
                                              meta.tensor_header)
        arrays = obj if isinstance(obj, (list, tuple)) else [obj]
        return obj, sum(a.nbytes for a in arrays)
    data = body.to_bytes()
    return data, len(data)


def stream_create(cntl, handler: StreamHandler | Callable | None = None,
                  max_buf_size: int = DEFAULT_BUF_SIZE,
                  device=None) -> Stream:
    """Client side: create a stream riding the next RPC issued with `cntl`
    (reference StreamCreate, stream.cpp:772).  `device` is where THIS side
    receives tensor payloads (advertised to the peer); the peer's receive
    device is learned from the rail map / settings response."""
    if callable(handler) and not isinstance(handler, StreamHandler):
        handler = _FnHandler(handler)
    s = Stream(next(_stream_ids), handler, max_buf_size, device=device)
    StreamRegistry.instance().register(s)
    cntl._stream = s
    return s


def stream_accept(cntl, handler: StreamHandler | Callable | None = None,
                  max_buf_size: int = DEFAULT_BUF_SIZE,
                  device=None) -> Stream:
    """Server side, inside a handler: accept the peer's stream
    (reference StreamAccept, stream.cpp:813).  `device` is this side's
    tensor receive device (advertised back in the settings response)."""
    meta = cntl.request_meta
    if meta is None or meta.stream_id == 0:
        raise errors.RpcError(errors.EREQUEST, "no stream attached")
    if callable(handler) and not isinstance(handler, StreamHandler):
        handler = _FnHandler(handler)
    s = Stream(next(_stream_ids), handler, max_buf_size, device=device)
    s.set_remote(meta.stream_id)     # client's local id from the request
    sbuf = meta.user_fields.get("sbuf")
    if sbuf:
        s.peer_buf_size = int(sbuf)
    sdev = meta.user_fields.get(M.F_SDEV)
    if sdev:
        # the client's advertised receive device: the process token in
        # the advert makes this fail closed for out-of-process peers,
        # whose rail tickets could never be claimed
        from brpc_tpu.ici import rail as _rail
        s.peer_device = _rail.device_from_wire(sdev)
    s.bind(cntl.peer_sid)
    StreamRegistry.instance().register(s)
    cntl._stream = s                 # response meta carries our local id
    return s
