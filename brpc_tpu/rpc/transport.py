"""TransportManager — Python-side hub over the native socket core.

Owns the process-lifetime ctypes callbacks (native sockets keep raw pointers
to them), routes complete messages by SocketId to the registered handler
(client connection or server), and wraps the native timer thread for
timeout/backup timers.  This is the Python face of the reference's
InputMessenger + SocketMap glue (SURVEY.md §2.3).
"""
from __future__ import annotations

import ctypes
import threading
from typing import Callable, Optional

from brpc_tpu._core import (ACCEPTED_CB, FAILED_CB, H2_EVENT_CB, IOBuf,
                            MESSAGE_CB,
                            MSG_FILTERED, MSG_H2, MSG_HTTP, MSG_MEMCACHE,
                            MSG_MONGO, MSG_NSHEAD, MSG_RAW, MSG_REDIS,
                            MSG_THRIFT, MSG_TRPC, REQUEST_CB, RESPONSE_CB,
                            TASK_CB, core, core_init)
from brpc_tpu._core import _fastrpc
from brpc_tpu import fault, rpcz


def _apply_send_fault(sid: int, payload):
    """ONE interpreter for every transport.send site (call only behind
    ``fault.ENABLED``).  Returns (rc, payload): a non-None rc
    short-circuits the write; otherwise the caller writes `payload`,
    which a CORRUPT fault mangles in place.  Each site passes the bytes
    whose corruption is meaningful there — the meta for framed writes
    (peer-side decode discards the frame), the raw buffer or the body
    for the others — so a counted injection is never a no-op."""
    f = fault.hit("transport.send", sid=sid)
    if f is None:
        return None, payload
    if f.kind == fault.CORRUPT:
        return None, fault.mangle(bytes(payload)) if payload else payload
    if f.kind == fault.OVERCROWD:
        return -2, payload
    if f.kind in (fault.RESET, fault.PARTIAL):
        if f.kind == fault.PARTIAL:
            # a torn prefix reaches the peer's parser before the close —
            # the classic half-written frame of a mid-write process death
            torn = b"TRPC\x00\x00\x00\x08"
            try:
                core.brpc_socket_write_raw(sid, torn, len(torn), None)
            except Exception:
                pass
        core.brpc_socket_set_failed(sid, 104)   # ECONNRESET
        return -1, payload
    return f.rc, payload   # ERROR: plain write failure


class Transport:
    _instance: Optional["Transport"] = None
    _instance_lock = threading.Lock()

    @classmethod
    def instance(cls) -> "Transport":
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    def __init__(self):
        core_init()
        self._lock = threading.Lock()
        # sid -> (on_message(sid, kind, meta_bytes, body: IOBuf),
        #         on_failed(sid, err))
        self._handlers: dict[int, tuple[Callable, Callable]] = {}
        # sid -> fast-path handlers (natively pre-parsed metas)
        self._request_handlers: dict[int, Callable] = {}
        self._response_handlers: dict[int, Callable] = {}
        # sid -> NativeH2Bridge (listener entries inherited by accepted
        # connections, exactly like _handlers)
        self._h2_bridges: dict[int, object] = {}
        self._request_cb_installed = False
        self._h2_cb_installed = False
        self._timer_lock = threading.Lock()
        self._timer_cbs: dict[int, Callable[[], None]] = {}
        self._timer_token = 1
        # in-socket TLS (rpc/tls_engine.py): sid -> TlsEngine, and TLS
        # listeners whose accepted connections auto-wrap
        self._tls: dict[int, object] = {}
        self._tls_listener_ctx: dict[int, object] = {}

        # Process-lifetime trampolines (pinned as attributes).
        @MESSAGE_CB
        def _on_message(sid, kind, meta, meta_len, body, user):
            buf = IOBuf(handle=body)  # takes ownership, freed at GC
            if kind == MSG_FILTERED:
                # in-socket TLS: ciphertext for this connection's engine;
                # decrypted bytes re-enter the native parser via inject
                eng = self._tls.get(sid)
                if eng is not None:
                    eng.feed_ciphertext(buf.to_bytes())
                return
            m = ctypes.string_at(meta, meta_len) if meta_len else b""
            if fault.ENABLED:
                f = fault.hit("transport.recv", sid=sid, kind=kind)
                if f is not None:
                    if f.kind == fault.DROP:
                        return          # delivered by TCP, lost above it
                    if f.kind == fault.CORRUPT:
                        # mangled meta fails RpcMeta.decode downstream —
                        # the frame is discarded exactly like line noise
                        m = fault.mangle(m)
            h = self._handlers.get(sid)
            if h is not None:
                try:
                    h[0](sid, kind, m, buf)
                except Exception:  # pragma: no cover - handler bug guard
                    import traceback
                    traceback.print_exc()

        @FAILED_CB
        def _on_failed(sid, err, user):
            with self._lock:
                h = self._handlers.pop(sid, None)
                self._request_handlers.pop(sid, None)
                self._response_handlers.pop(sid, None)
                bridge = self._h2_bridges.pop(sid, None)
                self._tls.pop(sid, None)
                self._tls_listener_ctx.pop(sid, None)
            if bridge is not None:
                try:
                    bridge.on_connection_failed(sid)
                except Exception:  # pragma: no cover
                    import traceback
                    traceback.print_exc()
            if h is not None and h[1] is not None:
                try:
                    h[1](sid, err)
                except Exception:  # pragma: no cover
                    import traceback
                    traceback.print_exc()

        @ACCEPTED_CB
        def _on_accepted(listener, conn, user):
            h = self._handlers.get(listener)
            if h is not None:
                # Accepted connections inherit the listener's handlers.
                with self._lock:
                    self._handlers[conn] = h
            rh = self._request_handlers.get(listener)
            if rh is not None:
                with self._lock:
                    self._request_handlers[conn] = rh
            br = self._h2_bridges.get(listener)
            if br is not None:
                with self._lock:
                    self._h2_bridges[conn] = br
            ctx = self._tls_listener_ctx.get(listener)
            if ctx is not None:
                # TLS listener: wrap the accepted connection BEFORE any
                # byte parses (accepted sockets are defer-registered, so
                # the filter flag is in place when the fd is armed)
                self.enable_tls(conn, ctx, server_side=True)

        # fast-path dispatchers (_fastrpc C extension: natively pre-parsed
        # metas arrive as flat args; the body is an IOBuf-backed READ-ONLY
        # memoryview — zero-copy, pins the blocks while referenced)
        def _on_request(sid, cid, attempt, service, method_, compress,
                        timeout_ms, content_type, attachment_size, body):
            h = self._request_handlers.get(sid)
            if h is None:
                # No per-socket handler (listener torn down mid-flight):
                # reply EINTERNAL rather than leaving the caller to hang
                # until its deadline.
                _fastrpc.send_response(sid, cid, attempt, 2001,
                                       "no request handler", "", b"")
                return
            try:
                h(sid, cid, attempt, service, method_, compress,
                  timeout_ms, content_type, attachment_size, body)
            except Exception:  # pragma: no cover - handler bug guard
                import traceback
                traceback.print_exc()
                try:
                    _fastrpc.send_response(sid, cid, attempt, 2001,
                                           "python handler raised", "", b"")
                except Exception:
                    pass

        def _on_response(sid, cid, attempt, error_code, error_text, compress,
                         content_type, attachment_size, body):
            h = self._response_handlers.get(sid)
            if h is not None:
                try:
                    h(sid, cid, attempt, error_code, error_text, compress,
                      content_type, attachment_size, body)
                except Exception:  # pragma: no cover
                    import traceback
                    traceback.print_exc()

        _fastrpc.set_response_handler(_on_response)

        @TASK_CB
        def _on_timer(arg):
            token = arg or 0
            with self._timer_lock:
                fn = self._timer_cbs.pop(token, None)
            if fn is not None:
                try:
                    fn()
                except Exception:  # pragma: no cover
                    import traceback
                    traceback.print_exc()

        self._cb_message = _on_message
        self._cb_failed = _on_failed
        self._cb_accepted = _on_accepted
        self._cb_timer = _on_timer
        self._cb_request = _on_request
        self._cb_response = _on_response

    # ---- sockets ----

    def listen(self, addr: str, port: int, on_message, on_failed=None,
               native_echo: bool = False) -> tuple[int, int]:
        sid = ctypes.c_uint64()
        bound = ctypes.c_int()
        rc = core.brpc_listen(addr.encode(), port, self._cb_message,
                              self._cb_failed, self._cb_accepted, None,
                              1 if native_echo else 0, ctypes.byref(sid),
                              ctypes.byref(bound))
        if rc != 0:
            raise OSError(f"listen on {addr}:{port} failed")
        with self._lock:
            self._handlers[sid.value] = (on_message, on_failed)
        return sid.value, bound.value

    def connect(self, host: str, port: int, on_message, on_failed=None) -> int:
        if fault.ENABLED and fault.hit("transport.connect", host=host,
                                       port=port) is not None:
            raise ConnectionError(
                f"injected connect refusal to {host}:{port}")
        sid = ctypes.c_uint64()
        rc = core.brpc_connect(host.encode(), port, self._cb_message,
                               self._cb_failed, None, ctypes.byref(sid))
        if rc != 0:
            raise ConnectionError(f"connect to {host}:{port} failed")
        with self._lock:
            self._handlers[sid.value] = (on_message, on_failed)
        return sid.value

    def listen_rpc(self, addr: str, port: int, on_message, on_failed=None,
                   on_request=None) -> tuple[int, int]:
        """Listen with the native unary fast path enabled: TRPC requests
        whose meta parses cleanly and whose method is registered
        (register_python_method) arrive pre-parsed at on_request(sid, hdr,
        body); everything else falls back to on_message."""
        if on_request is not None and not self._request_cb_installed:
            _fastrpc.set_request_handler(self._cb_request)
            self._request_cb_installed = True
        sid = ctypes.c_uint64()
        bound = ctypes.c_int()
        rc = core.brpc_listen_rpc(addr.encode(), port, self._cb_message,
                                  self._cb_failed, self._cb_accepted, None,
                                  ctypes.byref(sid), ctypes.byref(bound))
        if rc != 0:
            raise OSError(f"listen on {addr}:{port} failed")
        with self._lock:
            self._handlers[sid.value] = (on_message, on_failed)
            if on_request is not None:
                self._request_handlers[sid.value] = on_request
        return sid.value, bound.value

    def listen_rpc_h2(self, addr: str, port: int, on_message, bridge,
                      on_failed=None, on_request=None) -> tuple[int, int]:
        """listen_rpc + the NATIVE h2/gRPC data plane: accepted
        connections run framing/HPACK/flow control in C++ (net/h2.cc)
        and surface per-message events to `bridge`
        (rpc/h2_native.NativeH2Bridge)."""
        if on_request is not None and not self._request_cb_installed:
            _fastrpc.set_request_handler(self._cb_request)
            self._request_cb_installed = True
        self._ensure_h2_event_cb()
        sid = ctypes.c_uint64()
        bound = ctypes.c_int()
        rc = core.brpc_listen_rpc_h2(addr.encode(), port, self._cb_message,
                                     self._cb_failed, self._cb_accepted,
                                     None, ctypes.byref(sid),
                                     ctypes.byref(bound))
        if rc != 0:
            raise OSError(f"listen on {addr}:{port} failed")
        with self._lock:
            self._handlers[sid.value] = (on_message, on_failed)
            self._h2_bridges[sid.value] = bridge
            if on_request is not None:
                self._request_handlers[sid.value] = on_request
        return sid.value, bound.value

    def _ensure_h2_event_cb(self) -> None:
        if self._h2_cb_installed:
            return
        self._h2_cb_installed = True

        @H2_EVENT_CB
        def _on_h2_event(sid, stream_id, kind, service, service_len,
                         method, method_len, headers, headers_len,
                         body_iobuf, mflags, user):
            svc = ctypes.string_at(service, service_len).decode(
                "utf-8", "replace") if service_len else ""
            meth = ctypes.string_at(method, method_len).decode(
                "utf-8", "replace") if method_len else ""
            hdrs = ctypes.string_at(headers, headers_len) if headers_len \
                else b""
            body = None
            if body_iobuf:
                buf = IOBuf(handle=body_iobuf)  # owns; freed at GC
                body = buf.to_bytes()
            bridge = self._h2_bridges.get(sid)
            if bridge is None:
                return
            try:
                bridge.on_event(sid, stream_id, kind, svc, meth, hdrs,
                                body, mflags)
            except Exception:  # pragma: no cover - bridge bug guard
                import traceback
                traceback.print_exc()

        self._cb_h2_event = _on_h2_event      # pin for process lifetime
        core.brpc_h2_set_event_cb(_on_h2_event, None)

    def connect_rpc(self, host: str, port: int, on_message, on_failed=None,
                    on_response=None) -> int:
        """Connect with the pre-parsed response fast path (the C response
        trampoline from _fastrpc — zero ctypes on the per-response path)."""
        if fault.ENABLED and fault.hit("transport.connect", host=host,
                                       port=port) is not None:
            raise ConnectionError(
                f"injected connect refusal to {host}:{port}")
        sid = ctypes.c_uint64()
        rc = core.brpc_connect_rpc(
            host.encode(), port, self._cb_message, self._cb_failed,
            ctypes.cast(_fastrpc.response_cb_ptr(), RESPONSE_CB), None,
            ctypes.byref(sid))
        if rc != 0:
            raise ConnectionError(f"connect to {host}:{port} failed")
        with self._lock:
            self._handlers[sid.value] = (on_message, on_failed)
            if on_response is not None:
                self._response_handlers[sid.value] = on_response
        return sid.value

    # ---- in-socket TLS (rpc/tls_engine.py) ----

    def enable_tls(self, sid: int, context, server_side: bool,
                   server_hostname: str | None = None) -> None:
        """Switch `sid` into TLS mode: the native socket delivers raw
        ciphertext to a per-connection MemoryBIO engine and plaintext is
        re-injected into its parser; all outbound writes through this
        transport are encrypted.  Call before any traffic (right after
        connect, or from the accept hook)."""
        from brpc_tpu.rpc.tls_engine import TlsEngine
        eng = TlsEngine(sid, context, server_side, server_hostname)
        with self._lock:
            self._tls[sid] = eng
        core.brpc_socket_set_filter(sid, 1)
        if not server_side:
            eng.start()   # emit ClientHello

    def enable_tls_listener(self, listener_sid: int, context) -> None:
        """Every connection accepted by `listener_sid` is TLS-wrapped
        (server side) before its first byte parses."""
        with self._lock:
            self._tls_listener_ctx[listener_sid] = context

    def tls_engine(self, sid: int):
        return self._tls.get(sid)

    @staticmethod
    def _pack_trpc(meta: bytes, body: bytes) -> bytes:
        import struct
        return (b"TRPC" + struct.pack(">I", len(meta))
                + struct.pack(">Q", len(body)) + meta + body)

    @staticmethod
    def register_python_method(service: str, method: str) -> None:
        core.brpc_register_python_method(service.encode(), method.encode())

    @staticmethod
    def unregister_method(service: str, method: str) -> None:
        core.brpc_unregister_method(service.encode(), method.encode())

    @staticmethod
    def send_request(sid: int, cid: int, attempt: int, service: str,
                     method: str, timeout_ms: int, compress: int,
                     content_type: str, body: bytes) -> int:
        """Pack + write a TRPC request frame natively (no Python meta
        encode, no ctypes marshalling).  TLS connections pack in Python
        and ride the engine instead (the native writer would emit
        plaintext)."""
        if fault.ENABLED:
            rc, body = _apply_send_fault(sid, body)
            if rc is not None:
                return rc
        inst = Transport._instance
        eng = inst._tls.get(sid) if inst is not None else None
        if eng is not None:
            from brpc_tpu.rpc import meta as M
            m = M.RpcMeta(msg_type=M.MSG_REQUEST, correlation_id=cid,
                          attempt=attempt, service=service, method=method,
                          timeout_ms=timeout_ms or 0, compress_type=compress,
                          content_type=content_type or "")
            return eng.write_plain(
                Transport._pack_trpc(m.encode(), bytes(body)))
        with rpcz.stage("net.write", cid) as stg:
            if stg is not rpcz.NOOP_STAGE:
                stg.set(bytes=len(body))
            return _fastrpc.send_request(sid, cid, attempt, service, method,
                                         timeout_ms or 0, compress,
                                         content_type, body)

    @staticmethod
    def send_response(sid: int, cid: int, attempt: int, error_code: int,
                      error_text: str, content_type: str,
                      body: bytes) -> int:
        if fault.ENABLED:
            rc, body = _apply_send_fault(sid, body)
            if rc is not None:
                return rc
        inst = Transport._instance
        eng = inst._tls.get(sid) if inst is not None else None
        if eng is not None:
            from brpc_tpu.rpc import meta as M
            m = M.RpcMeta(msg_type=M.MSG_RESPONSE, correlation_id=cid,
                          attempt=attempt, error_code=error_code,
                          error_text=error_text or "",
                          content_type=content_type or "")
            return eng.write_plain(
                Transport._pack_trpc(m.encode(), bytes(body)))
        with rpcz.stage("net.write", cid) as stg:
            if stg is not rpcz.NOOP_STAGE:
                stg.set(bytes=len(body))
            return _fastrpc.send_response(sid, cid, attempt, error_code,
                                          error_text or "",
                                          content_type or "", body)

    def write_frame(self, sid: int, meta: bytes, body: bytes = b"",
                    body_iobuf: IOBuf | None = None) -> int:
        if fault.ENABLED:
            # CORRUPT mangles the META: the frame arrives, parses as
            # TRPC, fails decode at the peer and is discarded —
            # in-flight corruption the framing cannot catch
            rc, meta = _apply_send_fault(sid, meta)
            if rc is not None:
                return rc
        eng = self._tls.get(sid)
        if eng is not None:
            full = bytes(body)
            if body_iobuf is not None:
                full += body_iobuf.to_bytes()
            return eng.write_plain(self._pack_trpc(bytes(meta), full))
        with rpcz.stage("net.write") as stg:
            if stg is not rpcz.NOOP_STAGE:
                stg.set(bytes=len(meta) + len(body))
            return core.brpc_socket_write_frame(
                sid, meta, len(meta), body, len(body),
                body_iobuf.handle if body_iobuf is not None else None)

    def write_frames(self, sid: int, frames: list[tuple[bytes, bytes]]
                     ) -> int:
        """Write a run of (meta, body) frames as ONE socket write — one
        ctypes crossing and one write-stack push instead of N (the h2
        frame-coalescing story at the TRPC layer; the parser side
        already cuts multiple frames per buffer).  One rc for the whole
        run: ordering is preserved by the single write, and a failure
        means none/all-prefix delivery exactly like N sequential writes
        on a dead socket.  For SMALL frames: the coalesced payload is
        checked against the per-write EOVERCROWDED bound as one unit and
        is materialized contiguously — big bodies should go per-frame
        (the stream sender coalesces ticket frames only)."""
        with rpcz.stage("net.write") as stg:
            payload = b"".join(self._pack_trpc(bytes(m), bytes(b))
                               for m, b in frames)
            if stg is not rpcz.NOOP_STAGE:
                stg.set(bytes=len(payload), frames=len(frames))
            return self.write_raw(sid, payload)

    def write_raw(self, sid: int, data: bytes) -> int:
        if fault.ENABLED:
            rc, data = _apply_send_fault(sid, data)
            if rc is not None:
                return rc
        eng = self._tls.get(sid)
        if eng is not None:
            return eng.write_plain(bytes(data))
        return core.brpc_socket_write_raw(sid, data, len(data), None)

    def set_protocol(self, sid: int, kind: int) -> None:
        """Pre-select the wire protocol a connection's inbound bytes use
        (h2 / mongo / raw streaming clients whose first inbound bytes are
        ambiguous)."""
        core.brpc_socket_set_protocol(sid, kind)

    def close(self, sid: int, err: int = 0) -> None:
        core.brpc_socket_set_failed(sid, err)

    def alive(self, sid: int) -> bool:
        return bool(core.brpc_socket_alive(sid))

    def socket_stats(self, sid: int) -> dict | None:
        nread = ctypes.c_int64()
        nwritten = ctypes.c_int64()
        nmsg = ctypes.c_int64()
        ip = ctypes.create_string_buffer(48)
        port = ctypes.c_int()
        rc = core.brpc_socket_stats(sid, ctypes.byref(nread),
                                    ctypes.byref(nwritten), ctypes.byref(nmsg),
                                    ip, 48, ctypes.byref(port))
        if rc != 0:
            return None
        return {"bytes_read": nread.value, "bytes_written": nwritten.value,
                "messages_read": nmsg.value,
                "remote": f"{ip.value.decode()}:{port.value}"}

    # ---- timers (native TimerThread) ----

    def schedule(self, delay_s: float, fn: Callable[[], None]) -> tuple[int, int]:
        """Returns (native_timer_id, token) for cancel()."""
        with self._timer_lock:
            token = self._timer_token
            self._timer_token += 1
            self._timer_cbs[token] = fn
        tid = core.brpc_timer_add(self._cb_timer, ctypes.c_void_p(token),
                                  int(delay_s * 1e6))
        return tid, token

    def cancel(self, timer: tuple[int, int]) -> bool:
        tid, token = timer
        ok = core.brpc_timer_cancel(tid) == 0
        with self._timer_lock:
            self._timer_cbs.pop(token, None)
        return ok
