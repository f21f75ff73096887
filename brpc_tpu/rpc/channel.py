"""Channel — the client endpoint (reference channel.{h,cpp}; SURVEY.md §2.5).

Keeps the reference's client machinery shapes:
  * Channel.init("host:port" | "proto://cluster", lb) — naming service +
    load balancer resolve per call (channel.h:161).
  * CallMethod drives a per-call state machine on the Controller:
    (correlation_id, attempt) versioning so stale attempts can't complete a
    call twice (the bthread_id range trick, controller.h:692-703), retries
    re-issued on a different server with failed ones excluded
    (excluded_servers.h), backup requests racing a second attempt after
    backup_request_ms (channel.cpp:403-409), one overall deadline timer.
  * SocketMap: endpoint -> native socket reuse (socket_map.h:147).
"""
from __future__ import annotations

import itertools
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

from brpc_tpu import errors, rpcz
from brpc_tpu.butil.endpoint import EndPoint, str2endpoint
from brpc_tpu.rpc import meta as M
from brpc_tpu.rpc.controller import Controller, OneShotEvent
from brpc_tpu.rpc.serialization import compress, decompress, get_serializer
from brpc_tpu.rpc.transport import MSG_TRPC, Transport

_cid_counter = itertools.count(1)


@dataclass
class ChannelOptions:
    timeout_ms: int = 500                  # same default as ChannelOptions
    max_retry: int = 3
    backup_request_ms: int = -1            # <0 disables
    connection_type: str = "single"        # single | pooled | short
    protocol: str = "trpc"
    compress_type: int = M.COMPRESS_NONE
    load_balancer: str = ""                # "" = single server
    auth: Optional[Any] = None             # Authenticator
    retry_policy: Optional[Any] = None
    # availability floor for circuit breaking (ClusterRecoverPolicy);
    # None = isolate freely (single-server channels have no cluster)
    cluster_recover_policy: Optional[Any] = None
    # In-socket TLS (rpc/tls_engine.py): an ssl.SSLContext for client-side
    # TLS to this channel's servers.  Registered per endpoint on the
    # shared SocketMap (mirrors the reference's per-Channel
    # ChannelSSLOptions, socket.h SSL integration).
    tls_context: Optional[Any] = None
    tls_server_hostname: Optional[str] = None


class RetryPolicy:
    """DoRetry(cntl) — reference retry_policy.h semantics: retry connection
    errors, not deadline misses."""

    RETRYABLE = {errors.EFAILEDSOCKET, errors.EOVERCROWDED, errors.EEOF,
                 errors.ECONNREFUSED, errors.EINTERNAL}

    def do_retry(self, cntl: Controller) -> bool:
        return cntl.error_code in self.RETRYABLE


DEFAULT_RETRY_POLICY = RetryPolicy()


class _ClientConn:
    __slots__ = ("sid", "endpoint", "tls")

    def __init__(self, sid: int, endpoint: EndPoint):
        self.sid = sid
        self.endpoint = endpoint
        self.tls = False   # set by SocketMap._connect when TLS-wrapped


class SocketMap:
    """endpoint -> client connections (reference socket_map.h:147 +
    ConnectionType, protocol.h:161-180).  Three reuse schemes:

      * single — one shared multiplexed connection per endpoint (our TRPC
        framing correlates by id, so one socket carries any number of
        in-flight calls; the reference default for baidu_std).
      * pooled — a free-list of connections per endpoint; a call checks one
        out for its attempt and returns it at completion (the reference
        scheme for non-multiplexable protocols; here it also isolates large
        transfers from head-of-line blocking on the shared socket).
      * short  — a fresh connection per attempt, closed at call end.

    All client connections share one response handler (CallManager)."""

    _instance = None
    _instance_lock = threading.Lock()

    @classmethod
    def instance(cls) -> "SocketMap":
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    def __init__(self):
        self._lock = threading.Lock()
        # ep -> (ssl_context, server_hostname): connections to these
        # endpoints are TLS-wrapped at connect time (in-socket TLS)
        self._tls_eps: dict[EndPoint, tuple] = {}
        self._conns: dict[EndPoint, _ClientConn] = {}
        self._sid_to_ep: dict[int, EndPoint] = {}
        self._pool: dict[EndPoint, list[_ClientConn]] = {}
        self._pooled_sids: dict[int, _ClientConn] = {}
        self._closing: set[int] = set()   # deliberate local closes

    def _connect(self, ep: EndPoint) -> _ClientConn:
        mgr = CallManager.instance()
        # unix-scheme endpoints carry the path in .host; the native layer
        # selects AF_UNIX on the "unix:" prefix (butil/unix_socket role)
        host = f"unix:{ep.host}" if ep.scheme == "unix" else ep.host
        sid = Transport.instance().connect_rpc(
            host, ep.port, mgr.on_message, self._on_socket_failed,
            on_response=mgr.on_fast_response)
        tls = self._tls_eps.get(ep)
        if tls is not None:
            # wrap BEFORE returning: no caller may write plaintext first
            Transport.instance().enable_tls(
                sid, tls[0], server_side=False, server_hostname=tls[1])
        with self._lock:
            self._sid_to_ep[sid] = ep
        conn = _ClientConn(sid, ep)
        conn.tls = tls is not None
        return conn

    def set_endpoint_tls(self, ep, context, server_hostname=None) -> None:
        with self._lock:
            self._tls_eps[ep] = (context, server_hostname)

    def get_connection(self, ep: EndPoint) -> _ClientConn:
        with self._lock:
            c = self._conns.get(ep)
            want_tls = ep in self._tls_eps
            if c is not None and getattr(c, "tls", False) != want_tls:
                # TLS was registered for this endpoint AFTER a plaintext
                # connection was cached (or vice versa): reusing it would
                # send bytes in the wrong cryptographic mode — drop it
                # and reconnect in the registered mode
                self._conns.pop(ep, None)
                stale, c = c, None
            else:
                stale = None
            if c is not None:
                return c
        if stale is not None:
            self.close_quietly(stale.sid)
        c = self._connect(ep)
        with self._lock:
            cur = self._conns.get(ep)
            if cur is None:
                self._conns[ep] = c
        # NOTE: never close (or do anything that can fire socket callbacks)
        # while holding _lock — the native SetFailed invokes on_failed
        # synchronously on this thread, and _on_socket_failed re-takes _lock.
        if cur is not None:
            # lost the race; keep the established one, drop ours
            self.close_quietly(c.sid)
            return cur
        return c

    # ---- pooled scheme ----

    def get_pooled(self, ep: EndPoint) -> _ClientConn:
        t = Transport.instance()
        while True:
            with self._lock:
                free = self._pool.get(ep)
                c = free.pop() if free else None
            if c is None:
                return self._connect(ep)
            if t.alive(c.sid):
                return c
            # died while idle in the pool; try the next one

    def return_pooled(self, c: _ClientConn) -> None:
        if not Transport.instance().alive(c.sid):
            return
        with self._lock:
            self._pooled_sids[c.sid] = c
            self._pool.setdefault(c.endpoint, []).append(c)

    # ---- short scheme ----

    def make_short(self, ep: EndPoint) -> _ClientConn:
        return self._connect(ep)

    def close_quietly(self, sid: int) -> None:
        """Deliberate local close — not a server failure: skips the
        health-check / circuit-breaker marking that real failures get."""
        with self._lock:
            self._closing.add(sid)
        Transport.instance().close(sid)

    def _on_socket_failed(self, sid: int, err: int) -> None:
        with self._lock:
            deliberate = sid in self._closing
            self._closing.discard(sid)
            ep = self._sid_to_ep.pop(sid, None)
            if ep is not None and self._conns.get(ep) is not None and \
                    self._conns[ep].sid == sid:
                del self._conns[ep]
            pc = self._pooled_sids.pop(sid, None)
            if pc is not None and ep is not None:
                free = self._pool.get(ep)
                if free and pc in free:
                    free.remove(pc)
        CallManager.instance().on_socket_failed(sid, err)
        # streams riding the dead connection are unrecoverable: close
        # them so their handlers learn now (ISSUE 8 — the router's
        # replica failover keys off on_closed, and a silently-dead
        # peer sends no CLOSE frame)
        from brpc_tpu.rpc.stream import StreamRegistry
        StreamRegistry.instance().on_socket_failed(sid)
        # health check + LB notification (policy layer)
        from brpc_tpu.policy.health_check import on_connection_failed
        if ep is not None and not deliberate:
            on_connection_failed(ep)

    def evict(self, ep: EndPoint, sid: int) -> None:
        """Drop the cached single-connection mapping for `ep` iff it
        still points at `sid` — no close, no failure marking.  Used when
        a write already failed on `sid`: the socket is dying, but its
        failed-callback cleanup may still be in flight on another
        thread, and a retry that re-checks out the same dying
        connection burns every attempt on it (found by chaos injection,
        tests/test_chaos.py mid-call reset)."""
        with self._lock:
            c = self._conns.get(ep)
            if c is not None and c.sid == sid:
                del self._conns[ep]

    def drop(self, ep: EndPoint) -> None:
        with self._lock:
            c = self._conns.pop(ep, None)
            free = self._pool.pop(ep, [])
            for fc in free:
                self._pooled_sids.pop(fc.sid, None)
        if c is not None:
            self.close_quietly(c.sid)
        for fc in free:
            self.close_quietly(fc.sid)

    def pooled_count(self, ep: EndPoint) -> int:
        with self._lock:
            return len(self._pool.get(ep, ()))


class _CallState:
    __slots__ = ("cntl", "channel", "meta_template", "body", "done",
                 "deadline_timer", "backup_timer", "sids", "sid_attempts",
                 "tried_servers", "pooled_conns", "short_conns", "rail_obj",
                 "rail_tickets", "rail_fallback_cache", "span")

    def __init__(self, cntl, channel, meta_template, body, done,
                 span=rpcz.NULL_SPAN):
        self.cntl = cntl
        self.span = span      # the call's client span (rpcz)
        self.channel = channel
        self.meta_template = meta_template
        self.body = body
        self.done = done
        self.deadline_timer = None
        self.backup_timer = None
        self.sids: set[int] = set()
        # sid -> the attempt number that wrote on it, recorded at bind
        # time: the failed-socket callback retries a call only if the
        # failed socket still carries its CURRENT attempt (a stale
        # socket's death must not preempt a live retry chain)
        self.sid_attempts: dict[int, int] = {}
        self.tried_servers: list[EndPoint] = []
        # device-array payload deferred to _issue: staged over ICI when the
        # selected server advertises a device (ici/rail.py), host-serialized
        # only as the fallback
        self.rail_obj = None
        self.rail_tickets: list[str] = []
        self.rail_fallback_cache = None  # (body, tensor_header) once encoded
        # connections this call checked out (pooled) or owns (short); given
        # back / closed at completion — late replies are matched by cid, so
        # recycling before a stale attempt answers is safe
        self.pooled_conns: list[_ClientConn] = []
        self.short_conns: list[_ClientConn] = []


class CallManager:
    """Global pending-call table keyed by correlation id; completes calls
    exactly once across responses/timeouts/socket failures/retries (the role
    OnVersionedRPCReturned plays, controller.cpp:593)."""

    _instance = None
    _instance_lock = threading.Lock()

    @classmethod
    def instance(cls) -> "CallManager":
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    def __init__(self):
        self._lock = threading.Lock()
        self._pending: dict[int, _CallState] = {}
        self._by_sid: dict[int, set[int]] = {}

    # ---- registration ----

    def register(self, st: _CallState) -> None:
        with self._lock:
            self._pending[st.cntl.correlation_id] = st

    def bind_socket(self, cid: int, sid: int,
                    attempt: int = 0) -> None:
        with self._lock:
            st = self._pending.get(cid)
            if st is not None:
                st.sids.add(sid)
                # latest attempt wins: a retry re-using the same healthy
                # socket moves the sid's ownership to the new attempt
                st.sid_attempts[sid] = attempt
                self._by_sid.setdefault(sid, set()).add(cid)

    def _unregister(self, cid: int) -> Optional[_CallState]:
        with self._lock:
            st = self._pending.pop(cid, None)
            if st is not None:
                for sid in st.sids:
                    s = self._by_sid.get(sid)
                    if s is not None:
                        s.discard(cid)
                        if not s:
                            del self._by_sid[sid]
            return st

    # ---- events ----

    def on_message(self, sid: int, kind: int, meta_bytes: bytes, body) -> None:
        if kind != MSG_TRPC:
            return
        try:
            meta = M.RpcMeta.decode(meta_bytes)
        except ValueError:
            return
        if meta.msg_type == M.MSG_RESPONSE:
            self._on_response(meta, body)
        elif meta.msg_type in (M.MSG_STREAM_DATA, M.MSG_STREAM_FEEDBACK,
                               M.MSG_STREAM_CLOSE):
            from brpc_tpu.rpc.stream import StreamRegistry
            StreamRegistry.instance().on_frame(sid, meta, body)

    def on_fast_response(self, sid: int, cid: int, attempt: int,
                         error_code: int, error_text: str, compress: int,
                         content_type: str, attachment_size: int,
                         body) -> None:
        """Natively pre-parsed response (net/rpc.h via _fastrpc): no
        Python TLV walk; the body is an IOBuf-backed read-only memoryview
        (zero copy — pins the blocks while referenced).  Fast metas carry
        cid/attempt/error/compress/content_type/attachment_size — anything
        richer (streams, tensor headers, user fields) arrives via
        on_message with a full decode."""
        meta = M.RpcMeta(
            msg_type=M.MSG_RESPONSE,
            correlation_id=cid,
            attempt=attempt,
            error_code=error_code,
            error_text=error_text,
            compress_type=compress,
            content_type=content_type,
            attachment_size=attachment_size,
        )
        self._on_response(meta, body)

    def _on_response(self, meta: M.RpcMeta, body) -> None:
        with self._lock:
            st = self._pending.get(meta.correlation_id)
        with rpcz.span_scope(st.span if st is not None else rpcz.NULL_SPAN), \
                rpcz.stage("rpc.client.on_response", meta.correlation_id):
            self._complete(st, meta, body)

    def _complete(self, st: Optional[_CallState], meta: M.RpcMeta,
                  body) -> None:
        """A response frame against the pending call it names: retry,
        fail or decode, then ``_finish``."""
        if st is None:
            # stale attempt after completion — dropped; a rail ticket riding
            # it must be freed now, not left to the registry TTL
            if meta.user_fields and meta.user_fields.get(M.F_TICKET):
                from brpc_tpu.ici import rail
                rail.withdraw(meta.user_fields[M.F_TICKET])
            return
        cntl = st.cntl
        if meta.error_code != 0:
            # Stale-attempt errors must not touch the live call: only the
            # current attempt may drive retry/completion (the bthread_id
            # version check of the reference).  Success from ANY attempt
            # wins — that's what makes backup requests useful.
            if meta.attempt < cntl.current_attempt:
                return
            if meta.user_fields:
                # fields attached to FAILED completions surface too (the
                # reference packs response user fields on errors as well)
                cntl.response_user_fields = \
                    M.strip_reserved_user_fields(meta.user_fields)
            # versioned, like every other failure path: a concurrent
            # retry claim (failed-write / failed-socket) may already own
            # a newer attempt, and this error response is then stale —
            # it must neither stomp the claimed attempt's state nor
            # finish the call under the live attempt
            if not cntl.set_failed_if_current(meta.attempt,
                                              meta.error_code,
                                              meta.error_text):
                return
            if st.channel._should_retry(st, meta.attempt):
                return  # re-issued under the same cid, next attempt
            if cntl.current_attempt > meta.attempt or cntl.completed:
                return  # a racing path claimed the retry first
            self._finish(st)
            return
        # success: decode body
        rail_ticket = meta.user_fields.get(M.F_TICKET) \
            if meta.user_fields else None
        if rail_ticket is not None:
            # response payload rode ICI: claim the device arrays parked in
            # the rail registry — no body bytes exist to decode
            from brpc_tpu.ici import rail
            try:
                cntl.reset_for_retry()
                cntl.response = rail.claim(rail_ticket)
                cntl.response_attachment = b""
            except Exception as e:
                cntl.set_failed(errors.ERESPONSE,
                                f"cannot claim rail payload: {e}")
            self._finish(st)
            return
        try:
            # fast-path bodies arrive as IOBuf-backed memoryviews (zero
            # copy, _fastrpc FastBody); slicing memoryviews stays zero-copy
            raw = body if isinstance(body, (bytes, memoryview)) \
                else body.to_bytes()
            att_size = meta.attachment_size
            payload = raw[: len(raw) - att_size] if att_size else raw
            # attachments keep the documented bytes contract (handlers
            # .decode()/.startswith() them); materialize off the view
            cntl.response_attachment = bytes(raw[len(raw) - att_size:]) \
                if att_size else b""
            payload = decompress(payload, meta.compress_type)
            serializer = getattr(cntl, "_response_serializer", None) or \
                get_serializer(meta.content_type or "raw")
            cntl.reset_for_retry()
            cntl.response = serializer.decode(payload, meta.tensor_header)
            if meta.user_fields:
                # surface server-set user fields, minus transport keys
                cntl.response_user_fields = \
                    M.strip_reserved_user_fields(meta.user_fields)
            if meta.stream_id and cntl._stream is not None:
                sbuf = meta.user_fields.get(M.F_SBUF)
                if sbuf:
                    cntl._stream.peer_buf_size = int(sbuf)
                sdev = meta.user_fields.get(M.F_SDEV)
                if sdev:
                    # the server's EXPLICIT stream advertisement wins
                    # over the pre-bind unary-map guess — the accepting
                    # handler may have picked a different device than
                    # the server-wide ici_device
                    from brpc_tpu.ici import rail
                    dev = rail.device_from_wire(sdev)
                    if dev is not None:
                        cntl._stream.peer_device = dev
                cntl._stream.set_remote(meta.stream_id)
        except Exception as e:  # bad response
            cntl.set_failed(errors.ERESPONSE, f"cannot decode response: {e}")
        self._finish(st)

    def on_socket_failed(self, sid: int, err: int) -> None:
        with self._lock:
            cids = list(self._by_sid.pop(sid, ()))
            states = [(self._pending[c],
                       self._pending[c].sid_attempts.get(sid, 0))
                      for c in cids if c in self._pending]
        for st, owner in states:
            # the failed socket carries attempt `owner`.  If a newer
            # attempt already owns the call (the failed-write path
            # claimed the retry first, or a backup request is in
            # flight), this death is STALE: acting on it would stomp
            # the live attempt's state and burn a second retry —
            # chaos-pinned as the cluster-retry flake where the doomed
            # extra retry excluded every server and failed a call whose
            # live attempt was about to succeed.  The versioned
            # set_failed runs FIRST (the retry policy reads error_code)
            # and doubles as the staleness gate.
            if not st.cntl.set_failed_if_current(
                    owner, errors.EFAILEDSOCKET,
                    f"socket failed (errno {err})"):
                continue
            if st.channel._should_retry(st, owner):
                continue
            if st.cntl.current_attempt == owner and not st.cntl.completed:
                self._finish(st)

    def on_deadline(self, cid: int) -> None:
        self._fail_pending(cid, errors.ERPCTIMEDOUT, "deadline exceeded",
                           cancel_deadline=False)

    def cancel(self, cid: int) -> bool:
        """StartCancel analog (reference example/cancel_c++): complete the
        call NOW with ECANCELED; a late server response is dropped by the
        (correlation_id, attempt) versioning like any stale attempt.
        Returns False if the call already completed (including losing the
        race to a concurrent success)."""
        return self._fail_pending(cid, errors.ECANCELED,
                                  "canceled by caller")

    def _fail_pending(self, cid: int, code: int, text: str,
                      cancel_deadline: bool = True) -> bool:
        """Shared deadline/cancel path.  The error is applied INSIDE
        _finish, after winning the exactly-once completion race — setting
        it first would corrupt a concurrently-arriving success response's
        state (and misreport the failure as applied)."""
        with self._lock:
            st = self._pending.get(cid)
        if st is None:
            return False
        return self._finish(st, cancel_deadline=cancel_deadline,
                            fail=(code, text))

    def _finish(self, st: _CallState, cancel_deadline: bool = True,
                fail: tuple[int, str] | None = None) -> bool:
        if not st.cntl._try_complete():
            return False
        if fail is not None:
            st.cntl.set_failed(*fail)
        self._unregister(st.cntl.correlation_id)
        t = Transport.instance()
        if cancel_deadline and st.deadline_timer is not None:
            t.cancel(st.deadline_timer)
        if st.backup_timer is not None:
            t.cancel(st.backup_timer)
        cntl = st.cntl
        import time
        cntl.latency_us = int(time.monotonic() * 1e6) - cntl._start_us
        span = st.span
        if span is not rpcz.NULL_SPAN:
            span.remote_side = cntl.remote_side
            span.error_code = cntl.error_code
            rpcz.submit(span)
        if st.rail_tickets:
            # free staged payloads of attempts the server never claimed
            # (timeouts, failed sockets); claim is an atomic pop, so a
            # concurrently-claiming server wins and this no-ops
            from brpc_tpu.ici import rail
            for ticket in st.rail_tickets:
                rail.withdraw(ticket)
            st.rail_tickets.clear()
        # recycle per-call connections (pooled back to the free list,
        # short closed — ConnectionType semantics, protocol.h:161-180)
        if st.pooled_conns:
            smap = SocketMap.instance()
            for c in st.pooled_conns:
                smap.return_pooled(c)
            st.pooled_conns.clear()
        if st.short_conns:
            smap = SocketMap.instance()
            for c in st.short_conns:
                smap.close_quietly(c.sid)
            st.short_conns.clear()
        st.channel._on_call_end(st)
        if st.done is not None:
            try:
                st.done(cntl)
            except Exception:  # pragma: no cover
                import traceback
                traceback.print_exc()
        if cntl._done_event is not None:
            cntl._done_event.set()
        return True


class Channel:
    """Client channel to one server or a cluster (with a load balancer)."""

    def __init__(self, address: str | EndPoint | None = None,
                 options: ChannelOptions | None = None, **kw):
        self.options = options or ChannelOptions(**kw)
        self._lb = None
        self._ns_thread = None
        self._endpoint: Optional[EndPoint] = None
        if address is not None:
            self.init(address, self.options.load_balancer)

    # reference Channel::Init(addr, lb_name, opts)
    def init(self, address: str | EndPoint, load_balancer: str = "") -> "Channel":
        if isinstance(address, EndPoint):
            self._endpoint = address
            return self
        if "://" in address:
            from brpc_tpu.policy.naming import start_naming_service
            from brpc_tpu.policy.load_balancer import create_load_balancer
            self._lb = create_load_balancer(load_balancer or "rr")
            self._ns_thread = start_naming_service(address, self._lb)
        else:
            self._endpoint = str2endpoint(address)
        return self

    # ---- server selection (LB hook) ----

    def _select_server(self, st: _CallState) -> Optional[EndPoint]:
        if self._lb is not None:
            # exact exclusion of every tried server (the ExcludedServers
            # role, excluded_servers.h; a plain set — no capacity bound —
            # so high-retry calls never revisit a failed replica)
            return self._lb.select_server(
                exclude=set(st.tried_servers),
                request_code=st.cntl.request_code)
        return self._endpoint

    def _on_call_end(self, st: _CallState) -> None:
        if not st.tried_servers:
            return
        # Every select_server() gets exactly one feedback (LA balancers
        # track inflight); losing/failed attempts report as socket errors.
        if self._lb is not None:
            for ep in st.tried_servers[:-1]:
                self._lb.feedback(ep, errors.EFAILEDSOCKET, 0)
            self._lb.feedback(st.tried_servers[-1], st.cntl.error_code,
                              st.cntl.latency_us)
        # feed the circuit breaker (reference OnCallEnd, circuit_breaker.h);
        # the cluster guard lets ClusterRecoverPolicy veto isolation when
        # too few healthy servers would remain (cluster_recover_policy.h)
        from brpc_tpu.policy.circuit_breaker import global_breaker
        breaker = global_breaker()
        guard = self._cluster_guard()
        for ep in st.tried_servers[:-1]:
            if ep.scheme == "tcp":
                breaker.on_call_end(ep, errors.EFAILEDSOCKET,
                                    cluster=guard)
        last = st.tried_servers[-1]
        if last.scheme == "tcp":
            breaker.on_call_end(last, st.cntl.error_code,
                                latency_us=st.cntl.latency_us,
                                cluster=guard)

    def _cluster_guard(self):
        """ClusterRecoverPolicy guard bound to this channel's server view
        (None for single-server channels — there is no cluster to
        protect)."""
        if self._lb is None:
            return None
        policy = self.options.cluster_recover_policy
        if policy is None:
            return None
        from brpc_tpu.policy.cluster_recover_policy import \
            _ChannelClusterGuard
        return _ChannelClusterGuard(policy, self._lb)

    # ---- the call path ----

    def call(self, service: str, method_name: str, request: Any = b"",
             cntl: Controller | None = None,
             done: Callable[[Controller], None] | None = None,
             serializer: str = "raw", response_serializer: str | None = None,
             _sync_join: bool = False) -> Controller:
        """Issue an RPC.  With done=None this is async-with-join: the
        returned controller has an event; use .join() or call_sync()."""
        cntl = cntl or Controller()
        cid = cntl.correlation_id = next(_cid_counter)
        span = rpcz.child_span("client", service, method_name)
        with rpcz.span_scope(span), rpcz.stage("rpc.client.call", cid) as stg:
            if stg is not rpcz.NOOP_STAGE:
                stg.set(bytes=rpcz.payload_bytes(request))
            return self._start(service, method_name, request, cntl, done,
                               serializer, response_serializer, span,
                               _sync_join)

    def call_sync(self, service: str, method_name: str, request: Any = b"",
                  serializer: str = "raw", cntl: Controller | None = None,
                  response_serializer: str | None = None) -> Any:
        cntl = cntl or Controller()
        cid = cntl.correlation_id = next(_cid_counter)
        span = rpcz.child_span("client", service, method_name)
        # the stage ends when the reply is in the caller's hands
        with rpcz.span_scope(span), rpcz.stage("rpc.client.call", cid) as stg:
            if stg is not rpcz.NOOP_STAGE:
                stg.set(bytes=rpcz.payload_bytes(request))
            self._start(service, method_name, request, cntl, None,
                        serializer, response_serializer, span,
                        sync_join=True)
            cntl.join()
        cntl.raise_if_failed()
        return cntl.response

    def _start(self, service: str, method_name: str, request: Any,
               cntl: Controller, done, serializer: str,
               response_serializer: str | None, span,
               sync_join: bool = False) -> Controller:
        """Build the call's state and send its first attempt.  The
        caller has named the call (``cntl.correlation_id``) and made its
        client span."""
        import time
        opts = self.options
        if cntl.timeout_ms is None:
            cntl.timeout_ms = opts.timeout_ms
        if cntl.max_retry is None:
            cntl.max_retry = opts.max_retry
        if cntl.backup_request_ms is None:
            cntl.backup_request_ms = opts.backup_request_ms
        cntl._start_us = int(time.monotonic() * 1e6)
        if done is None:
            cntl._done_event = OneShotEvent()

        ser = get_serializer(serializer)
        rail_obj = None
        if ser.name == "tensor" and not cntl.request_attachment:
            # attachments ride the socket body; mixing them with a railed
            # payload would drop them — such calls stay on the host path
            from brpc_tpu.ici import rail
            if rail.railable(request):
                # Defer serialization: the payload may ride ICI instead of
                # the socket, decided per attempt once the server is known
                # (the CutFromIOBufList slot — socket.cpp:1751-1757).
                rail_obj = request
        if rail_obj is None:
            body, tensor_header = ser.encode(request)
            body = compress(body, cntl.compress_type)
        else:
            body, tensor_header = b"", b""
        meta = M.RpcMeta(
            msg_type=M.MSG_REQUEST,
            correlation_id=cntl.correlation_id,
            service=service,
            method=method_name,
            compress_type=cntl.compress_type,
            timeout_ms=cntl.timeout_ms or 0,
            content_type=ser.name,
            tensor_header=tensor_header,
        )
        if cntl.user_fields:
            # caller-supplied opaque metadata (request_user_fields slot);
            # copied so a reused Controller can't mutate an issued frame.
            # ONE shared validation (meta.normalize_user_fields): clean
            # str keys, reserved transport keys rejected — a spoofed
            # rail ticket would make the server claim device blocks
            # instead of decoding the body
            meta.user_fields.update(
                M.normalize_user_fields(cntl.user_fields))
        # the client-side response serializer: typed instances (e.g. a
        # PbSerializer bound to a generated message class) must decode the
        # response locally — the wire's content_type can only name the
        # generic codec.  Deliberately NOT a user field: nothing consumes
        # it on the wire, and any user field disqualifies the call from
        # the native fast-send path.
        if response_serializer:
            cntl._response_serializer = get_serializer(response_serializer)
        # credential is generated per ATTEMPT in _issue (replay-tracking
        # authenticators reject reused nonces), not here
        if cntl.request_attachment:
            meta.attachment_size = len(cntl.request_attachment)
            body = body + cntl.request_attachment

        # stream riding this RPC (stream_create was called with this cntl)
        stream = getattr(cntl, "_stream", None)
        if stream is not None:
            meta.stream_id = stream.stream_id
            meta.user_fields[M.F_SBUF] = str(stream.max_buf_size)
            if stream.device is not None:
                # advertise OUR tensor receive device (rail settings);
                # the embedded process token scopes it to this process
                from brpc_tpu.ici import rail
                meta.user_fields[M.F_SDEV] = rail.device_advert(
                    stream.device)

        # the client span is the callee's parent; the sampled bit rides
        # a meta flag so the callee inherits the trace-root decision
        # instead of re-rolling.  With rpcz off the null span reads as
        # zeros and the frame stays on the native fast path.
        meta.trace_id = cntl.trace_id = span.trace_id
        meta.span_id = cntl.span_id = span.span_id
        if span.trace_id and span.sampled:
            meta.flags |= M.FLAG_TRACE_SAMPLED
        span.request_size = len(body)

        st = _CallState(cntl, self, meta, body, done, span)
        st.rail_obj = rail_obj
        mgr = CallManager.instance()
        mgr.register(st)

        t = Transport.instance()
        if cntl.timeout_ms and cntl.timeout_ms > 0:
            if sync_join:
                # call_sync joins immediately: the joining thread IS the
                # deadline timer (join() computes the remaining budget from
                # _start_us and fires on_deadline itself) — saves a native
                # timer arm+cancel per call on the hot path.  Plain call()
                # users may never join, so they keep the native timer.
                cntl._sync_deadline = True
            else:
                cid = cntl.correlation_id
                st.deadline_timer = t.schedule(cntl.timeout_ms / 1e3,
                                               lambda: mgr.on_deadline(cid))
        if cntl.backup_request_ms and cntl.backup_request_ms > 0:
            st.backup_timer = t.schedule(cntl.backup_request_ms / 1e3,
                                         lambda: self._issue_backup(st))
        self._issue(st)
        return cntl

    def _issue(self, st: _CallState) -> None:
        """Send the current attempt.  On immediate failure, walk the retry
        path (IssueRPC, controller.cpp:1042)."""
        cntl = st.cntl
        mgr = CallManager.instance()
        # the attempt number THIS _issue call issues: every failure
        # below is versioned against it, so a stale path (a concurrent
        # retry already owns a newer attempt) can neither overwrite the
        # live attempt's state nor finish the call under it
        attempt = cntl.current_attempt
        ep = self._select_server(st)
        if ep is None:
            if cntl.set_failed_if_current(attempt, errors.ENODATA,
                                          "no available server"):
                mgr._finish(st)
            return
        st.tried_servers.append(ep)
        cntl.remote_side = str(ep)
        try:
            smap = SocketMap.instance()
            if self.options.tls_context is not None:
                # NS/LB channels resolve endpoints dynamically: register
                # TLS for whichever server this attempt selected BEFORE
                # the connection is (possibly) created
                smap.set_endpoint_tls(
                    ep, self.options.tls_context,
                    self.options.tls_server_hostname or ep.host)
            ctype = self.options.connection_type
            if ctype == "pooled":
                conn = smap.get_pooled(ep)
                st.pooled_conns.append(conn)
            elif ctype == "short":
                conn = smap.make_short(ep)
                st.short_conns.append(conn)
            else:
                conn = smap.get_connection(ep)
        except (ConnectionError, OSError):
            # versioned set BEFORE the retry check (the retry policy
            # reads error_code); a False return means a newer attempt
            # owns the call and this refusal is stale
            if not cntl.set_failed_if_current(attempt, errors.ECONNREFUSED,
                                              f"cannot connect to {ep}"):
                return
            if self._should_retry(st, attempt):
                return
            if cntl.current_attempt == attempt and not cntl.completed:
                mgr._finish(st)
            return
        meta = st.meta_template
        meta.attempt = cntl.current_attempt
        if st.rail_obj is not None:
            self._prepare_rail_attempt(st, ep)
        if self.options.auth is not None:
            # fresh credential per attempt: replay-tracking authenticators
            # (HmacAuthenticator) reject a reused nonce, so retries and
            # backup requests must not resend the first attempt's
            meta.auth = self.options.auth.generate_credential()
        mgr.bind_socket(cntl.correlation_id, conn.sid, attempt)
        stream = getattr(cntl, "_stream", None)
        if stream is not None and not stream.connected:
            if stream.peer_device is None:
                # same slide-under decision the rail makes for unary
                # payloads: an advertised server device means tensor
                # writes ride ICI from the first write, before the
                # settings response arrives.  Resolve BEFORE bind —
                # bind flushes pending writes, which must already know
                # their transport
                from brpc_tpu.ici import rail
                stream.peer_device = rail.lookup(ep)
            stream.bind(conn.sid)
        # `attempt` (captured at entry) versions the write: failing the
        # socket below can run the failed-socket callback SYNCHRONOUSLY
        # or on the transport thread, whose retry path claims the next
        # attempt — after which THIS frame's failure is stale and must
        # stay silent (the reference's bthread_id versioning,
        # OnVersionedRPCReturned; chaos-pinned: a stale path that kept
        # going either finished the call with no response or issued a
        # duplicate attempt)
        if (not meta.auth and not meta.trace_id and not meta.span_id
                and not meta.stream_id and not meta.tensor_header
                and not meta.user_fields and not meta.attachment_size):
            # simple request: meta packed + framed natively
            rc = Transport.send_request(
                conn.sid, meta.correlation_id, meta.attempt, meta.service,
                meta.method, meta.timeout_ms, meta.compress_type,
                meta.content_type, st.body)
        else:
            rc = Transport.instance().write_frame(conn.sid, meta.encode(),
                                                  st.body)
        if rc != 0:
            if rc == -2:
                # native write-queue bound tripped (Socket::Write -2):
                # the peer is reading too slowly for this call's bytes
                # (the socket is healthy — keep it cached).  The guard
                # is ATOMIC under the completion lock: an unlocked
                # check-then-act here could still stomp a concurrently
                # completing call's state
                cntl.set_failed_if_current(attempt, errors.EOVERCROWDED,
                                           "socket write queue overcrowded")
            else:
                cntl.set_failed_if_current(attempt, errors.EFAILEDSOCKET,
                                           "write failed")
                if self.options.connection_type == "single":
                    # the socket is dying but its failed-callback
                    # cleanup may still be in flight on another thread:
                    # evict the cached mapping NOW so the retry below
                    # reconnects instead of re-checking out the same
                    # dying connection and burning every attempt on it
                    smap.evict(ep, conn.sid)
                # and make sure the socket IS failed: a real rc=-1 means
                # it already is (a no-op then), but an evicted-yet-open
                # socket (e.g. an injected plain write error) would leak
                # its fd + handler entries forever.  May synchronously
                # hand the call to the failed-callback's retry path.
                Transport.instance().close(conn.sid, 0)
            if cntl.current_attempt > attempt or cntl.completed:
                return   # a newer attempt or a completion owns the call
            if self._should_retry(st, attempt):
                return
            if cntl.current_attempt > attempt or cntl.completed:
                return   # a racing path claimed the retry first
            mgr._finish(st)

    def _prepare_rail_attempt(self, st: _CallState, ep: EndPoint) -> None:
        """Decide, per attempt, whether the device-array payload rides ICI
        (server advertised a device: stage + transfer + deposit, frame
        carries a ticket) or falls back to host serialization.  Mirrors how
        the reference picks RdmaEndpoint vs the fd per socket at write
        time (socket.cpp:1751-1757)."""
        from brpc_tpu.ici import rail
        meta = st.meta_template
        meta.user_fields.pop(rail.F_TICKET, None)
        meta.user_fields.pop(rail.F_SRC_DEV, None)
        dev = rail.lookup(ep)
        if dev is not None:
            try:
                ticket = rail.ship(st.rail_obj, dev)
            except Exception:
                dev = None  # pool exhausted / transfer failed: host fallback
            else:
                st.rail_tickets.append(ticket)
                meta.user_fields[rail.F_TICKET] = ticket
                meta.user_fields[rail.F_SRC_DEV] = str(
                    rail.source_device(st.rail_obj).id)
                meta.tensor_header = b""
                st.body = b""
                return
        rail.rail_fallbacks.add(1)
        if st.rail_fallback_cache is None:
            ser = get_serializer("tensor")
            body, tensor_header = ser.encode(st.rail_obj)
            st.rail_fallback_cache = (compress(body, st.cntl.compress_type),
                                      tensor_header)
        st.body, meta.tensor_header = st.rail_fallback_cache

    def _should_retry(self, st: _CallState,
                      owner_attempt: int | None = None) -> bool:
        """If allowed, claim the next attempt and re-issue.  Returns
        True when a retry was started (the call stays pending).  The
        claim is ATOMIC against the attempt version (`owner_attempt`,
        defaulting to the current attempt): of two failure paths racing
        to retry the same attempt, exactly one wins — the loser must
        re-check attempt/completion before finishing the call."""
        cntl = st.cntl
        if cntl.completed:
            return False
        policy = self.options.retry_policy or DEFAULT_RETRY_POLICY
        if cntl.current_attempt >= (cntl.max_retry or 0):
            return False
        if not policy.do_retry(cntl):
            return False
        owner = cntl.current_attempt if owner_attempt is None \
            else owner_attempt
        if not cntl.claim_retry(owner):
            return False
        self._issue(st)
        return True

    def _issue_backup(self, st: _CallState) -> None:
        """Backup request: race a second attempt; first response wins
        (channel.cpp:403-409)."""
        cntl = st.cntl
        if cntl.completed:
            return
        if cntl.current_attempt >= (cntl.max_retry or 0):
            return  # max_retry=0 disables backups too (single attempt only)
        if not cntl.claim_backup():
            return
        self._issue(st)
