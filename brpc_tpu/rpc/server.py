"""Server — service registry + dispatch (reference server.{h,cpp}; §2.6).

Request path mirrors §3.3: native core parses a frame and hands it to an
executor thread → verify auth → find method in the method map → concurrency
limiter OnRequested → decompress/deserialize → user method → serialize,
compress, write response → MethodStatus::OnResponded feeds per-method
LatencyRecorders (the /status page data).  HTTP messages on the same port go
to the builtin console router (SURVEY.md §2.7).
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Optional

from brpc_tpu import errors, flags as _flags, rpcz
from brpc_tpu.rpc import rpc_dump as _rpc_dump  # registers rpc_dump_* flags
from brpc_tpu.bvar import Adder, LatencyRecorder, PassiveStatus
from brpc_tpu.rpc import meta as M
from brpc_tpu.rpc.controller import Controller
from brpc_tpu.rpc.serialization import (PbSerializer, as_bytes, compress,
                                        decompress, get_serializer,
                                        pb_message_pool)
from brpc_tpu.rpc.service import MethodSpec, Service, method
from brpc_tpu.rpc.transport import (MSG_H2, MSG_HTTP, MSG_MEMCACHE,
                                    MSG_MONGO, MSG_REDIS, MSG_THRIFT,
                                    MSG_TRPC, Transport)

# responses whose socket write was rejected (EOVERCROWDED backlog or a
# dead socket) — the client can only learn via its own deadline, so these
# are the server-side visibility: the Adder counts Python-path drops, the
# PassiveStatus mirrors the native fast path's C++ counter onto /vars
_dropped_responses = Adder("rpc_server_dropped_responses")


class _StreamBody:
    """Server-streaming response body: iterates the handler's generator,
    encoding one item per __next__ (bounded by the service's tag pool),
    and guarantees the cleanup callback runs EXACTLY once however the
    stream ends — exhaustion, mid-stream error, or close() before the
    first item (where a plain generator's finally would never run)."""

    _END = object()

    def __init__(self, gen, serializer, pool, cleanup):
        self._gen = gen
        self._ser = serializer
        self._pool = pool
        self._cleanup = cleanup
        self._done = False

    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        try:
            if self._pool is not None:
                item = self._pool.submit(next, self._gen, self._END).result()
            else:
                item = next(self._gen, self._END)
        except BaseException:
            self._settle(errors.EINTERNAL)
            raise
        if item is self._END:
            self._settle(0)
            raise StopIteration
        try:
            body, _ = self._ser.encode(item)
        except BaseException:
            self._settle(errors.EINTERNAL)
            raise
        return body

    def close(self) -> None:
        if self._done:
            return
        try:
            self._gen.close()
        except Exception:
            pass
        self._settle(errors.ECANCELED)

    def _settle(self, code: int) -> None:
        if not self._done:
            self._done = True
            self._cleanup(code)


def _interceptor_code(verdict):
    """Maps an interceptor verdict to an error code, or None to admit.
    ONE implementation for every dispatch path (native, RESTful, gRPC):
    bool is an int subtype and error code 0 reads as success on the
    client, so both `False` and a C-style 0 must mean EREJECT — not a
    silent empty success (interceptor.h:26)."""
    if verdict is None or verdict is True:
        return None
    if isinstance(verdict, int) and not isinstance(verdict, bool) \
            and verdict != 0:
        return verdict
    return errors.EREJECT
_native_dropped = PassiveStatus(
    lambda: __import__("brpc_tpu._core", fromlist=["core"])
    .core.brpc_rpc_dropped_responses()).expose(
        "rpc_native_dropped_responses")


@dataclass
class ServerOptions:
    num_threads: int = 0                   # 0 = native executor default
    max_concurrency: int | str = 0         # 0=unlimited, int, or "auto"
    method_max_concurrency: int | str = 0
    auth: Optional[Any] = None             # Authenticator (verify side)
    interceptor: Optional[Any] = None      # pre-dispatch hook
    internal_port: int = -1                # separate console port (optional)
    has_builtin_services: bool = True
    server_info_name: str = "tpu-rpc"
    graceful_quit_timeout_s: float = 5.0
    # Serve the redis protocol on the same port (reference
    # ServerOptions.redis_service, redis.h:192): a RedisService whose
    # command handlers answer RESP traffic detected by the native parser.
    redis_service: Optional[Any] = None
    # Serve the memcache binary protocol on the same port (the reference
    # is client-only for memcache; server side mirrors redis_service so
    # loopback tests and demos work): a MemcacheService.
    memcache_service: Optional[Any] = None
    # Serve framed-binary thrift on the same port (reference
    # thrift_service.h adaptor): a ThriftService with method handlers.
    thrift_service: Optional[Any] = None
    # Serve the mongo wire protocol (reference mongo_service_adaptor.h):
    # an object with handle_bytes(raw) -> bytes.
    mongo_service: Optional[Any] = None
    # Catch-all service for unmatched (service, method) — the generic
    # proxy hook (reference baidu_master_service.{h,cpp}).  An object with
    # process(cntl, request_bytes) -> bytes; the target names are on
    # cntl.request_meta.service/.method.
    master_service: Optional[Any] = None
    # Per-request pooled session data (reference simple_data_pool +
    # data_factory.h): a DataFactory, or a zero-arg callable; each request
    # sees the pooled object as cntl.session_data.
    session_data_factory: Optional[Any] = None
    # pooled pb request messages (reference RpcPBMessageFactory arena
    # pooling, rpc_pb_message_factory.{h,cpp}).  Opt-in: the framework
    # owns the request message and reuses it after done — handlers that
    # stash the message past completion must copy it first.
    pb_message_pooling: bool = False
    # Advertise this server as ICI-reachable on the given jax device: tensor
    # payloads from in-process channels then ride the BlockPool/IciEndpoint
    # rail instead of the socket (the use_rdma switch — channel.h:109,
    # rdma_endpoint.h:82; see ici/rail.py).
    ici_device: Optional[Any] = None
    # register the _dcn service (topology handshake + remote device-service
    # bridge, ici/dcn.py) at start — the DCN half of SURVEY §5.8
    enable_dcn: bool = False
    # Run handlers in a WIDE dedicated thread pool instead of the
    # fixed-width native executor workers (the reference's
    # FLAGS_usercode_in_pthread + usercode_backup_pool,
    # details/usercode_backup_pool.cpp): handlers that BLOCK (nested
    # RPCs, IO, long sleeps) stop competing for the executor's cores+1
    # workers, so blocking user code cannot starve dispatch of other
    # requests.  Costs a thread hop per request — off by default,
    # exactly like the reference flag.  NOTE: unlike the reference's
    # grow-on-demand backup pool this pool is FIXED-CAP
    # (usercode_pool_workers, default 64) — beyond that many
    # simultaneously blocked handlers, requests queue behind them.
    usercode_in_pthread: bool = False
    # pool width when usercode_in_pthread is on (0 = 64)
    usercode_pool_workers: int = 0
    # Native admission control for the GIL-serialized Python lane
    # (reference ELIMIT fail-fast semantics, expressed as a latency
    # budget): when > 0, a request whose estimated queue wait (pending x
    # EMA upcall time, tracked in C++) exceeds this many milliseconds is
    # answered ELIMIT natively — it never reaches Python.  0 = off, the
    # reference's default.  Process-wide (the native lane is shared).
    usercode_latency_budget_ms: float = 0.0
    # Single-threaded event-loop mode: run handlers INLINE on the native
    # dispatcher thread (no executor hop, no cross-thread GIL convoy —
    # the lowest-variance path on core-starved hosts).  STRICTLY for
    # handlers that never block: a blocking handler stalls every socket
    # on that dispatcher, and a nested RPC through it can deadlock.
    # Process-wide.  Mutually exclusive in spirit with
    # usercode_in_pthread (which exists FOR blocking handlers).
    usercode_inline: bool = False
    # In-socket TLS for the main port (reference ServerSSLOptions /
    # socket.h SSL integration): an ssl.SSLContext with a loaded cert
    # chain; every accepted connection is TLS-wrapped before its first
    # byte parses, and every protocol on the port rides it.  NOTE: do
    # not combine with usercode_latency_budget_ms (its native-packed
    # ELIMIT shed would bypass the TLS engine).
    tls_context: Optional[Any] = None
    # NATIVE h2/gRPC data plane (src/cc/net/h2.cc + rpc/h2_native.py,
    # mirroring the reference's native http2_rpc_protocol.cpp): h2
    # framing, HPACK, flow control and gRPC framing run in C++; Python
    # is upcalled once per message.  Off → the pure-Python plane
    # (rpc/h2.py GrpcServerConnection) serves h2 on the port instead.
    # Forced off under in-socket TLS: the TLS engine re-injects
    # plaintext through the generic parser path on the LISTENER's
    # options, and the native session would bypass the record layer.
    h2_native: bool = True


class MethodStatus:
    """Per-method concurrency + latency tracking
    (reference details/method_status.{h,cpp}).

    The per-request path is native end to end (VERDICT r2 task 5):
    concurrency is a native EXACT atomic (admission control needs a
    linearizable count — a combiner's relaxed cell-walk can transiently
    undercount and over-admit) and latency rides the native combiner
    LatencyRecorder backend — no Python-level lock is taken per
    request."""

    def __init__(self, full_name: str, limiter=None):
        from brpc_tpu._core import core
        safe = full_name.replace("/", "_").replace(".", "_")
        self.full_name = full_name
        self.latency_rec = LatencyRecorder(f"rpc_server_{safe}")
        self.nerror = Adder(f"rpc_server_{safe}_error")
        self._conc_h = core.brpc_atomic_new()
        self._conc_incr = core.brpc_atomic_incr
        self._conc_get = core.brpc_atomic_get
        self._conc_free = core.brpc_atomic_free  # cached for __del__
        self.limiter = limiter
        PassiveStatus(lambda: self.concurrency).expose(
            f"rpc_server_{safe}_concurrency")

    def on_requested(self) -> bool:
        c = self._conc_incr(self._conc_h, 1)
        if self.limiter is not None and not self.limiter.on_requested(c):
            self._conc_incr(self._conc_h, -1)
            return False
        return True

    def on_responded(self, error_code: int, latency_us: int) -> None:
        # self-heal at zero (the old locked max(0, c-1)): an unmatched
        # on_responded must not drive the gauge permanently negative and
        # disable the limiter
        if self._conc_incr(self._conc_h, -1) < 0:
            self._conc_incr(self._conc_h, 1)
        if error_code == 0:
            self.latency_rec.add(latency_us)
        else:
            self.nerror.add(1)
        if self.limiter is not None:
            self.limiter.on_responded(error_code, latency_us)

    @property
    def concurrency(self) -> int:
        return max(0, self._conc_get(self._conc_h))

    def __del__(self):
        h = getattr(self, "_conc_h", None)
        if h:
            try:
                self._conc_free(h)
            except Exception:
                pass
            self._conc_h = None


class Server:
    def __init__(self, options: ServerOptions | None = None, **kw):
        self.options = options or ServerOptions(**kw)
        self._services: dict[str, Service] = {}
        self._methods: dict[tuple[str, str], MethodSpec] = {}
        self._method_status: dict[tuple[str, str], MethodStatus] = {}
        self._listen_sid: Optional[int] = None
        self._port: Optional[int] = None
        self._started = False
        self._stopping = False
        self._inflight = 0
        self._inflight_mu = threading.Lock()
        self._inflight_zero = threading.Event()
        self._inflight_zero.set()
        self._connections: set[int] = set()
        self._conn_mu = threading.Lock()
        self._start_time = time.time()
        self._limiter = None
        # http console router installed at start
        self._http_router = None
        # user HTTP handlers served alongside the builtin console
        self._http_handlers: dict[str, Any] = {}
        # pooled per-request session data (simple_data_pool analog)
        self._session_pool = None
        if self.options.session_data_factory is not None:
            from brpc_tpu.rpc.data_pool import SimpleDataPool
            self._session_pool = SimpleDataPool(
                self.options.session_data_factory)
        if self.options.master_service is not None:
            self._method_status[("*", "*")] = \
                MethodStatus("master_service/process")
        # h2/gRPC connections on the shared port (auto-detected by the
        # native parser via the client preface), sid -> GrpcServerConnection
        self._h2_conns: dict[int, Any] = {}
        # bthread-tag analog: isolated per-tag worker pools + service->tag;
        # sizes recorded so start() can (re)create pools after join()
        self._tag_pools: dict[str, Any] = {}
        self._tag_sizes: dict[str, int] = {}
        self._service_tags: dict[str, str] = {}

    def add_http_handler(self, path: str, fn) -> "Server":
        """Register a custom HTTP handler on the console port; fn(req) may
        return str/bytes, (body, content_type), a full HTTP/1.1 response, or
        a ProgressiveResponse for chunked push."""
        self._http_handlers[path] = fn
        return self

    # ---- registry (Server::AddService, server.h:376) ----

    def add_service(self, service: Service,
                    tag: str | None = None,
                    tag_workers: int = 4) -> "Server":
        """Register a service; an optional ``tag`` runs its handlers on an
        isolated worker pool so one service's load cannot starve another
        (the bthread tag of the reference, task_control.h:90-147 /
        example/bthread_tag_echo_c++).  Untagged services run inline on
        the native dispatch threads."""
        if self._started:
            raise RuntimeError("cannot add services after start")
        name = service.service_name()
        if name in self._services:
            raise ValueError(f"service {name!r} already added")
        if tag is not None:
            if tag == "":
                # "" is the usercode_in_pthread pool's reserved key; a
                # user tag colliding with it would silently replace the
                # wide pool with this tag's width
                raise ValueError('tag "" is reserved (usercode pool); '
                                 'pick a non-empty tag name')
            # validate BEFORE mutating any registry state
            prev = self._tag_sizes.get(tag)
            if prev is not None and prev != tag_workers:
                raise ValueError(
                    f"tag {tag!r} already sized at {prev} workers; "
                    f"conflicting tag_workers={tag_workers}")
        self._services[name] = service
        if tag is not None:
            self._tag_sizes[tag] = tag_workers
            self._service_tags[name] = tag
        from brpc_tpu.policy.concurrency_limiter import create_limiter
        for mname, spec in service.rpc_methods().items():
            key = (name, mname)
            self._methods[key] = spec
            limiter = None
            limit = spec.max_concurrency \
                if spec.max_concurrency is not None \
                else self.options.method_max_concurrency
            if limit:
                limiter = create_limiter(limit)
            self._method_status[key] = MethodStatus(f"{name}/{mname}", limiter)
        return self

    @property
    def services(self) -> dict[str, Service]:
        return dict(self._services)

    @property
    def method_statuses(self) -> dict[tuple[str, str], MethodStatus]:
        return dict(self._method_status)

    # ---- lifecycle (Start/Stop/Join, server.cpp:788,1259,1278) ----

    def start(self, addr: str = "0.0.0.0", port: int = 0) -> "Server":
        if self._started:
            raise RuntimeError("already started")
        self._stopping = False   # support stop()/join()/start() again
        if self.options.max_concurrency:
            from brpc_tpu.policy.concurrency_limiter import create_limiter
            self._limiter = create_limiter(self.options.max_concurrency)
        if self.options.has_builtin_services:
            from brpc_tpu.builtin.router import HttpRouter
            self._http_router = HttpRouter(self)
            # gRPC health protocol (reference grpc_health_check /
            # builtin grpc health): stock grpc health clients call
            # /grpc.health.v1.Health/Check and expect
            # HealthCheckResponse{status: SERVING=1} == pb bytes 08 01
            if "grpc.health.v1.Health" not in self._services:
                outer = self

                class _GrpcHealth(Service):
                    NAME = "grpc.health.v1.Health"

                    @method(request="raw", response="raw")
                    def Check(self, cntl, req):
                        # HealthCheckRequest.service is pb field 1
                        # (length-delimited): empty = whole server
                        svc = ""
                        if len(req) >= 2 and req[0] == 0x0A:
                            n = req[1]
                            svc = req[2:2 + n].decode("utf-8", "replace")
                        if svc and svc not in outer._services:
                            return b"\x08\x03"  # SERVICE_UNKNOWN
                        return b"\x08\x01" if outer.running \
                            else b"\x08\x02"  # NOT_SERVING

                self.add_service(_GrpcHealth())
        from brpc_tpu.bvar.default_variables import expose_default_variables
        expose_default_variables()  # process cpu/rss/fds on /vars (§2.7)
        from brpc_tpu.butil.flight import expose_flight_variables
        expose_flight_variables()   # flight recorder + syscall attribution
        # always-on stage-tagged sampling profiler (ISSUE 6): the
        # /hotspots ring starts with the first server; flag-gated
        # (hotspot_sampler_enabled), live-flippable on /flags
        from brpc_tpu.builtin.sampler import HotspotSampler
        HotspotSampler.ensure_started()
        # (re)create tagged worker pools — join() shuts them down, and a
        # Server may be started again afterwards
        from concurrent.futures import ThreadPoolExecutor
        for tag, workers in self._tag_sizes.items():
            if tag not in self._tag_pools:
                self._tag_pools[tag] = ThreadPoolExecutor(
                    max_workers=workers,
                    thread_name_prefix=f"svc-tag-{tag}")
        if self.options.usercode_in_pthread:
            # the usercode pool IS a tag pool under the reserved ""
            # tag: creation here, recreation after join(), shutdown and
            # inflight accounting all ride the one mechanism
            if "" not in self._tag_pools:
                self._tag_pools[""] = ThreadPoolExecutor(
                    max_workers=self.options.usercode_pool_workers or 64,
                    thread_name_prefix="usercode")
        if self.options.usercode_inline and (
                self.options.usercode_in_pthread or self._tag_sizes):
            # pooled handlers under inline dispatch would defeat BOTH
            # features: the inline upcall measures only the pool-submit
            # cost (admission control silently dead while the pool queue
            # grows) and the pool hop reintroduces the cross-thread
            # convoy inline mode exists to remove
            raise ValueError(
                "usercode_inline is for handlers that run inline and "
                "never block; it cannot be combined with "
                "usercode_in_pthread or per-service tag pools")
        if self.options.usercode_latency_budget_ms > 0 or \
                self.options.usercode_inline:
            from brpc_tpu._core import core as _core
            if self.options.usercode_latency_budget_ms > 0:
                _core.brpc_set_usercode_budget_us(
                    int(self.options.usercode_latency_budget_ms * 1000))
            if self.options.usercode_inline:
                _core.brpc_set_usercode_inline(1)
            _usercode_policy_owners.add(id(self))
        if self.options.enable_dcn:
            # cross-process device RPC: topology handshake + remote
            # device-service bridge (ici/dcn.py; the RdmaEndpoint
            # TCP-assisted-handshake slot, rdma_endpoint.h:112-115).
            # Added BEFORE the native-registration loop below so DCN
            # methods ride the same path as every other service.
            from brpc_tpu.ici.dcn import DCN_SERVICE, DcnService
            if DCN_SERVICE not in self._services:
                self.add_service(DcnService())
        t = Transport.instance()
        use_native_h2 = (self.options.h2_native
                         and self.options.tls_context is None)
        if use_native_h2:
            from brpc_tpu.rpc.h2_native import NativeH2Bridge
            self._h2_bridge = NativeH2Bridge(self)
            self._listen_sid, self._port = t.listen_rpc_h2(
                addr, port, self._on_message, self._h2_bridge,
                on_failed=self._on_conn_failed,
                on_request=self._on_fast_request)
        else:
            self._listen_sid, self._port = t.listen_rpc(
                addr, port, self._on_message, self._on_conn_failed,
                on_request=self._on_fast_request)
        if self.options.tls_context is not None:
            if self.options.usercode_latency_budget_ms > 0:
                # the native ELIMIT shed packs and writes PLAINTEXT
                # directly, bypassing the TLS engine: under overload the
                # error response would leak in cleartext and kill the
                # session — refuse the combination up front
                raise ValueError(
                    "tls_context cannot be combined with "
                    "usercode_latency_budget_ms (the native shed path "
                    "bypasses the TLS engine)")
            t.enable_tls_listener(self._listen_sid, self.options.tls_context)
        # native method map (FlatMap behind DoublyBufferedData, net/rpc.h):
        # requests to these methods are meta-parsed and method-matched in
        # C++ and arrive pre-parsed; everything else (auth/trace/stream
        # metas, unknown methods, master-service catch-all) still comes
        # through _on_message with full Python decode
        for key in self._methods:
            _native_method_register(key)
        self._methods_registered = True
        if self.options.ici_device is not None:
            from brpc_tpu.ici import rail
            rail.advertise(self._port, self.options.ici_device)
        self._started = True
        self._start_time = time.time()
        _register_server(self)
        return self

    @property
    def port(self) -> Optional[int]:
        return self._port

    @property
    def running(self) -> bool:
        return self._started and not self._stopping

    def stop(self) -> None:
        """Stop accepting; in-flight requests drain in join()."""
        if not self._started or self._stopping:
            return
        self._stopping = True
        if self.options.ici_device is not None and self._port is not None:
            from brpc_tpu.ici import rail
            rail.unadvertise(self._port)
        if self._listen_sid is not None:
            Transport.instance().close(self._listen_sid)

    def join(self) -> None:
        if not self._started:
            return  # idempotent: a second join() must not double-unregister
        self._stopping = True  # decrements only signal the event when stopping
        with self._inflight_mu:
            if self._inflight == 0:
                self._inflight_zero.set()
            else:
                self._inflight_zero.clear()
        self._inflight_zero.wait(self.options.graceful_quit_timeout_s)
        with self._conn_mu:
            conns = list(self._connections)
        t = Transport.instance()
        for sid in conns:
            t.close(sid)
        for pool in self._tag_pools.values():
            pool.shutdown(wait=False)
        self._tag_pools.clear()   # start() recreates from _tag_sizes
        if getattr(self, "_methods_registered", False):
            self._methods_registered = False
            for key in self._methods:
                _native_method_unregister(key)
        if self.options.usercode_latency_budget_ms > 0 or \
                self.options.usercode_inline:
            # budget/inline are process-wide native state: clear only
            # when the LAST owning server leaves, so stopping one server
            # can't strip admission control from another still running
            from brpc_tpu._core import core as _core
            _usercode_policy_owners.discard(id(self))
            if not _usercode_policy_owners:
                _core.brpc_set_usercode_budget_us(0)
                _core.brpc_set_usercode_inline(0)
        _unregister_server(self)
        self._started = False

    def run_until_interrupt(self) -> None:  # RunUntilAskedToQuit analog
        try:
            while self.running:
                time.sleep(0.5)
        except KeyboardInterrupt:
            pass
        self.stop()
        self.join()

    # ---- stats for builtins ----

    @property
    def uptime_s(self) -> float:
        return time.time() - self._start_time

    @property
    def connection_count(self) -> int:
        with self._conn_mu:
            return len(self._connections)

    def connections(self) -> list[int]:
        with self._conn_mu:
            return list(self._connections)

    # ---- dispatch ----

    def _on_conn_failed(self, sid: int, err: int) -> None:
        with self._conn_mu:
            self._connections.discard(sid)
        conn = self._h2_conns.pop(sid, None)
        if conn is not None:
            # unblock bidi handlers parked on this connection's request
            # queues, or they leak their inflight slots forever
            conn.abort_bidi()

    def _track_conn(self, sid: int) -> None:
        if sid in self._connections:  # GIL-safe read; hot path skips the lock
            return
        with self._conn_mu:
            self._connections.add(sid)

    def _on_message(self, sid: int, kind: int, meta_bytes: bytes, body) -> None:
        self._track_conn(sid)
        if kind == MSG_HTTP:
            if self._http_router is not None:
                self._http_router.handle(sid, body.to_bytes())
            else:
                Transport.instance().write_raw(
                    sid, b"HTTP/1.1 404 Not Found\r\nContent-Length: 0\r\n\r\n")
            return
        if kind == MSG_H2:
            conn = self._h2_conns.get(sid)
            if conn is None:
                from brpc_tpu.rpc.h2 import GrpcServerConnection, \
                    feed_frames
                self._h2_feed = feed_frames   # hot path: no per-msg import
                conn = self._h2_conns[sid] = GrpcServerConnection(sid, self)
            self._h2_feed(conn, meta_bytes, body.to_bytes())
            return
        if kind == MSG_REDIS:
            svc = self.options.redis_service
            if svc is None:
                Transport.instance().write_raw(
                    sid, b"-ERR this server has no redis service\r\n")
            else:
                Transport.instance().write_raw(
                    sid, svc.handle_bytes(body.to_bytes()))
            return
        if kind == MSG_MEMCACHE:
            svc = self.options.memcache_service
            if svc is None:
                # binary "unknown command" so clients fail fast
                from brpc_tpu.rpc.memcache import (MAGIC_RES,
                                                   ST_UNKNOWN_COMMAND,
                                                   pack_packet)
                Transport.instance().write_raw(
                    sid, pack_packet(MAGIC_RES, 0,
                                     status=ST_UNKNOWN_COMMAND))
            else:
                Transport.instance().write_raw(
                    sid, svc.handle_bytes(body.to_bytes()))
            return
        if kind == MSG_THRIFT:
            svc = self.options.thrift_service
            if svc is None:
                from brpc_tpu.rpc.thrift import (decode_message,
                                                 encode_exception)
                try:
                    req = decode_message(body.to_bytes())
                    name, seqid = req.name, req.seqid
                except ValueError:
                    name, seqid = "unknown", 0
                Transport.instance().write_raw(
                    sid, encode_exception(name, seqid,
                                          "this server has no thrift "
                                          "service", 1))
            else:
                out = svc.handle_bytes(body.to_bytes())
                if out:
                    Transport.instance().write_raw(sid, out)
            return
        if kind == MSG_MONGO:
            svc = self.options.mongo_service
            if svc is None:
                # no silent drop: close so mongo drivers fail fast instead
                # of blocking on recv forever
                Transport.instance().close(sid)
            else:
                out = svc.handle_bytes(body.to_bytes())
                if out:
                    Transport.instance().write_raw(sid, out)
            return
        try:
            meta = M.RpcMeta.decode(meta_bytes)
        except ValueError:
            return
        if meta.msg_type == M.MSG_REQUEST:
            self._route_request(sid, meta, body, meta_bytes)
        elif meta.msg_type in (M.MSG_STREAM_DATA, M.MSG_STREAM_FEEDBACK,
                               M.MSG_STREAM_CLOSE):
            from brpc_tpu.rpc.stream import StreamRegistry
            StreamRegistry.instance().on_frame(sid, meta, body)

    def _inflight_inc(self) -> None:
        # Hot path: a bare counter under the lock.  The zero-event is only
        # observed by join(), so Event.set()/clear() churn (measured
        # ~6us/request — notify_all allocates and wakes) happens ONLY while
        # stopping, not per request.
        with self._inflight_mu:
            self._inflight += 1

    def _inflight_dec(self) -> None:
        with self._inflight_mu:
            self._inflight -= 1
            if self._inflight == 0 and self._stopping:
                self._inflight_zero.set()

    def _on_fast_request(self, sid: int, cid: int, attempt: int,
                         service: str, method_name: str, compress: int,
                         timeout_ms: int, content_type: str,
                         attachment_size: int, body: bytes) -> None:
        """Natively pre-parsed request (net/rpc.h fast path via _fastrpc):
        the meta TLV walk, method lookup and frame cut all happened in C++;
        only the handler body and response serialization run in Python."""
        self._track_conn(sid)
        meta = M.RpcMeta(
            msg_type=M.MSG_REQUEST,
            correlation_id=cid,
            attempt=attempt,
            service=service,
            method=method_name,
            compress_type=compress,
            timeout_ms=timeout_ms,
            content_type=content_type,
            attachment_size=attachment_size,
        )
        self._route_request(sid, meta, body, None)

    def _route_request(self, sid: int, meta: M.RpcMeta, body,
                       meta_bytes: bytes | None) -> None:
        # sampled traffic capture for rpc_replay (rpc_dump.h:69, §5.5);
        # the body copy (and the fast path's meta re-encode) happen only
        # when dumping is on
        if _flags.get_flag("rpc_dump"):
            from brpc_tpu.rpc.rpc_dump import RpcDumper
            from brpc_tpu.rpc.serialization import as_bytes
            RpcDumper.instance().sample(
                meta_bytes or meta.encode(),
                as_bytes(body) if isinstance(body, (bytes, memoryview))
                else body.to_bytes())
        tag = self._service_tags.get(meta.service)
        pool = self._tag_pools.get(tag) if tag is not None else None
        if pool is None:
            # usercode_in_pthread (usercode_backup_pool.cpp): BLOCKING
            # handlers hop to the wide "" tag pool so they never park
            # the fixed-width executor workers dispatching everyone else
            pool = self._tag_pools.get("")
        if pool is not None:
            if self._stopping:
                # the pre_accepted contract covers requests QUEUED
                # before stop(); a request ARRIVING after stop() gets
                # ELOGOFF here, same as the non-pool path's gate
                self._respond_error(sid, meta, errors.ELOGOFF)
                return
            # isolated worker pool for this service (bthread tag);
            # count the QUEUED request so graceful join() waits for it
            self._inflight_inc()
            pool.submit(self._process_tagged, sid, meta, body)
        else:
            self._process_request(sid, meta, body)

    def _process_tagged(self, sid: int, meta: M.RpcMeta, body) -> None:
        try:
            # pre_accepted: this request entered the queue before any
            # stop(); graceful join() is waiting for it — serve it
            self._process_request(sid, meta, body, pre_accepted=True)
        finally:
            self._inflight_dec()

    def _respond_error(self, sid: int, meta: M.RpcMeta, code: int,
                       text: str = "") -> None:
        # error responses carry only cid/attempt/error TLVs: pack natively
        Transport.send_response(sid, meta.correlation_id, meta.attempt,
                                code, text or errors.describe(code), "", b"")

    def _process_request(self, sid: int, meta: M.RpcMeta, body,
                         pre_accepted: bool = False) -> None:
        """ProcessRpcRequest analog (baidu_rpc_protocol.cpp:398)."""
        with rpcz.stage("rpc.server.process", meta.correlation_id):
            self._serve_request(sid, meta, body, pre_accepted)

    def _serve_request(self, sid: int, meta: M.RpcMeta, body,
                       pre_accepted: bool) -> None:
        start = time.monotonic()
        if self._stopping and not pre_accepted:
            self._respond_error(sid, meta, errors.ELOGOFF)
            return
        # auth (§2.5 Auth: first-message piggyback — we verify every frame)
        if self.options.auth is not None:
            if not self.options.auth.verify_credential(meta.auth):
                self._respond_error(sid, meta, errors.ERPCAUTH)
                return
        # interceptor (interceptor.h:26)
        if self.options.interceptor is not None:
            code = _interceptor_code(self.options.interceptor(meta))
            if code is not None:
                self._respond_error(sid, meta, code)
                return
        key = (meta.service, meta.method)
        spec = self._methods.get(key)
        if spec is None:
            master = self.options.master_service
            if master is not None:
                # catch-all dispatch (baidu_master_service: generic method
                # for proxies, baidu_rpc_protocol.cpp:521-560); raw bytes
                # in/out, target names readable off cntl.request_meta
                key = ("*", "*")
                spec = MethodSpec(
                    name="process",
                    fn=lambda cntl, req: master.process(cntl, req),
                    request_serializer=get_serializer("raw"),
                    response_serializer=get_serializer("raw"))
            elif meta.service not in self._services:
                self._respond_error(sid, meta, errors.ENOSERVICE,
                                    f"unknown service {meta.service!r}")
                return
            else:
                self._respond_error(sid, meta, errors.ENOMETHOD,
                                    f"unknown method {meta.method!r}")
                return
        # server-level then method-level concurrency (§2.6)
        if self._limiter is not None and not self._limiter.on_requested(
                self._total_concurrency() + 1):
            self._respond_error(sid, meta, errors.ELIMIT)
            return
        status = self._method_status[key]
        if not status.on_requested():
            if self._limiter is not None:
                self._limiter.on_responded(errors.ELIMIT, 0)
            self._respond_error(sid, meta, errors.ELIMIT)
            return

        self._inflight_inc()

        span = rpcz.new_span("server", meta.service, meta.method,
                             trace_id=meta.trace_id,
                             parent_span_id=meta.span_id,
                             # a joined trace inherits the root's
                             # head-sampling decision from the wire;
                             # a fresh trace (no id) decides locally
                             sampled=bool(meta.flags
                                          & M.FLAG_TRACE_SAMPLED)
                             if meta.trace_id else None)
        cntl = Controller()
        cntl.is_server_side = True
        cntl.request_meta = meta
        cntl.peer_sid = sid
        cntl.trace_id = span.trace_id
        cntl.span_id = span.span_id
        rail_src = meta.user_fields.get(M.F_SRC_DEV) \
            if meta.user_fields else None
        # ---- decode phase ----
        try:
            if meta.user_fields.get(M.F_TICKET):
                # request payload rode ICI: claim the device arrays from the
                # rail registry (ici/rail.py) — the frame carried only the
                # ticket, no body bytes exist
                from brpc_tpu.ici import rail
                with rpcz.span_scope(span):
                    request = rail.claim(meta.user_fields[M.F_TICKET])
                span.request_size = 0
            else:
                # fast-path bodies arrive as IOBuf-backed memoryviews
                # (zero copy, _fastrpc FastBody); the generic path hands
                # an IOBuf.  memoryview slicing keeps it zero-copy.
                raw = body if isinstance(body, (bytes, memoryview)) \
                    else body.to_bytes()
                att = meta.attachment_size
                payload = raw[: len(raw) - att] if att else raw
                # bytes contract for attachments (same boundary rule as
                # the raw serializer): handlers get bytes, not views
                cntl.request_attachment = bytes(raw[len(raw) - att:]) \
                    if att else b""
                if meta.compress_type:
                    payload = decompress(payload, meta.compress_type)
                req_ser = spec.request_serializer
                if (self.options.pb_message_pooling
                        and isinstance(req_ser, PbSerializer)
                        and req_ser.message_class is not None):
                    # pooled request message (RpcPBMessageFactory slot);
                    # returned to the pool after done fires
                    request = pb_message_pool.get(req_ser.message_class)
                    cntl._pooled_request = request  # BEFORE parse: a
                    # parse failure path still returns it to the pool
                    request.ParseFromString(as_bytes(payload))
                else:
                    request = req_ser.decode(payload, meta.tensor_header)
                span.request_size = len(raw)
                # request wire size surfaced to handlers (per-serializer
                # wire-bytes accounting, e.g. psserve_wire_bytes_*)
                cntl.request_body_size = len(raw)
        except Exception as e:
            if isinstance(e, ValueError):
                # malformed payload = bad INPUT, not a server bug: every
                # serializer's malformed-body path raises ValueError (the
                # contract serialization.py documents), and the peer must
                # see a clean EREQUEST instead of EINTERNAL — the
                # tensorframe fuzz surface pins this
                e = errors.RpcError(errors.EREQUEST,
                                    f"cannot decode request: {e}")
            self._complete_request(sid, meta, span, cntl, spec, status,
                                   start, rail_src, None, exc=e)
            return
        # ---- handler phase ----
        # The done closure runs the response path exactly once; a handler
        # that calls cntl.defer() parks the RPC as that closure (data,
        # not a thread) and any thread releases it later — the
        # reference's done Closure (svc->CallMethod(..., done),
        # baidu_rpc_protocol.cpp:398).  It is built LAZILY by defer():
        # the common synchronous path completes inline below without
        # paying a closure + once-guard lock per request.
        cntl._done_factory = lambda: self._make_server_done(
            sid, meta, span, cntl, spec, status, start, rail_src)
        if self._session_pool is not None:
            cntl.session_data = self._session_pool.borrow()
        try:
            # with rpcz off the scope is the shared no-op: no context
            # variable is touched per request
            with rpcz.span_scope(span), \
                    rpcz.stage("rpc.server.handler", meta.correlation_id):
                response = spec.fn(cntl, request)
        except Exception as e:
            if cntl._deferred:
                # defer() transferred response ownership to done(); the
                # raise is a handler bug but completing here would race
                # the legitimate done() (reference contract: after done is
                # handed to CallMethod the framework never responds on
                # handler return — a leaked done hangs, an owned one wins)
                import traceback
                traceback.print_exc()
                return
            self._complete_request(sid, meta, span, cntl, spec, status,
                                   start, rail_src, None, exc=e)
            return
        finally:
            if self._session_pool is not None:
                # deferred handlers must not rely on session_data after
                # returning: the pooled object goes back with the handler
                self._session_pool.give_back(cntl.session_data)
                cntl.session_data = None
        if cntl._deferred:
            return  # the parked done() closure completes the RPC later
        self._complete_request(sid, meta, span, cntl, spec, status,
                               start, rail_src, response)

    def _make_server_done(self, sid, meta, span, cntl, spec, status,
                          start, rail_src):
        """One-shot done(response) closure for DEFERRED completion —
        built only when a handler actually calls cntl.defer()."""
        fired = [False]
        fired_mu = threading.Lock()

        def done(response=None):
            with fired_mu:
                if fired[0]:
                    raise RuntimeError(
                        f"done() called twice for "
                        f"{meta.service}.{meta.method}"
                        f" cid={meta.correlation_id}")
                fired[0] = True
            self._complete_request(sid, meta, span, cntl, spec, status,
                                   start, rail_src, response)

        return done

    def _complete_request(self, sid: int, meta: M.RpcMeta, span, cntl,
                          spec, status, start: float, rail_src,
                          response, exc: Exception | None = None) -> None:
        """Response path + accounting (SendRpcResponse analog,
        baidu_rpc_protocol.cpp:187).  Runs exactly once per accepted
        request — inline for plain handlers, from done() for deferred
        ones (then on the thread that called done)."""
        try:
            with rpcz.span_scope(span), \
                    rpcz.stage("rpc.server.respond", meta.correlation_id):
                self._respond(sid, meta, span, cntl, spec, status, start,
                              rail_src, response, exc)
        finally:
            # after the stage has ended: its phase is in the span
            span.end_us = rpcz.now_us()
            rpcz.submit(span)

    def _respond(self, sid: int, meta: M.RpcMeta, span, cntl, spec, status,
                 start: float, rail_src, response,
                 exc: Exception | None) -> None:
        # completion consumes the lazy done factory: a handler that
        # already responded and calls defer() afterwards now fails
        # loudly in defer() instead of minting a fresh once-guard and
        # double-sending
        cntl._done_factory = None
        error_code = 0
        try:
            if exc is not None:
                raise exc
            if cntl.failed():
                error_code = cntl.error_code
                if cntl.response_user_fields:
                    # fields ride FAILED completions too (the reference
                    # packs user fields on error responses): rich meta
                    # instead of the minimal native error pack
                    err = M.RpcMeta(msg_type=M.MSG_RESPONSE,
                                    correlation_id=meta.correlation_id,
                                    attempt=meta.attempt,
                                    error_code=cntl.error_code,
                                    error_text=cntl.error_text or
                                    errors.describe(cntl.error_code))
                    err.user_fields.update(M.normalize_user_fields(
                        cntl.response_user_fields))
                    Transport.instance().write_frame(sid, err.encode(), b"")
                else:
                    self._respond_error(sid, meta, cntl.error_code,
                                        cntl.error_text)
            elif rail_src is not None and self._ship_rail_response(
                    sid, meta, span, cntl, response, rail_src):
                pass  # response rode ICI; control frame already written
            else:
                res_ser = spec.response_serializer
                rbody, theader = res_ser.encode(response)
                if meta.compress_type:
                    rbody = compress(rbody, meta.compress_type)
                if (cntl._stream is None and not cntl.response_attachment
                        and not theader and not meta.compress_type
                        and not span.trace_id
                        and not cntl.response_user_fields):
                    # plain response: cid/attempt/content_type only — pack
                    # the meta and frame natively (PackResponseFrame)
                    span.response_size = len(rbody)
                    rc = Transport.send_response(
                        sid, meta.correlation_id, meta.attempt, 0, "",
                        res_ser.name, rbody)
                    if rc != 0:
                        # the response frame was dropped (overcrowded
                        # write queue or dead socket): nothing can reach
                        # this client, but the accounting must not claim
                        # success (reference SendRpcResponse logs the
                        # Write failure the same way)
                        error_code = errors.EOVERCROWDED if rc == -2 \
                            else errors.EFAILEDSOCKET
                        _dropped_responses.add(1)
                else:
                    resp = M.RpcMeta(msg_type=M.MSG_RESPONSE,
                                     correlation_id=meta.correlation_id,
                                     attempt=meta.attempt,
                                     compress_type=meta.compress_type,
                                     content_type=res_ser.name,
                                     tensor_header=theader,
                                     trace_id=span.trace_id,
                                     span_id=span.span_id)
                    if cntl.response_user_fields:
                        # same contract as the request side — ONE shared
                        # validation (meta.normalize_user_fields)
                        resp.user_fields.update(M.normalize_user_fields(
                            cntl.response_user_fields))
                    if cntl._stream is not None:
                        # tell the client our local stream id + window size
                        # (StreamSettings exchange in the reference)
                        resp.stream_id = cntl._stream.stream_id
                        resp.user_fields[M.F_SBUF] = \
                            str(cntl._stream.max_buf_size)
                        if cntl._stream.device is not None:
                            from brpc_tpu.ici import rail
                            resp.user_fields[M.F_SDEV] = \
                                rail.device_advert(cntl._stream.device)
                    if cntl.response_attachment:
                        resp.attachment_size = len(cntl.response_attachment)
                        rbody = rbody + cntl.response_attachment
                    span.response_size = len(rbody)
                    rc = Transport.instance().write_frame(sid, resp.encode(),
                                                          rbody)
                    if rc != 0:
                        error_code = errors.EOVERCROWDED if rc == -2 \
                            else errors.EFAILEDSOCKET
                        _dropped_responses.add(1)
        except errors.RpcError as e:
            # a typed failure keeps its code on the wire (the decode
            # phase wraps malformed payloads as EREQUEST; EINTERNAL for
            # those would misreport bad input as a server bug)
            error_code = e.code
            self._respond_error(sid, meta, e.code, str(e))
        except Exception as e:
            error_code = errors.EINTERNAL
            self._respond_error(sid, meta, errors.EINTERNAL,
                                f"{type(e).__name__}: {e}")
        finally:
            pooled = getattr(cntl, "_pooled_request", None)
            if pooled is not None:
                # the framework owns the request message; done has fired,
                # so return it (RpcPBMessageFactory Return semantics)
                cntl._pooled_request = None
                pb_message_pool.give_back(pooled)
            latency_us = int((time.monotonic() - start) * 1e6)
            status.on_responded(error_code, latency_us)
            if self._limiter is not None:
                self._limiter.on_responded(error_code, latency_us)
            span.error_code = error_code
            self._inflight_dec()

    def _ship_rail_response(self, sid: int, meta: M.RpcMeta, span, cntl,
                            response, rail_src: str) -> bool:
        """Return the response over the ICI rail: stage the handler's device
        arrays, transfer them to the requester's device, and write a
        control-only response frame carrying the claim ticket.  Returns
        False (caller host-serializes) when the response isn't device
        arrays, the transfer fails, or the response needs frame features
        the rail's control-only frame doesn't carry (stream settings,
        attachment bytes, user fields)."""
        from brpc_tpu.ici import rail
        if cntl._stream is not None or cntl.response_attachment \
                or cntl.response_user_fields:
            # user fields would be silently lost on the control-only
            # frame; the host path carries them
            return False
        if not rail.railable(response):
            return False
        try:
            target = rail.device_by_id(int(rail_src))
            ticket = rail.ship(response, target)
        except Exception:
            rail.rail_fallbacks.add(1)
            return False
        resp = M.RpcMeta(msg_type=M.MSG_RESPONSE,
                         correlation_id=meta.correlation_id,
                         attempt=meta.attempt,
                         content_type="tensor",
                         trace_id=span.trace_id,
                         span_id=span.span_id)
        resp.user_fields[M.F_TICKET] = ticket
        span.response_size = 0
        if Transport.instance().write_frame(sid, resp.encode(), b"") != 0:
            # peer gone: the ticket would leak until TTL — free it now
            rail.withdraw(ticket)
        return True

    def _total_concurrency(self) -> int:
        return sum(s.concurrency for s in self._method_status.values())

    # ---- RESTful bridge entry (builtin/router.py) ----

    def invoke_restful(self, service: str, method_name: str, payload):
        """Call a method on behalf of the HTTP JSON bridge, through the SAME
        gates as RPC traffic: auth (refused — HTTP carries no credential),
        interceptor, concurrency limiters, MethodStatus and inflight
        accounting.  Raises RpcError on any refusal."""
        if self._stopping:
            raise errors.RpcError(errors.ELOGOFF)
        if self.options.auth is not None:
            raise errors.RpcError(
                errors.ERPCAUTH, "RESTful access disabled on authed server")
        meta = M.RpcMeta(msg_type=M.MSG_REQUEST, service=service,
                         method=method_name, content_type="json")
        if self.options.interceptor is not None:
            code = _interceptor_code(self.options.interceptor(meta))
            if code is not None:
                raise errors.RpcError(code)
        key = (service, method_name)
        spec = self._methods.get(key)
        if spec is None:
            raise errors.RpcError(
                errors.ENOSERVICE if service not in self._services
                else errors.ENOMETHOD)
        if self._limiter is not None and not self._limiter.on_requested(
                self._total_concurrency() + 1):
            raise errors.RpcError(errors.ELIMIT)
        status = self._method_status[key]
        if not status.on_requested():
            if self._limiter is not None:
                self._limiter.on_responded(errors.ELIMIT, 0)
            raise errors.RpcError(errors.ELIMIT)
        self._inflight_inc()
        start = time.monotonic()
        error_code = 0
        try:
            cntl = Controller()
            cntl.is_server_side = True
            # json2pb bridge (reference json2pb/, restful.cpp): pb-typed
            # methods get the JSON body parsed into their message class,
            # and pb responses render back as JSON-able dicts
            from brpc_tpu.rpc.serialization import PbSerializer
            req_ser = spec.request_serializer
            if isinstance(req_ser, PbSerializer) and \
                    req_ser.message_class is not None and \
                    isinstance(payload, dict):
                from google.protobuf import json_format
                try:
                    payload = json_format.ParseDict(
                        payload, req_ser.message_class())
                except json_format.ParseError as e:
                    # client error (bad field/shape), not a server fault
                    raise errors.RpcError(errors.EREQUEST,
                                          f"json2pb: {e}")
            tag = self._service_tags.get(service)
            pool = self._tag_pools.get(tag) if tag is not None else None
            if pool is not None:
                # RESTful traffic honors the service's isolated pool too
                result = pool.submit(spec.fn, cntl, payload).result()
            else:
                result = spec.fn(cntl, payload)
            if result is not None and hasattr(result, "DESCRIPTOR"):
                from google.protobuf import json_format
                # proto field names, not camelCase: clients must get back
                # the same keys they sent (reference json2pb behavior)
                result = json_format.MessageToDict(
                    result, preserving_proto_field_name=True)
            if cntl.failed():
                error_code = cntl.error_code
                raise errors.RpcError(cntl.error_code, cntl.error_text)
            return result
        except errors.RpcError:
            raise
        except Exception as e:
            error_code = errors.EINTERNAL
            raise errors.RpcError(errors.EINTERNAL,
                                  f"{type(e).__name__}: {e}")
        finally:
            latency_us = int((time.monotonic() - start) * 1e6)
            status.on_responded(error_code, latency_us)
            if self._limiter is not None:
                self._limiter.on_responded(error_code, latency_us)
            self._inflight_dec()

    # ---- gRPC entry (policy/http2_rpc_protocol.cpp server role) ----

    def invoke_grpc(self, service: str, method_name: str, payload: bytes,
                    headers: dict[str, str],
                    peer_sid: Optional[int] = None,
                    payload_iter=None) -> tuple[bytes, int, str]:
        """Dispatch one gRPC request through the SAME gates as native
        traffic.  Returns (response_payload, error_code, error_text); the
        h2 connection maps error_code to a grpc-status trailer.
        payload_iter (BIDI): a live iterator of raw request messages —
        the handler receives a lazily-decoding iterator and may consume
        it while producing responses."""
        if self._stopping:
            return b"", errors.ELOGOFF, "server stopping"
        reg_name = service
        if service not in self._services and "." in service:
            # gRPC paths carry package-qualified names; fall back to the
            # bare service name our registry may have used
            bare = service.rsplit(".", 1)[1]
            if bare in self._services:
                reg_name = bare
        key = (reg_name, method_name)
        spec = self._methods.get(key)
        meta = M.RpcMeta(msg_type=M.MSG_REQUEST, service=key[0],
                         method=method_name, content_type="pb",
                         auth=headers.get("authorization", "").encode())
        if self.options.auth is not None:
            if not self.options.auth.verify_credential(meta.auth):
                return b"", errors.ERPCAUTH, "bad credential"
        if self.options.interceptor is not None:
            code = _interceptor_code(self.options.interceptor(meta))
            if code is not None:
                return b"", code, errors.describe(code)
        if spec is None:
            master = self.options.master_service
            if master is not None:
                # catch-all proxy dispatch, same as native traffic
                # (baidu_master_service, baidu_rpc_protocol.cpp:521-560)
                key = ("*", "*")
                spec = MethodSpec(
                    name="process",
                    fn=lambda cntl, req: master.process(cntl, req),
                    request_serializer=get_serializer("raw"),
                    response_serializer=get_serializer("raw"))
            elif key[0] not in self._services:
                return b"", errors.ENOSERVICE, f"unknown service {service!r}"
            else:
                return b"", errors.ENOMETHOD, f"unknown method {method_name!r}"
        if self._limiter is not None and not self._limiter.on_requested(
                self._total_concurrency() + 1):
            return b"", errors.ELIMIT, "server concurrency limit"
        status = self._method_status[key]
        if not status.on_requested():
            if self._limiter is not None:
                self._limiter.on_responded(errors.ELIMIT, 0)
            return b"", errors.ELIMIT, "method concurrency limit"
        self._inflight_inc()
        span = rpcz.new_span("server", key[0], method_name)
        span.annotate("protocol=grpc")
        start = time.monotonic()
        error_code = 0
        text = ""
        resp = b""
        streaming = False

        def _finish(code: int) -> None:
            # accounting + resource release, exactly once per call.  For
            # unary calls it runs in this function's finally; a STREAMING
            # call defers it to the end of frame transmission so graceful
            # join() waits for in-flight streams and the session object
            # stays borrowed while the generator body still runs.
            latency_us = int((time.monotonic() - start) * 1e6)
            status.on_responded(code, latency_us)
            if self._limiter is not None:
                self._limiter.on_responded(code, latency_us)
            span.error_code = code
            span.end_us = rpcz.now_us()
            rpcz.submit(span)
            self._inflight_dec()

        cntl = None
        try:
            if payload_iter is not None:
                # BIDI: decode lazily as the handler pulls
                req_ser = spec.request_serializer
                request = (req_ser.decode(p, "") for p in payload_iter)
                span.request_size = 0
            elif isinstance(payload, list):
                # CLIENT-STREAMING: one decoded message per request
                # frame; the handler receives the list
                request = [spec.request_serializer.decode(p, "")
                           for p in payload]
                span.request_size = sum(len(p) for p in payload)
            else:
                request = spec.request_serializer.decode(payload, "")
                span.request_size = len(payload)
            cntl = Controller()
            cntl.is_server_side = True
            cntl.request_meta = meta
            cntl.request_headers = dict(headers)   # gRPC metadata surface
            cntl.peer_sid = peer_sid
            rpcz.set_current_span(span)
            if self._session_pool is not None:
                cntl.session_data = self._session_pool.borrow()
            tag = self._service_tags.get(key[0])
            pool = self._tag_pools.get(tag) if tag is not None else None
            result = None
            try:
                if pool is not None:
                    # honor the service's isolated pool for gRPC too: the
                    # calling h2 worker blocks, but handler CONCURRENCY is
                    # bounded by the tag pool like native traffic
                    result = pool.submit(spec.fn, cntl, request).result()
                else:
                    result = spec.fn(cntl, request)
            finally:
                rpcz.set_current_span(None)
                # a streaming result keeps its session until the
                # generator finishes (the body runs per-item, later)
                if self._session_pool is not None and \
                        not hasattr(result, "__next__"):
                    self._session_pool.give_back(cntl.session_data)
                    cntl.session_data = None
            if cntl.failed():
                error_code, text = cntl.error_code, cntl.error_text
                if hasattr(result, "__next__"):
                    # failed AND returned a generator: the streaming
                    # branch below won't run, so release its resources
                    # here (the generator body never executes)
                    try:
                        result.close()
                    except Exception:
                        pass
                    if self._session_pool is not None:
                        self._session_pool.give_back(cntl.session_data)
                        cntl.session_data = None
            elif hasattr(result, "__next__"):
                # SERVER-STREAMING: each item is encoded lazily as the h2
                # layer pulls it into one gRPC frame.  Item production
                # stays bounded by the service's tag pool (one submit per
                # item); cleanup (session give-back + _finish accounting)
                # runs when the stream ends HOWEVER it ends — including
                # close() before the first item (a plain generator's
                # finally never runs if iteration never starts, which
                # leaked the inflight slot when the h2 layer bailed
                # between handler return and transmission).
                streaming = True
                span.annotate("server-streaming")

                def _cleanup(code, cn=cntl):
                    if self._session_pool is not None:
                        self._session_pool.give_back(cn.session_data)
                        cn.session_data = None
                    _finish(code)

                # BIDI handlers legitimately block awaiting the peer's
                # next message; pulling their items through the bounded
                # tag pool would park a pool worker for the call's
                # lifetime — the per-call dedicated thread is their
                # isolation instead
                resp = _StreamBody(result, spec.response_serializer,
                                   None if payload_iter is not None
                                   else pool, _cleanup)
            else:
                resp, _ = spec.response_serializer.encode(result)
                span.response_size = len(resp)
        except Exception as e:
            error_code = errors.EINTERNAL
            text = f"{type(e).__name__}: {e}"
        finally:
            if not streaming:
                _finish(error_code)
        return resp, error_code, text


# ---- global server registry (builtin services enumerate servers) ----

_servers: list[Server] = []
_servers_mu = threading.Lock()
# servers that installed the process-wide usercode budget/inline policy;
# the native flags are cleared only when the last owner joins
_usercode_policy_owners: set[int] = set()

# process-wide refcounts for the native method registry (several servers
# may expose the same (service, method); the registry is global)
_native_reg: dict[tuple[str, str], int] = {}
_native_reg_mu = threading.Lock()


def _native_method_register(key: tuple[str, str]) -> None:
    with _native_reg_mu:
        n = _native_reg.get(key, 0)
        _native_reg[key] = n + 1
        if n == 0:
            Transport.register_python_method(*key)


def _native_method_unregister(key: tuple[str, str]) -> None:
    with _native_reg_mu:
        n = _native_reg.get(key, 0)
        if n <= 1:
            _native_reg.pop(key, None)
            Transport.unregister_method(*key)
        else:
            _native_reg[key] = n - 1


def _register_server(s: Server) -> None:
    with _servers_mu:
        _servers.append(s)


def _unregister_server(s: Server) -> None:
    with _servers_mu:
        if s in _servers:
            _servers.remove(s)


def list_servers() -> list[Server]:
    with _servers_mu:
        return list(_servers)
