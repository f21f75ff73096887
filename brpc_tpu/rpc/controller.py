"""Controller — per-RPC context and completion state.

Role of the reference's brpc::Controller (controller.h:114; SURVEY.md §2.5):
carries options in (timeout, retries, compression), results out (error code/
text, response, attachment), and owns the call's completion state machine.
The retry/backup versioning trick of bthread_id (each attempt has its own
slot; stale attempts can't complete the call twice) is kept via the
(correlation_id, attempt) pair and a completion lock.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Optional

from brpc_tpu import errors, rpcz
from brpc_tpu.rpc import meta as M


class OneShotEvent:
    """threading.Event specialized for exactly-once RPC completion: a
    pre-acquired raw lock released by set().  Half the primitive lock
    operations of Event's Condition dance per sync call — the wait is
    ONE acquire on the completer's release, not an allocate/append/
    reacquire cycle.  set() is called once (the completion path is
    exactly-once via Controller._try_complete); a benign double-set is
    absorbed."""

    __slots__ = ("_lock", "_flag")

    def __init__(self):
        self._lock = threading.Lock()
        self._lock.acquire()
        self._flag = False

    def set(self) -> None:
        if not self._flag:
            self._flag = True
            try:
                self._lock.release()
            except RuntimeError:   # benign double-set race
                pass

    def is_set(self) -> bool:
        return self._flag

    def wait(self, timeout: float | None = None) -> bool:
        if self._flag:
            return True
        if timeout is None:
            acquired = self._lock.acquire()
        else:
            acquired = self._lock.acquire(True, timeout)
        if acquired:
            try:
                self._lock.release()   # pass the baton to other waiters
            except RuntimeError:       # absorbed the same double-set race
                pass                   # set() guards against
        return self._flag


class Controller:
    def __init__(self, *, timeout_ms: Optional[int] = None,
                 max_retry: Optional[int] = None,
                 backup_request_ms: Optional[int] = None,
                 compress_type: int = M.COMPRESS_NONE):
        # ---- client-side options (None = inherit from ChannelOptions) ----
        self.timeout_ms = timeout_ms
        self.max_retry = max_retry
        self.backup_request_ms = backup_request_ms
        self.compress_type = compress_type
        self.request_attachment: bytes = b""
        # consistent-hashing affinity key (reference
        # Controller::set_request_code): c_* balancers route by it
        self.request_code: Optional[int] = None
        # opaque per-request key/values riding the RpcMeta (reference
        # Controller::request_user_fields, baidu_rpc_meta.proto
        # user_fields); server handlers read cntl.request_meta.user_fields
        # — VALUES arrive there as bytes (wire convention, meta.py decode)
        self.user_fields: dict = {}
        # the response direction (Controller::response_user_fields):
        # server handlers SET this; the client reads it after completion
        # (values arrive as bytes, internal transport keys stripped).
        # Carried on native TRPC responses — including failed ones; gRPC
        # responses do not carry it (h2 trailers are status-only here)
        self.response_user_fields: dict = {}

        # ---- result state ----
        self.error_code: int = 0
        self.error_text: str = ""
        self.response: Any = None
        self.response_attachment: bytes = b""
        # server-side: the request body's wire size (set in the decode
        # phase) — handlers doing per-serializer wire-bytes accounting
        # (psserve_wire_bytes_*) read it instead of re-encoding
        self.request_body_size: int = 0
        self.trace_id: int = 0
        self.span_id: int = 0

        # ---- call bookkeeping ----
        self.correlation_id: int = 0
        self.current_attempt: int = 0
        self.retried_count: int = 0
        self.remote_side: str = ""
        self.latency_us: int = 0
        self._start_us: int = 0
        self._done_event: Optional["OneShotEvent"] = None
        self._done_cb: Optional[Callable[["Controller"], None]] = None
        self._completed = False
        self._lock = threading.Lock()
        self._timeout_timer = None
        self._backup_timer = None

        # ---- server-side state ----
        self.is_server_side = False
        self.request_meta: Optional[M.RpcMeta] = None
        # gRPC only: the request's h2 headers/metadata (":path",
        # "authorization", caller metadata...) — the reference exposes
        # gRPC metadata to handlers the same way
        self.request_headers: dict = {}
        self.peer_sid: int = 0
        # pooled per-request data (ServerOptions.session_data_factory)
        self.session_data = None
        # stream riding this RPC (see rpc/stream.py)
        self._stream = None
        # deferred completion (the reference's done Closure: SendRpcResponse
        # runs when the handler calls done->Run(), not when it returns —
        # baidu_rpc_protocol.cpp:398 passes done into svc->CallMethod)
        self._server_done: Optional[Callable[[Any], None]] = None
        self._done_factory: Optional[Callable[[], Callable]] = None
        self._deferred = False

    def accept_stream(self, handler=None, max_buf_size: int = 2 * 1024 * 1024,
                      device=None):
        """Server handler: accept the stream the client attached.
        `device` = where this side receives tensor payloads (rail)."""
        from brpc_tpu.rpc.stream import stream_accept
        return stream_accept(self, handler, max_buf_size, device=device)

    def defer(self) -> Callable[[Any], None]:
        """Server handler: switch this RPC to asynchronous completion.

        Returns a one-shot ``done(response)`` callable; the handler may
        return immediately (its return value is ignored) and any thread may
        later call ``done(response)`` to run the response path.  Until then
        the RPC is in-flight as a parked closure — data, not a thread —
        which is how 10k concurrent in-flight RPCs are served by a small
        worker pool (reference: brpc's done Closure + bthread parking;
        SURVEY.md §2.2, VERDICT r2 task 3)."""
        with self._lock:
            if not self.is_server_side or (self._server_done is None
                                           and self._done_factory is None):
                # also the LATE-defer case: inline completion consumed
                # the factory, so a handler that already responded and
                # defers afterwards fails loudly instead of silently
                # double-sending
                raise RuntimeError("defer() is only valid inside a server "
                                   "handler invocation")
            self._deferred = True
            if self._server_done is None:
                # the done closure (once-guard lock included) is built ON
                # DEMAND: the common non-deferred path completes inline
                # without allocating it per request.  One-shot: the
                # factory is consumed under the lock so concurrent
                # defer() calls share one closure/once-guard
                factory, self._done_factory = self._done_factory, None
                self._server_done = factory()
            return self._server_done

    # ---- result api (mirrors Controller::Failed/ErrorCode/ErrorText) ----

    def failed(self) -> bool:
        return self.error_code != 0

    def set_failed(self, code: int, text: str = "") -> None:
        self.error_code = code
        self.error_text = text or errors.describe(code)

    def set_failed_if_current(self, attempt: int, code: int,
                              text: str = "") -> bool:
        """set_failed iff the call is not completed AND `attempt` is
        still the current attempt — check and set atomically under the
        completion lock, so a stale failure path (a failed write racing
        a concurrently-completing response) can never overwrite a
        finished call's state.  Same discipline as reset_for_retry."""
        with self._lock:
            if self._completed or self.current_attempt != attempt:
                return False
            self.error_code = code
            self.error_text = text or errors.describe(code)
            return True

    def claim_retry(self, owner_attempt: int) -> bool:
        """Atomically claim ownership of the NEXT attempt: succeeds iff
        the call is not completed and `owner_attempt` is still current.
        The winner bumps current_attempt and clears the failed
        attempt's state (the reset_for_retry discipline) in the same
        critical section.  Two failure paths racing to retry the same
        attempt — the writer's failed-write path and the transport's
        failed-socket callback — resolve here to exactly ONE retry
        chain: the loser sees a stale attempt and stands down instead
        of issuing a duplicate attempt (or burning the retry budget
        twice and failing a call whose live attempt was about to
        succeed)."""
        with self._lock:
            if self._completed or self.current_attempt != owner_attempt:
                return False
            self.current_attempt += 1
            self.retried_count += 1
            self.error_code = 0
            self.error_text = ""
            self.response_user_fields = {}
            return True

    def claim_backup(self) -> bool:
        """Atomically take the next attempt number for a backup request
        (no error-state reset — the primary attempt stays live and the
        first response wins).  An unlocked += here would let a backup
        and a concurrent retry claim share one version number, and the
        stale-failure gates built on current_attempt stop gating."""
        with self._lock:
            if self._completed:
                return False
            self.current_attempt += 1
            self.retried_count += 1
            return True

    def reset_for_retry(self) -> None:
        # Guarded by the completion lock: a retry path that loses the
        # race to a concurrently-arriving completion (success response on
        # the dispatcher thread vs the failed-write retry on the caller
        # thread) must NOT wipe the finished call's error/response state
        # — the chaos suite's exactly-once invariant (the doomed extra
        # attempt it goes on to issue is dropped by the pending-table
        # lookup like any stale attempt).
        with self._lock:
            if self._completed:
                return
            self.error_code = 0
            self.error_text = ""
            # fields from a FAILED attempt must not leak into a later
            # successful completion
            self.response_user_fields = {}

    # ---- completion (exactly once) ----

    def _try_complete(self) -> bool:
        """Returns True for the winner; stale attempts/timeouts lose."""
        with self._lock:
            if self._completed:
                return False
            self._completed = True
            return True

    @property
    def completed(self) -> bool:
        return self._completed

    def join(self, extra_timeout_s: float = 5.0) -> None:
        """Block until the RPC completes (sync calls).  With timeout_ms=0
        (deadline disabled) this waits indefinitely."""
        if self._done_event is None:
            return
        with rpcz.stage("rpc.client.wait", self.correlation_id):
            self._wait_done(extra_timeout_s)

    def _wait_done(self, extra_timeout_s: float) -> None:
        if not self.timeout_ms or self.timeout_ms <= 0:
            self._done_event.wait()
            return
        # when the call was issued without a native deadline timer (sync
        # fast path), this thread enforces the deadline exactly — measured
        # from ISSUE time, not join time; otherwise leave slack for the
        # timer to fire first
        if getattr(self, "_sync_deadline", False):
            elapsed = time.monotonic() - self._start_us / 1e6
            budget = max(0.0, self.timeout_ms / 1e3 - elapsed)
        else:
            budget = self.timeout_ms / 1e3 + extra_timeout_s
        if not self._done_event.wait(budget):
            # The deadline timer should have fired; complete the call
            # properly (exactly-once, unregisters) instead of mutating a
            # still-pending controller.
            from brpc_tpu.rpc.channel import CallManager
            CallManager.instance().on_deadline(self.correlation_id)
            self._done_event.wait(1.0)

    def cancel(self) -> bool:
        """StartCancel analog (reference controller.h StartCancel /
        example/cancel_c++): fail this in-flight call with ECANCELED now;
        a late server response is dropped as a stale attempt."""
        from brpc_tpu.rpc.channel import CallManager
        return CallManager.instance().cancel(self.correlation_id)

    def raise_if_failed(self) -> None:
        if self.failed():
            raise errors.RpcError(self.error_code, self.error_text)
