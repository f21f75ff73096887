"""Chaos test suite — seeded fault injection over the full RPC/ICI data
path (brpc_tpu/fault.py).

Each scenario runs REAL client/server pairs over loopback under a
deterministic fault schedule and asserts the hard invariants the
recovery stack promises:

  * every call finishes exactly once, with a definite success or error
    (never a hang, never a double completion);
  * no leaked deadline/backup timers after calls complete;
  * block-pool occupancy and stream credit return to baseline after
    drain (duplicate-frame credit loss is explained by the
    reorder_replay_bytes_dropped counter, never silent);
  * broken endpoints get probed and revived once reachable, and the
    circuit-breaker isolation hold is respected while broken.

Scenarios are parametrized over three fixed seeds (override with
BRPC_CHAOS_SEEDS=..., comma-separated) so the schedule is a regression
artifact, not a dice roll.  `make chaos` runs exactly this file.
"""
import io
import os
import socket
import threading
import time

import numpy as np
import pytest

import brpc_tpu as brpc
from brpc_tpu import errors, fault
from brpc_tpu.butil.endpoint import str2endpoint
from brpc_tpu.rpc import meta as M
from brpc_tpu.rpc.channel import CallManager, SocketMap
from brpc_tpu.rpc.transport import Transport

from testutil import wait_until

SEEDS = [int(s) for s in
         os.environ.get("BRPC_CHAOS_SEEDS", "101,202,303").split(",")]


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------

@pytest.fixture(autouse=True)
def _chaos_hygiene():
    """Fast health probes for the duration, and NEVER leak an installed
    plan or broken-endpoint state into the rest of the suite."""
    from brpc_tpu.policy import health_check as hc
    old = hc.health_check_interval_s
    hc.health_check_interval_s = 0.05
    fault.clear()
    yield
    fault.clear()
    hc.health_check_interval_s = old
    hc.reset_all()


class EchoService(brpc.Service):
    NAME = "ChaosEcho"

    @brpc.method(request="json", response="json")
    def Echo(self, cntl, req):
        return {"msg": req["msg"]}


@pytest.fixture()
def server():
    s = brpc.Server()
    s.add_service(EchoService())
    s.start("127.0.0.1", 0)
    yield s
    s.stop()
    s.join()


class DoneCounter:
    """Counts completions — the exactly-once probe.  Locked: a double
    completion is by definition two threads racing into __call__, and an
    unsynchronized += could lose exactly the increment that proves it."""

    def __init__(self):
        self.n = 0
        self.cntl = None
        self.event = threading.Event()
        self._mu = threading.Lock()

    def __call__(self, cntl):
        with self._mu:
            self.n += 1
        self.cntl = cntl
        self.event.set()


def _timer_count() -> int:
    return len(Transport.instance()._timer_cbs)


def _pending_calls() -> int:
    return len(CallManager.instance()._pending)


def assert_quiesced(timers_before: int) -> None:
    """No call left pending, no deadline/backup timer leaked."""
    assert wait_until(lambda: _pending_calls() == 0, 10), \
        f"{_pending_calls()} calls still pending after chaos"
    assert wait_until(lambda: _timer_count() <= timers_before, 10), \
        f"timers leaked: {_timer_count()} > baseline {timers_before}"


# ---------------------------------------------------------------------------
# scenario 1: connection refused, retry succeeds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_connect_refused_then_retry(server, seed):
    port = server.port
    plan = fault.FaultPlan(seed).on(
        "transport.connect", fault.REFUSE, times=1,
        match=lambda ctx: ctx.get("port") == port)
    timers0 = _timer_count()
    ch = brpc.Channel(f"127.0.0.1:{port}", timeout_ms=5000, max_retry=3)
    done = DoneCounter()
    with fault.injected(plan):
        ch.call("ChaosEcho", "Echo", {"msg": "hi"}, serializer="json",
                done=done)
        assert done.event.wait(10), "call hung under connect fault"
    time.sleep(0.05)           # a double completion would land here
    assert done.n == 1
    assert not done.cntl.failed()
    assert done.cntl.response == {"msg": "hi"}
    assert plan.injected["transport.connect"] == 1
    assert_quiesced(timers0)


@pytest.mark.parametrize("seed", SEEDS)
def test_connect_refused_persistent_definite_error(server, seed):
    port = server.port
    plan = fault.FaultPlan(seed).on(
        "transport.connect", fault.REFUSE, times=-1,
        match=lambda ctx: ctx.get("port") == port)
    timers0 = _timer_count()
    ch = brpc.Channel(f"127.0.0.1:{port}", timeout_ms=2000, max_retry=2)
    done = DoneCounter()
    with fault.injected(plan):
        ch.call("ChaosEcho", "Echo", {"msg": "hi"}, serializer="json",
                done=done)
        assert done.event.wait(10), "call hung under persistent refusal"
    time.sleep(0.05)
    assert done.n == 1
    assert done.cntl.failed()
    assert done.cntl.error_code == errors.ECONNREFUSED
    # every attempt (first + 2 retries) was refused
    assert plan.injected["transport.connect"] == 3
    assert_quiesced(timers0)


# ---------------------------------------------------------------------------
# scenario 2: mid-call connection reset -> retry + probe revival
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_midcall_reset_retries_and_endpoint_revives(server, seed):
    from brpc_tpu.policy import health_check as hc
    port = server.port
    ep = str2endpoint(f"127.0.0.1:{port}")
    ch = brpc.Channel(f"127.0.0.1:{port}", timeout_ms=5000, max_retry=3)
    assert ch.call_sync("ChaosEcho", "Echo", {"msg": "warm"},
                        serializer="json") == {"msg": "warm"}
    sid = SocketMap.instance()._conns[ep].sid
    plan = fault.FaultPlan(seed).on(
        "transport.send", fault.RESET, times=1,
        match=lambda ctx: ctx.get("sid") == sid)
    timers0 = _timer_count()
    with fault.injected(plan):
        resp = ch.call_sync("ChaosEcho", "Echo", {"msg": "again"},
                            serializer="json")
    assert resp == {"msg": "again"}
    assert plan.injected["transport.send"] == 1
    # the reset marked the endpoint broken; the server is alive, so the
    # probe loop must revive it
    assert wait_until(lambda: not hc.is_broken(ep), 10), \
        "endpoint never revived after injected reset"
    assert_quiesced(timers0)


# ---------------------------------------------------------------------------
# scenario 3: corrupt frame on the gRPC/h2 plane -> definite outcome
# ---------------------------------------------------------------------------

class GrpcEcho(brpc.Service):
    NAME = "chaos.Grpc"

    @brpc.method(request="raw", response="raw")
    def Echo(self, cntl, req):
        return req


@pytest.mark.parametrize("seed", SEEDS)
def test_corrupt_h2_frame_definite_outcome(seed):
    from brpc_tpu.rpc.h2 import GrpcChannel
    srv = brpc.Server()
    srv.add_service(GrpcEcho())
    srv.start("127.0.0.1", 0)
    try:
        ch = GrpcChannel(f"127.0.0.1:{srv.port}", timeout_ms=3000)
        payload = b"chaos-payload-" * 8
        assert ch.call("chaos.Grpc", "Echo", payload) == payload   # warm
        sid = ch._conn.sid
        plan = fault.FaultPlan(seed).on(
            "transport.send", fault.CORRUPT, times=1,
            match=lambda ctx: ctx.get("sid") == sid)
        with fault.injected(plan):
            # one flipped byte mid-request: either the h2/HPACK framing
            # catches it (connection error -> RpcError) or it lands in
            # the opaque payload and the echo returns promptly — a
            # DEFINITE outcome within the deadline either way, never a
            # hang or a wedged connection
            try:
                ch.call("chaos.Grpc", "Echo", payload, timeout_ms=3000)
            except errors.RpcError:
                pass
        assert plan.injected["transport.send"] == 1
        # the plane must recover: a fresh call (reconnecting if the
        # corruption killed the connection) succeeds
        assert ch.call("chaos.Grpc", "Echo", b"after-chaos") == b"after-chaos"
        # the h2.send site covers the JOINED unary fast path too: an
        # injected send failure kills the connection -> definite error,
        # then the channel reconnects
        sid2 = ch._conn.sid
        plan2 = fault.FaultPlan(seed).on(
            "h2.send", fault.ERROR, times=1,
            match=lambda ctx: ctx.get("sid") == sid2)
        with fault.injected(plan2):
            with pytest.raises(errors.RpcError):
                ch.call("chaos.Grpc", "Echo", payload, timeout_ms=3000)
        assert plan2.injected["h2.send"] == 1
        assert ch.call("chaos.Grpc", "Echo", b"final") == b"final"
    finally:
        srv.stop()
        srv.join()


@pytest.mark.parametrize("seed", SEEDS)
def test_recv_drop_definite_outcome(seed):
    """`transport.recv` / `h2.recv` DROP (ISSUE 14: the fault-sites
    pass found both sites with ZERO referencing tests — injection
    surface that silently stopped being exercised).  transport.recv
    sees the Python message trampoline (stream traffic — the fault.py
    caveat: unary rides the C fast path), so the scenario drops one
    stream FEEDBACK frame at the TRANSPORT level and the cumulative-
    offset healing of scenario 8 must still hold; h2.recv drops one
    h2 frame on a live gRPC connection -> definite outcome, then the
    connection recovers."""
    N, MSG = 6, 512
    StreamSink.received = []
    StreamSink.got_all = threading.Event()
    StreamSink.want = 2 * N
    srv = brpc.Server()
    srv.add_service(StreamSink())
    srv.start("127.0.0.1", 0)
    try:
        ch = brpc.Channel(f"127.0.0.1:{srv.port}", timeout_ms=5000)
        cntl = brpc.Controller()
        stream = brpc.stream_create(cntl, None, max_buf_size=8192)
        assert ch.call_sync("ChaosStream", "Open", {}, serializer="json",
                            cntl=cntl) == {"ok": True}
        # one stream frame swallowed BELOW the stream layer, at the
        # client transport's recv trampoline — scoped by sid to the
        # CLIENT connection, where the only trampoline traffic is the
        # server's CONSUMED feedback; loss heals via the next
        # cumulative offset exactly like scenario 8
        client_sid = stream._sid
        plan = fault.FaultPlan(seed).on(
            "transport.recv", fault.DROP, times=1,
            match=lambda ctx: ctx.get("sid") == client_sid)
        with fault.injected(plan):
            for i in range(N):
                stream.write(bytes([i]) * MSG, timeout_s=10)
            assert wait_until(lambda: len(StreamSink.received) >= N, 10), \
                f"only {len(StreamSink.received)}/{N} delivered"
            assert plan.injected["transport.recv"] == 1
            for i in range(N):
                stream.write(bytes([N + i]) * MSG, timeout_s=10)
            assert StreamSink.got_all.wait(10), \
                f"only {len(StreamSink.received)}/{2 * N} delivered"
            assert wait_until(
                lambda: stream._produced - stream._remote_consumed == 0,
                10), "credit lost with the transport-dropped feedback " \
                     "frame never returned"
        stream.close()
    finally:
        srv.stop()
        srv.join()

    # the h2 layer's own recv site, over a live gRPC connection
    from brpc_tpu.rpc.h2 import GrpcChannel
    srv = brpc.Server()
    srv.add_service(GrpcEcho())
    srv.start("127.0.0.1", 0)
    try:
        gch = GrpcChannel(f"127.0.0.1:{srv.port}", timeout_ms=2000)
        assert gch.call("chaos.Grpc", "Echo", b"warm") == b"warm"
        plan2 = fault.FaultPlan(seed).on("h2.recv", fault.DROP, times=1)
        with fault.injected(plan2):
            try:
                gch.call("chaos.Grpc", "Echo", b"payload", timeout_ms=2000)
            except errors.RpcError:
                pass               # dropped frame -> definite error
        assert plan2.injected["h2.recv"] == 1
        assert gch.call("chaos.Grpc", "Echo", b"after") == b"after"
    finally:
        srv.stop()
        srv.join()


@pytest.mark.parametrize("seed", SEEDS)
def test_injected_write_error_does_not_leak_sockets(server, seed):
    """A plain injected write error (rc=-1, socket left open by the
    fault) must not leak the evicted connection: the retry path fails
    the socket so its fd + handler entries are reclaimed."""
    port = server.port
    ep = str2endpoint(f"127.0.0.1:{port}")
    ch = brpc.Channel(f"127.0.0.1:{port}", timeout_ms=5000, max_retry=3)
    assert ch.call_sync("ChaosEcho", "Echo", {"msg": "warm"},
                        serializer="json") == {"msg": "warm"}
    handlers0 = len(Transport.instance()._handlers)
    for k in range(3):
        sid = SocketMap.instance()._conns[ep].sid
        plan = fault.FaultPlan(seed + k).on(
            "transport.send", fault.ERROR, times=1,
            match=lambda ctx, s=sid: ctx.get("sid") == s)
        with fault.injected(plan):
            resp = ch.call_sync("ChaosEcho", "Echo", {"msg": f"r{k}"},
                                serializer="json")
        assert resp == {"msg": f"r{k}"}
        assert plan.injected["transport.send"] == 1
    # each failed-write socket (and its server-side accepted twin) must
    # be reclaimed through the normal failure path — at most the one
    # live replacement pair outlasts the loop
    assert wait_until(
        lambda: len(Transport.instance()._handlers) <= handlers0 + 2,
        10), (f"leaked socket handlers: "
              f"{len(Transport.instance()._handlers)} > {handlers0} + 2")


@pytest.mark.parametrize("seed", SEEDS)
def test_corrupt_unary_body_definite_outcome(server, seed):
    """CORRUPT on transport.send mangles the body even on the native
    fast-send path (a counted injection is never a no-op): the call ends
    definitively — either an error or a promptly-delivered (possibly
    altered) response — and the channel recovers."""
    port = server.port
    ep = str2endpoint(f"127.0.0.1:{port}")
    ch = brpc.Channel(f"127.0.0.1:{port}", timeout_ms=3000, max_retry=3)
    assert ch.call_sync("ChaosEcho", "Echo", {"msg": "warm"},
                        serializer="json") == {"msg": "warm"}
    sid = SocketMap.instance()._conns[ep].sid
    plan = fault.FaultPlan(seed).on(
        "transport.send", fault.CORRUPT, times=1,
        match=lambda ctx: ctx.get("sid") == sid)
    timers0 = _timer_count()
    with fault.injected(plan):
        try:
            ch.call_sync("ChaosEcho", "Echo", {"msg": "x" * 64},
                         serializer="json")
        except errors.RpcError:
            pass
    assert plan.injected["transport.send"] == 1
    assert ch.call_sync("ChaosEcho", "Echo", {"msg": "after"},
                        serializer="json") == {"msg": "after"}
    assert_quiesced(timers0)


# ---------------------------------------------------------------------------
# scenario 4: slow peer (delayed response) triggers the backup request
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_slow_response_triggers_backup_request(server, seed):
    port = server.port
    ep = str2endpoint(f"127.0.0.1:{port}")
    ch = brpc.Channel(f"127.0.0.1:{port}", timeout_ms=5000, max_retry=3,
                      backup_request_ms=100)
    assert ch.call_sync("ChaosEcho", "Echo", {"msg": "warm"},
                        serializer="json") == {"msg": "warm"}
    client_sid = SocketMap.instance()._conns[ep].sid
    # delay the SERVER's response send for the first attempt (the server
    # writes on its accepted socket, not client_sid); the backup attempt
    # races past it
    plan = fault.FaultPlan(seed).on(
        "transport.send", fault.LATENCY, latency_s=1.5, times=1,
        match=lambda ctx: ctx.get("sid") != client_sid)
    timers0 = _timer_count()
    cntl = brpc.Controller()
    with fault.injected(plan):
        t0 = time.monotonic()
        resp = ch.call_sync("ChaosEcho", "Echo", {"msg": "slowpoke"},
                            serializer="json", cntl=cntl)
        elapsed = time.monotonic() - t0
    assert resp == {"msg": "slowpoke"}
    assert cntl.retried_count >= 1, "backup request never fired"
    assert elapsed < 1.2, \
        f"call waited out the slow attempt ({elapsed:.2f}s) instead of " \
        "completing via the backup request"
    assert plan.injected["transport.send"] == 1
    # the delayed first response is a stale attempt: it must not
    # double-complete the call or leak its timers
    time.sleep(1.7 - elapsed if elapsed < 1.7 else 0)
    assert_quiesced(timers0)


# ---------------------------------------------------------------------------
# scenario 5: HBM block-pool exhaustion -> host-serialized fallback
# ---------------------------------------------------------------------------

class TensorEcho(brpc.Service):
    NAME = "ChaosTensor"

    @brpc.method(request="tensor", response="tensor")
    def Double(self, cntl, req):
        return req * 2


@pytest.mark.parametrize("seed", SEEDS)
def test_rail_transfer_fault_falls_back_to_host(seed):
    """An injected ICI transfer failure on the rail's fast path must
    degrade the call to host serialization, not fail it."""
    import jax
    import jax.numpy as jnp
    from brpc_tpu.ici import rail

    dev = jax.devices()[0]
    srv = brpc.Server(brpc.ServerOptions(ici_device=dev))
    srv.add_service(TensorEcho())
    srv.start("127.0.0.1", 0)
    try:
        ch = brpc.Channel(f"127.0.0.1:{srv.port}", timeout_ms=10000)
        x = jnp.arange(1024, dtype=jnp.float32)
        # warm call: compiles staging kernels, proves the rail path works
        warm = ch.call_sync("ChaosTensor", "Double", x, serializer="tensor")
        np.testing.assert_allclose(np.asarray(warm), np.asarray(x) * 2)
        fb0 = rail.rail_fallbacks.get_value()
        plan = fault.FaultPlan(seed).on("ici.send", fault.ERROR, times=1)
        timers0 = _timer_count()
        with fault.injected(plan):
            resp = ch.call_sync("ChaosTensor", "Double", x,
                                serializer="tensor")
        np.testing.assert_allclose(np.asarray(resp), np.asarray(x) * 2)
        assert plan.injected["ici.send"] == 1
        assert rail.rail_fallbacks.get_value() > fb0, \
            "failed rail transfer did not fall back to host serialization"
        assert_quiesced(timers0)
    finally:
        srv.stop()
        srv.join()


@pytest.mark.parametrize("seed", SEEDS)
def test_block_pool_exhaustion_releases_credit_and_blocks(seed):
    """Injected HBM block exhaustion mid-staging: the block pipe must
    fail definitively, release its window credit, leak no blocks — and
    the SAME transfer succeeds once the pool recovers."""
    import jax
    from brpc_tpu.ici.block_pool import get_block_pool
    from brpc_tpu.ici.endpoint import IciEndpoint

    dev = jax.devices()[0]
    pool = get_block_pool(dev)

    def occupancy():
        with pool._lock:
            return {c: len(pool._free[c]) for c in pool._free}

    free0 = occupancy()
    ep = IciEndpoint(dev)
    payload = bytes(range(256)) * (20 * 1024)   # 5MB -> three 2MB chunks
    try:
        # exhaustion strikes on the SECOND block of the staging run, so
        # the first block is already allocated and must be freed on the
        # error path
        plan = fault.FaultPlan(seed).on("ici.alloc", fault.EXHAUST,
                                        times=1, after=1)
        with fault.injected(plan):
            with pytest.raises(MemoryError):
                ep.send_bytes(payload, pool)
        assert plan.injected["ici.alloc"] == 1
        # invariants: no leaked blocks, no stuck window credit
        assert wait_until(lambda: occupancy() == free0, 10), \
            f"pool leaked blocks: {occupancy()} != {free0}"
        assert wait_until(lambda: ep.inflight_bytes == 0, 10), \
            f"window credit stuck: {ep.inflight_bytes}B in flight"
        # recovery: the same transfer succeeds with the fault cleared
        out = ep.send_bytes(payload, pool)
        got = b"".join(b.get() for b in out)
        assert got == payload
        for b in out:
            b.free()
        assert wait_until(lambda: occupancy() == free0, 10)
        assert wait_until(lambda: ep.inflight_bytes == 0, 10)
    finally:
        ep.close()


# ---------------------------------------------------------------------------
# scenario 6: DCN hop loss (client- and server-side) -> definite errors,
# next hop succeeds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_dcn_hop_loss_definite_error_then_recovery(seed):
    import jax.numpy as jnp
    from brpc_tpu.ici.channel import register_device_service
    from brpc_tpu.ici.dcn import DcnChannel

    register_device_service("ChaosMat", "Inc", lambda x: x + 1.0)
    srv = brpc.Server(enable_dcn=True)
    srv.start("127.0.0.1", 0)
    try:
        dch = DcnChannel(f"ici://127.0.0.1:{srv.port}/0", timeout_ms=10000)
        plan = (fault.FaultPlan(seed)
                .on("dcn.call", fault.ERROR, times=1)
                .on("dcn.serve", fault.ERROR, times=1))
        x = jnp.ones((8,), jnp.float32)
        timers0 = _timer_count()
        with fault.injected(plan):
            with pytest.raises(errors.RpcError):    # client-side hop loss
                dch.call_sync("ChaosMat", "Inc", x)
            # server-side hop loss: EINTERNAL is retryable, so the
            # channel re-issues and the second attempt lands — the hop
            # loss is healed TRANSPARENTLY by the recovery stack
            out = dch.call_sync("ChaosMat", "Inc", x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x) + 1.0)
        assert plan.injected == {"dcn.call": 1, "dcn.serve": 1}
        # persistent hop loss must end in a DEFINITE error (retries
        # exhausted), never a hang
        plan2 = fault.FaultPlan(seed).on("dcn.serve", fault.ERROR, times=-1)
        with fault.injected(plan2):
            with pytest.raises(errors.RpcError) as ei:
                dch.call_sync("ChaosMat", "Inc", x)
            assert ei.value.code == errors.EINTERNAL
        assert plan2.injected["dcn.serve"] >= 1
        # and the data path recovers once the chaos clears
        out2 = dch.call_sync("ChaosMat", "Inc", x)
        np.testing.assert_allclose(np.asarray(out2), np.asarray(x) + 1.0)
        assert_quiesced(timers0)
    finally:
        srv.stop()
        srv.join()


# ---------------------------------------------------------------------------
# scenario 7: duplicate DATA frames (transport replay) — dropped, counted,
# and the drain still balances the credit ledger
# ---------------------------------------------------------------------------

class StreamSink(brpc.Service):
    NAME = "ChaosStream"
    WINDOW = 1024
    received: list = []
    got_all = threading.Event()
    want = 0

    @brpc.method(request="json", response="json")
    def Open(self, cntl, req):
        def on_msg(stream, data):
            StreamSink.received.append(data)
            if len(StreamSink.received) >= StreamSink.want:
                StreamSink.got_all.set()
        cntl.accept_stream(on_msg, max_buf_size=self.WINDOW)
        return {"ok": True}


@pytest.mark.parametrize("seed", SEEDS)
def test_stream_duplicate_frames_credit_explained(seed):
    from brpc_tpu.rpc import stream as stream_mod
    N, MSG = 8, 512
    StreamSink.received = []
    StreamSink.got_all = threading.Event()
    StreamSink.want = N
    srv = brpc.Server()
    srv.add_service(StreamSink())
    srv.start("127.0.0.1", 0)
    try:
        ch = brpc.Channel(f"127.0.0.1:{srv.port}", timeout_ms=5000)
        cntl = brpc.Controller()
        stream = brpc.stream_create(cntl, None,
                                    max_buf_size=StreamSink.WINDOW)
        assert ch.call_sync("ChaosStream", "Open", {}, serializer="json",
                            cntl=cntl) == {"ok": True}
        drops0 = stream_mod.reorder_replays_dropped.get_value()
        bytes0 = stream_mod.reorder_replay_bytes_dropped.get_value()
        # every DATA frame to the SERVER's stream is delivered twice
        # (injected transport-level redelivery); the reorder layer must
        # drop each duplicate.  Scoped to this stream so concurrent
        # in-process streams can't consume the schedule.
        sink_id = stream.remote_id
        plan = fault.FaultPlan(seed).on(
            "stream.frame", fault.DUP, times=-1,
            match=lambda ctx: (ctx.get("msg_type") == M.MSG_STREAM_DATA
                               and ctx.get("stream_seq", 0) != 0
                               and ctx.get("stream_id") == sink_id))
        with fault.injected(plan):
            for i in range(N):
                stream.write(bytes([i]) * MSG, timeout_s=10)
            assert StreamSink.got_all.wait(10), \
                f"only {len(StreamSink.received)}/{N} delivered"
            # exactly-once, in-order delivery despite duplicates
            assert StreamSink.received == [bytes([i]) * MSG
                                           for i in range(N)]
            # the last frame's duplicate may still be in flight when the
            # handler fires got_all — wait for the full drop count
            assert wait_until(
                lambda: stream_mod.reorder_replays_dropped.get_value()
                - drops0 == N, 10), "duplicates not all dropped"
            dup_drops = stream_mod.reorder_replays_dropped.get_value() \
                - drops0
            dup_bytes = stream_mod.reorder_replay_bytes_dropped.get_value() \
                - bytes0
            assert dup_drops == N
            # the credit ledger: every byte of shortfall is explained by
            # the replay counter (ADVICE r5 — never a silent wedge)
            assert dup_bytes == dup_drops * MSG
            # delivered credit is acked back: the writer drains to zero
            # outstanding (window 1024, msg 512 -> feedback every msg)
            assert wait_until(
                lambda: stream._produced - stream._remote_consumed == 0,
                10), ("writer credit never returned: "
                      f"{stream._produced - stream._remote_consumed}B "
                      f"outstanding, {dup_bytes}B explained by replays")
        stream.close()
    finally:
        srv.stop()
        srv.join()


# ---------------------------------------------------------------------------
# scenario 8: lost CONSUMED feedback — credit return is delayed, not leaked
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_feedback_loss_heals_via_cumulative_offsets(seed):
    """Feedback offsets are CUMULATIVE: one lost CONSUMED frame delays
    credit return until the next crossing, it never leaks it.  The
    writer's window is sized above the total payload so it can always
    produce the traffic that forces that next crossing (a writer wedged
    at a full window can't — which is exactly why feedback rides the
    reliable socket in production)."""
    N, MSG = 6, 512
    StreamSink.received = []
    StreamSink.got_all = threading.Event()
    StreamSink.want = 2 * N
    srv = brpc.Server()
    srv.add_service(StreamSink())        # server recv window: 1024
    srv.start("127.0.0.1", 0)
    try:
        ch = brpc.Channel(f"127.0.0.1:{srv.port}", timeout_ms=5000)
        cntl = brpc.Controller()
        stream = brpc.stream_create(cntl, None, max_buf_size=8192)
        assert ch.call_sync("ChaosStream", "Open", {}, serializer="json",
                            cntl=cntl) == {"ok": True}
        sink_id = stream.remote_id
        # the FIRST feedback frame from the server's stream is lost
        # (scoped to this stream — see scenario 7)
        plan = fault.FaultPlan(seed).on(
            "stream.feedback", fault.DROP, times=1,
            match=lambda ctx: ctx.get("stream_id") == sink_id)
        with fault.injected(plan):
            # phase 1 guarantees at least one feedback crossing (3072B
            # consumed vs a 512B threshold) — the drop lands here
            for i in range(N):
                stream.write(bytes([i]) * MSG, timeout_s=10)
            assert wait_until(lambda: len(StreamSink.received) >= N, 10), \
                f"only {len(StreamSink.received)}/{N} delivered"
            assert plan.injected["stream.feedback"] == 1
            # phase 2 forces the NEXT crossing; its cumulative offset
            # must return phase 1's lost credit too
            for i in range(N):
                stream.write(bytes([N + i]) * MSG, timeout_s=10)
            assert StreamSink.got_all.wait(10), \
                f"only {len(StreamSink.received)}/{2 * N} delivered"
            assert wait_until(
                lambda: stream._produced - stream._remote_consumed == 0,
                10), "credit lost with the dropped feedback frame never " \
                     "returned (cumulative offsets should heal it)"
        stream.close()
    finally:
        srv.stop()
        srv.join()


# ---------------------------------------------------------------------------
# health-check revival under faults (satellite): CB hold + generation bump
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# scenario 10: paged KV cache — pool exhaustion + eviction failure mid-decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_kvcache_exhaustion_mid_decode_exactly_once_and_baseline(seed):
    """Injected KV faults uphold the paged-cache invariants (ISSUE 3):

    * `kvcache.page_alloc` exhausts the page pool mid-decode and
      `kvcache.evict` kills one pressure-relief attempt -> the affected
      requests complete exactly once with a definite error (ELIMIT),
      the untouched ones stream their full token sequences;
    * no shared page is freed while a forked sequence still references
      it — the fork's contents survive the chaos run bit-exact;
    * refcounts and BLOCK-POOL occupancy return to baseline once the
      sequences retire and the radix cache is dropped.
    """
    import jax

    from brpc_tpu.kvcache import KVCacheStore
    from brpc_tpu.serving import DecodeEngine

    store = KVCacheStore(page_bytes=256, page_tokens=4, max_blocks=16,
                         name=f"chaos_kv{seed}")
    device_pool = store.pagepool.pool

    def occupancy():
        with device_pool._lock:
            return {c: len(device_pool._free[c])
                    for c in device_pool._free}

    free0 = occupancy()

    @jax.jit
    def step(tokens, positions, pages):
        return tokens + 1

    engine = DecodeEngine(step, num_slots=3, store=store,
                          max_pages_per_slot=16,
                          name=f"chaos_kve{seed}")
    try:
        # a forked pair held LIVE across the whole chaos run: its shared
        # pages must never be reclaimed out from under it
        held = store.admit([1, 2, 3, 4, 5, 6])
        forked = store.fork(held)
        store.extend(held, 70)       # COW: tails diverge pre-chaos
        store.extend(forked, 80)
        held_words = store.pagepool.read(held.pages[-1], 3).tolist()
        fork_words = store.pagepool.read(forked.pages[-1], 3).tolist()

        plan = fault.FaultPlan(seed)
        plan.on("kvcache.page_alloc", fault.EXHAUST, times=2, after=6)
        plan.on("kvcache.evict", fault.ERROR, times=1)
        shared = list(range(100, 108))
        with fault.injected(plan):
            n = 12
            outcomes = []
            mu = threading.Lock()
            events = []
            for i in range(n):
                done = threading.Event()
                events.append(done)
                prompt = shared + [300 + i]

                def on_done(err, d=done):
                    with mu:
                        outcomes.append(0 if err is None else err.code)
                    d.set()

                engine.submit(prompt, 4, lambda t: None, on_done)
            for done in events:
                assert done.wait(30), "kvcache chaos request hung"
            # exactly once each: every request has ONE definite outcome
            assert len(outcomes) == n, f"{n - len(outcomes)} calls hung"
            assert plan.injected["kvcache.page_alloc"] == 2
            nerr = sum(1 for c in outcomes if c != 0)
            assert nerr >= 1, "injected exhaustion reached no request"
            assert all(c in (0, errors.ELIMIT) for c in outcomes), outcomes
        # the forked pair's shared prefix and diverged tails are intact:
        # eviction under pressure never touched referenced pages
        assert store.pagepool.read(held.pages[0]).tolist() == [1, 2, 3, 4]
        assert store.pagepool.read(held.pages[-1], 3).tolist() == held_words
        assert store.pagepool.read(forked.pages[-1], 3).tolist() == \
            fork_words
        store.pagepool.assert_consistent()
        # post-chaos the engine still serves
        assert engine.join_idle(10)
        done = threading.Event()
        toks = []
        engine.submit([7, 8, 9], 2, toks.append, lambda err: done.set())
        assert done.wait(20) and len(toks) == 2
        assert engine.join_idle(10)
        # baseline: retire everything, drop the cache -> refcounts zero
        # and every HBM block back in the device pool
        store.retire(held, cache=False)
        store.retire(forked, cache=False)
        assert store.stats()["live_seqs"] == 0
        store.clear()
        store.pagepool.assert_consistent()
        assert store.pagepool.blocks_leased() == 0
        assert wait_until(lambda: occupancy() == free0, 10), \
            f"KV blocks leaked: {occupancy()} != {free0}"
    finally:
        engine.close()
        store.close()


# ---------------------------------------------------------------------------
# scenario 11: engine crash mid-decode -> supervised failover over the
# surviving KV cache (ISSUE 4)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_engine_crash_midstream_failover_exactly_once(seed):
    """Injected `serving.step` crash mid-decode under an
    EngineSupervisor upholds the recovery invariants (ISSUE 4):

    * every in-flight generation completes with an exactly-once,
      BIT-EXACT token stream — no duplicated and no dropped token at
      the restart seam (the emitted-token cursor + resume from the
      last emitted token);
    * recovery re-decodes STRICTLY fewer tokens than a from-scratch
      replay whenever committed prefix pages existed: the detached
      sequences' full pages are committed to the radix tree, so
      re-admission prefix-hits and only the uncommitted tail
      re-prefills (re-decoded-token ratio < 1.0);
    * refcounts and BLOCK-POOL occupancy return to baseline once the
      wave retires and the cache is dropped — recovery pins are
      released, nothing leaks across the engine generations.
    """
    import gc

    import jax

    from brpc_tpu import native_path
    from brpc_tpu.kvcache import KVCacheStore
    from brpc_tpu.serving import DecodeEngine, EngineSupervisor

    store = KVCacheStore(page_bytes=256, page_tokens=4, max_blocks=32,
                         name=f"sup_chaos_kv{seed}")
    device_pool = store.pagepool.pool

    def occupancy():
        with device_pool._lock:
            return {c: len(device_pool._free[c])
                    for c in device_pool._free}

    free0 = occupancy()
    gc.collect()
    ring0 = native_path.tokring_live()

    @jax.jit
    def step(tokens, positions, pages):
        # position-dependent: the resumed stream is bit-exact ONLY if
        # recovery restores the exact (last token, position) cursor
        return (tokens * 7 + positions) % 997

    def expected(prompt, n):
        last, pos, out = prompt[-1], len(prompt), []
        for _ in range(n):
            last = (last * 7 + pos) % 997
            out.append(last)
            pos += 1
        return out

    calm = ({"queue_delay_us": float("inf"), "pool_ratio": 9.9,
             "queue_depth": 1e9},) * 3
    sup = EngineSupervisor(
        lambda: DecodeEngine(step, num_slots=3, store=store,
                             max_pages_per_slot=32,
                             name=f"sup_chaos_e{seed}"),
        store=store, heartbeat_deadline_s=5.0, check_interval_s=0.02,
        ladder=calm, name=f"sup_chaos{seed}")
    try:
        # warm the jit cache; commit the shared prefix by retiring one
        # clean completion into the radix tree
        shared = list(range(700, 708))           # two full pages
        done = threading.Event()
        sup.submit(shared + [1], 2, lambda t: None, lambda e: done.set())
        assert done.wait(30)
        assert sup.join_idle(10)
        h0 = store.hit_tokens.get_value()
        p0 = store.prompt_tokens.get_value()

        plan = fault.FaultPlan(seed)
        plan.on("serving.step", fault.ERROR, times=1, after=2)
        n = 9
        sinks = []
        with fault.injected(plan):
            for i in range(n):
                ev = threading.Event()
                toks: list = []
                errs: list = []
                sinks.append((ev, toks, errs))
                sup.submit(shared + [800 + i], 6, toks.append,
                           lambda e, ev=ev, errs=errs: (errs.append(e),
                                                        ev.set()))
            for ev, _, _ in sinks:
                assert ev.wait(60), "generation hung across the restart"
        assert plan.injected["serving.step"] == 1
        st = sup.stats()
        assert st["restarts"] == 1
        assert st["last_recovery"]["stolen_slots"] >= 1
        assert st["last_recovery"]["pinned_seqs"] >= 1, \
            "no committed prefix pages pinned at takeover"
        # exactly-once + bit-exact across the seam, for every request
        for i, (ev, toks, errs) in enumerate(sinks):
            assert errs == [None], f"req {i}: {errs}"
            assert toks == expected(shared + [800 + i], 6), \
                f"req {i}: stream diverged at the restart seam"
        # re-decoded-token ratio < 1.0: a from-scratch replay would
        # prefill every prompt token of every (re-)admission; the
        # committed prefix pages made some of that compute a cache hit
        dp = store.prompt_tokens.get_value() - p0
        dh = store.hit_tokens.get_value() - h0
        assert dp > 0
        ratio = (dp - dh) / dp
        assert ratio < 1.0, \
            "recovery re-decoded as much as a from-scratch replay"
        # baseline: pins released, sequences retired, cache dropped ->
        # refcounts consistent and every HBM block back in the pool
        assert sup.join_idle(10)
        assert store.stats()["live_seqs"] == 0
        store.clear()
        store.pagepool.assert_consistent()
        assert store.pagepool.blocks_leased() == 0
        assert wait_until(lambda: occupancy() == free0, 10), \
            f"KV blocks leaked across restart: {occupancy()} != {free0}"
    finally:
        sup.close()
        store.close()
    # ISSUE 9: the restart seam must not strand native emit rings —
    # every re-admitted request's old ring is freed with its request
    assert wait_until(
        lambda: (gc.collect(), native_path.tokring_live())[1] <= ring0,
        10), "native emit rings leaked across the engine restart"


# ---------------------------------------------------------------------------
# scenario 11b (ISSUE 10): engine crash mid-step with the REAL model
# runner -> recovery resumes bit-exact over real paged attention state
# ---------------------------------------------------------------------------

_mr_chaos_cache: dict = {}


def _mr_chaos_model():
    """One shared (cfg, params, dense-oracle cache) across the three
    seeds — the module-level jit cache in models/runner.py makes every
    seed after the first compile-free."""
    if not _mr_chaos_cache:
        from brpc_tpu.models.runner import (TransformerConfig,
                                            init_runner_params)
        cfg = TransformerConfig()
        _mr_chaos_cache["cfg"] = cfg
        _mr_chaos_cache["params"] = init_runner_params(cfg)
        _mr_chaos_cache["oracle"] = {}
    return _mr_chaos_cache


def _mr_expected(prompt, n) -> list:
    """Dense cache-less oracle for one prompt (memoized: the same
    prompts recur across seeds)."""
    from brpc_tpu.models.runner import dense_generate
    m = _mr_chaos_model()
    key = (tuple(prompt), n)
    if key not in m["oracle"]:
        m["oracle"][key] = dense_generate(m["params"], m["cfg"],
                                          prompt, n)
    return m["oracle"][key]


@pytest.mark.parametrize("seed", SEEDS)
def test_engine_crash_with_real_runner_resumes_bit_exact(seed):
    """The scenario 11 invariants upgraded from the token harness to
    the REAL TransformerRunner, with the crash injected INSIDE the
    model (`model.step_compute`, the ISSUE 10 fault site):

    * every stream completes exactly-once and matches the cache-less
      dense oracle token for token — recovery resumed from the emitted
      cursor over real paged K/V, re-prefilling only what the detached
      radix commit didn't cover;
    * the re-decode was cheaper than a from-scratch replay
      (hit-token delta > 0 across the restart);
    * page-pool refcounts and HBM block occupancy return to baseline.
    """
    import gc

    from brpc_tpu import native_path
    from brpc_tpu.models.runner import (TransformerRunner,
                                        make_store_for)
    from brpc_tpu.serving import DecodeEngine, EngineSupervisor

    m = _mr_chaos_model()
    cfg, params = m["cfg"], m["params"]
    store = make_store_for(cfg, page_tokens=4, max_blocks=32,
                           name=f"mr_chaos_kv{seed}")
    device_pool = store.pagepool.pool

    def occupancy():
        with device_pool._lock:
            return {c: len(device_pool._free[c])
                    for c in device_pool._free}

    free0 = occupancy()
    gc.collect()
    ring0 = native_path.tokring_live()
    runner = TransformerRunner(params, cfg, store=store,
                               name=f"mr_chaos_m{seed}")
    calm = ({"queue_delay_us": float("inf"), "pool_ratio": 9.9,
             "queue_depth": 1e9},) * 3
    sup = EngineSupervisor(
        lambda: DecodeEngine(runner=runner, num_slots=2, store=store,
                             max_pages_per_slot=24,
                             prefill_buckets=(8, 16),
                             name=f"mr_chaos_e{seed}"),
        store=store, heartbeat_deadline_s=10.0, check_interval_s=0.02,
        ladder=calm, name=f"mr_chaos{seed}")
    try:
        # jit warm + commit a shared 2-page prefix into the radix tree
        shared = [50, 61, 12, 73, 24, 85, 36, 97]
        done = threading.Event()
        sup.submit(shared + [1], 2, lambda t: None,
                   lambda e: done.set())
        assert done.wait(120)
        assert sup.join_idle(30)
        h0 = store.hit_tokens.get_value()
        p0 = store.prompt_tokens.get_value()

        plan = fault.FaultPlan(seed)
        plan.on("model.step_compute", fault.ERROR, times=1, after=2)
        prompts = [shared + [100 + i] for i in range(4)]
        sinks = []
        with fault.injected(plan):
            for p in prompts:
                ev = threading.Event()
                toks: list = []
                errs: list = []
                sinks.append((ev, toks, errs))
                sup.submit(p, 5, toks.append,
                           lambda e, ev=ev, errs=errs: (errs.append(e),
                                                        ev.set()))
            for ev, _, _ in sinks:
                assert ev.wait(180), \
                    "generation hung across the restart"
        assert plan.injected["model.step_compute"] == 1
        st = sup.stats()
        assert st["restarts"] == 1
        assert st["last_recovery"]["stolen_slots"] >= 1
        # exactly-once + bit-exact vs the DENSE oracle: the resumed
        # stream rode real paged K/V across detach/re-admit/prefill
        for p, (ev, toks, errs) in zip(prompts, sinks):
            assert errs == [None], f"{p[-1]}: {errs}"
            assert toks == _mr_expected(p, 5), \
                f"req {p[-1]}: real-runner stream diverged at the seam"
        # cheaper than a from-scratch replay: some prompt tokens were
        # served by committed pages (shared prefix and/or recovery)
        dp = store.prompt_tokens.get_value() - p0
        dh = store.hit_tokens.get_value() - h0
        assert dp > 0 and (dp - dh) / dp < 1.0, \
            "recovery re-decoded as much as a from-scratch replay"
        assert sup.join_idle(30)
        assert store.stats()["live_seqs"] == 0
        store.clear()
        store.pagepool.assert_consistent()
        assert store.pagepool.blocks_leased() == 0
        assert wait_until(lambda: occupancy() == free0, 10), \
            f"KV blocks leaked: {occupancy()} != {free0}"
    finally:
        sup.close()
        store.close()
    assert wait_until(
        lambda: (gc.collect(), native_path.tokring_live())[1] <= ring0,
        10), "native emit rings leaked across the real-runner restart"


# ---------------------------------------------------------------------------
# scenario 12: engine crash mid-decode -> ONE generation trace linking
# pre- and post-crash spans (ISSUE 5, same seeds as scenario 11)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_engine_crash_yields_one_linked_trace(seed):
    """rpcz generation tracing upholds trace CONTINUITY across crash
    recovery: an injected `serving.step` crash mid-decode yields, for
    each interrupted generation, ONE trace whose post-crash attempt
    span carries the SAME trace_id, links its predecessor via
    ``recovered_from``, and annotates the resume cursor and the
    re-decoded-token count — the timeline a person debugging "why was
    this generation slow" actually needs."""
    import jax

    from brpc_tpu import rpcz
    from brpc_tpu.kvcache import KVCacheStore
    from brpc_tpu.serving import DecodeEngine, EngineSupervisor

    store = KVCacheStore(page_bytes=256, page_tokens=4, max_blocks=32,
                         name=f"tr_chaos_kv{seed}")

    @jax.jit
    def step(tokens, positions, pages):
        return (tokens * 7 + positions) % 997

    calm = ({"queue_delay_us": float("inf"), "pool_ratio": 9.9,
             "queue_depth": 1e9},) * 3
    sup = EngineSupervisor(
        lambda: DecodeEngine(step, num_slots=3, store=store,
                             max_pages_per_slot=32,
                             name=f"tr_chaos_e{seed}"),
        store=store, heartbeat_deadline_s=5.0, check_interval_s=0.02,
        ladder=calm, name=f"tr_chaos{seed}")
    rpcz.set_enabled(True)
    try:
        shared = list(range(300, 308))
        done = threading.Event()
        sup.submit(shared + [1], 2, lambda t: None, lambda e: done.set())
        assert done.wait(30)
        assert sup.join_idle(10)

        plan = fault.FaultPlan(seed)
        plan.on("serving.step", fault.ERROR, times=1, after=2)
        sinks = []
        with fault.injected(plan):
            for i in range(6):
                ev = threading.Event()
                errs: list = []
                sinks.append((ev, errs))
                sup.submit(shared + [400 + i], 6, lambda t: None,
                           lambda e, ev=ev, errs=errs: (errs.append(e),
                                                        ev.set()))
            for ev, _ in sinks:
                assert ev.wait(60), "generation hung across the restart"
        assert plan.injected["serving.step"] == 1
        for ev, errs in sinks:
            assert errs == [None]
        assert sup.stats()["restarts"] == 1

        # every interrupted generation produced one recovered_from-
        # linked trace: >= 1 such trace exists, each holding BOTH
        # attempt spans under one trace_id plus both decode spans
        spans = rpcz.recent_spans(limit=2048)
        gens: dict = {}
        for s in spans:
            if s.kind == "generation" and s.method == f"tr_chaos{seed}":
                gens.setdefault(s.trace_id, []).append(s)
        linked = []
        for tid, group in gens.items():
            if len(group) < 2:
                continue
            group.sort(key=lambda s: s.span_id)
            if group[1].recovered_from == group[0].span_id:
                linked.append((tid, group))
        assert linked, \
            f"seed {seed}: no trace links pre- and post-crash attempts"
        full_seam = 0
        for tid, group in linked:
            notes = " | ".join(m for _, m in group[1].annotations)
            assert "recovered_from=span" in notes
            assert "resume_cursor=" in notes
            assert "re_decoded_tokens=" in notes, \
                f"seed {seed}: re-decoded tokens not annotated: {notes}"
            trace = [s for s in spans if s.trace_id == tid]
            decode_spans = [s for s in trace if s.kind == "decode"]
            # a generation that was IN a slot at crash time shows both
            # decode attempts: the pre-crash span closed ELOGOFF at
            # takeover plus the post-crash one; a generation still
            # QUEUED at the crash legitimately has only the second
            if len(decode_spans) >= 2 and any(
                    s.error_code == errors.ELOGOFF for s in decode_spans):
                full_seam += 1
        assert full_seam >= 1, \
            f"seed {seed}: no trace shows the full pre-crash/post-crash " \
            f"decode seam (stolen slots: " \
            f"{sup.stats()['last_recovery']['stolen_slots']})"
    finally:
        rpcz.set_enabled(False)
        sup.close()
        store.clear()
        store.close()


class TestHealthCheckRevival:
    def test_probe_respects_isolation_hold_while_reachable(self, server):
        """The circuit breaker's isolation hold (_hold_until) must be
        respected even when the endpoint is ALREADY reachable — the
        probe may connect, but revival waits out the hold."""
        from brpc_tpu.policy import health_check as hc
        ep = str2endpoint(f"127.0.0.1:{server.port}")
        t0 = time.monotonic()
        hc.mark_broken(ep, hold_s=0.6)
        assert hc.is_broken(ep)
        time.sleep(0.3)
        assert hc.is_broken(ep), "revived inside the CB isolation hold"
        assert wait_until(lambda: not hc.is_broken(ep), 10), \
            "reachable endpoint never revived after the hold elapsed"
        assert time.monotonic() - t0 >= 0.6

    def test_reset_all_generation_stands_down_probes(self):
        """A probe loop started before reset_all() must exit WITHOUT
        reviving the endpoint into the deliberately-cleared state, even
        if the endpoint becomes reachable afterwards."""
        from brpc_tpu.policy import health_check as hc
        s = socket.socket()
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        ep = str2endpoint(f"127.0.0.1:{port}")
        revived0 = hc._revived_counter.get_value()
        hc.mark_broken(ep)          # unreachable: probe loop spins
        assert hc.is_broken(ep)
        hc.reset_all()              # generation bump clears everything
        assert not hc.is_broken(ep)
        # NOW the endpoint comes up: the old-generation probe connects,
        # sees the bump, and stands down without touching state
        lst = socket.socket()
        lst.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        lst.bind(("127.0.0.1", port))
        lst.listen(8)
        try:
            assert wait_until(lambda: ep not in hc._probe_threads, 10), \
                "stale-generation probe thread never stood down"
            assert hc._revived_counter.get_value() == revived0, \
                "stale-generation probe fired a revival"
            assert not hc.is_broken(ep)
        finally:
            lst.close()


# ---------------------------------------------------------------------------
# the fault layer itself: determinism + disabled-by-default
# ---------------------------------------------------------------------------

class TestFaultLayer:
    def test_disabled_by_default_and_noop(self):
        assert fault.ENABLED is False
        assert fault.hit("transport.send") is None

    def test_seeded_schedule_replays_exactly(self):
        def run(seed):
            plan = fault.FaultPlan(seed)
            plan.on("chaos.unit", fault.DROP, times=-1, prob=0.3)
            with fault.injected(plan):
                return [fault.hit("chaos.unit") is not None
                        for _ in range(64)]
        assert run(7) == run(7), "same seed must replay the same schedule"
        assert run(7) != run(8), "different seeds must differ"

    def test_after_and_times_fire_by_hit_index(self):
        plan = fault.FaultPlan(0)
        plan.on("chaos.idx", fault.ERROR, times=2, after=3)
        with fault.injected(plan):
            fired = [fault.hit("chaos.idx") is not None for _ in range(8)]
        assert fired == [False, False, False, True, True,
                         False, False, False]

    def test_match_scopes_rules(self):
        plan = fault.FaultPlan(0)
        plan.on("chaos.match", fault.ERROR, times=1,
                match=lambda ctx: ctx.get("who") == "target")
        with fault.injected(plan):
            assert fault.hit("chaos.match", who="bystander") is None
            assert fault.hit("chaos.match", who="target") is not None
            assert fault.hit("chaos.match", who="target") is None

    def test_injected_counts_reach_bvar(self):
        before = fault.injected_counts().get("chaos.bvar", 0)
        plan = fault.FaultPlan(0).on("chaos.bvar", fault.DROP, times=2)
        with fault.injected(plan):
            fault.hit("chaos.bvar")
            fault.hit("chaos.bvar")
        assert fault.injected_counts()["chaos.bvar"] == before + 2


# ---------------------------------------------------------------------------
# ADVICE r5 regressions
# ---------------------------------------------------------------------------

class TestAdviceRegressions:
    def test_recordio_crc_fail_short_tail_returns_none(self):
        """A damaged record at EOF followed by a sub-magic-sized tail
        must end the stream (return None) — NOT rescan its own payload
        and fabricate a record from embedded MAGIC bytes."""
        from brpc_tpu.butil.recordio import RecordReader, RecordWriter
        buf = io.BytesIO()
        w = RecordWriter(buf)
        w.write(b"first-record")
        rec1_len = buf.tell()
        # second record's body EMBEDS a complete valid record — the
        # fabrication bait (rpc_dump bodies are raw network bytes)
        inner = io.BytesIO()
        RecordWriter(inner).write(b"FAKE")
        w.write(b"xx" + inner.getvalue() + b"yy")
        data = bytearray(buf.getvalue())
        # corrupt one body byte OUTSIDE the embedded record: crc fails,
        # lengths stay intact
        data[rec1_len + 20] ^= 0xFF          # the leading 'x'
        data += b"Zq"                        # short (<4B) damaged tail
        r = RecordReader(io.BytesIO(bytes(data)))
        assert r.read() == (b"", b"first-record")
        assert r.read() is None, \
            "fabricated a record from bytes inside a damaged tail record"

    def test_recordio_crc_fail_aligned_next_record_still_skips(self):
        """Counter-case: when the next bytes ARE a magic, the damaged
        record is skipped in place and the next record survives."""
        from brpc_tpu.butil.recordio import RecordReader, RecordWriter
        buf = io.BytesIO()
        w = RecordWriter(buf)
        w.write(b"victim")
        next_off = buf.tell()
        w.write(b"survivor")
        data = bytearray(buf.getvalue())
        data[next_off - 1] ^= 0xFF           # corrupt victim's body tail
        r = RecordReader(io.BytesIO(bytes(data)))
        assert r.read() == (b"", b"survivor")
        assert r.read() is None

    def test_h2_respond_error_claims_stream_atomically(self):
        """Only ONE responder may emit trailers HEADERS on a stream: the
        claim happens under _fc, so a backlog shed and a finishing
        handler can never both respond (ADVICE r5)."""
        from brpc_tpu.rpc.h2 import GrpcServerConnection

        class _RecordingTp:
            def __init__(self):
                self.writes = []

            def write_raw(self, sid, data):
                self.writes.append(bytes(data))
                return 0

            def close(self, sid, err=0):
                pass

            def alive(self, sid):
                return True

        conn = GrpcServerConnection(sock_id=(1 << 62), server=None)
        tp = _RecordingTp()
        conn._tp = tp
        conn.open_stream(1)
        conn._respond_error(1, 13, "boom")
        assert len(tp.writes) == 1, "error trailers not sent"
        conn._respond_error(1, 13, "again")
        assert len(tp.writes) == 1, "duplicate trailers HEADERS emitted"
        # handler wins the claim first: a late shed stays silent
        conn.open_stream(3)
        assert conn.claim_responder(3) is True
        conn._respond_error(3, 13, "late shed")
        assert len(tp.writes) == 1
        assert conn.claim_responder(3) is False
        # closed streams are unclaimable
        conn.close_stream(3)
        assert conn.claim_responder(3) is False

    def test_stream_duplicate_data_bytes_counted(self):
        """Dropped replayed DATA frames consume sender credit forever;
        the byte counter must account for them (ADVICE r5)."""
        from brpc_tpu.rpc import stream as sm
        got = []
        s = sm.Stream(999_999_001, sm._FnHandler(
            lambda st, m: got.append(m)))
        c0 = sm.reorder_replays_dropped.get_value()
        b0 = sm.reorder_replay_bytes_dropped.get_value()
        s._on_data(b"abc", 3, 1)
        s._on_data(b"abc", 3, 1)         # transport replay
        assert got == [b"abc"], "duplicate delivered to the handler"
        assert sm.reorder_replays_dropped.get_value() == c0 + 1
        assert sm.reorder_replay_bytes_dropped.get_value() == b0 + 3


# ---------------------------------------------------------------------------
# scenario 9: serving layer — mid-batch failure + KV slot lease failure
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_serving_midbatch_fault_exactly_once_and_kv_baseline(seed):
    """Injected serving faults uphold the serving layer's invariants:

    * `serving.batch` fires mid-batch -> EVERY member call of that batch
      completes exactly once with a definite error (never neither, never
      a partial scatter), calls in other batches succeed, and the
      batcher's queue accounting returns to baseline;
    * `serving.slot_alloc` fails one KV lease -> that request gets a
      definite error, the step loop keeps serving the others, and
      block-pool occupancy returns to baseline (no leaked KV blocks).
    """
    import jax
    import numpy as np

    from brpc_tpu.serving import DecodeEngine, DynamicBatcher, \
        register_serving

    traces = []

    def _fn(x):
        traces.append(tuple(x.shape))
        return x.sum(axis=1)

    batcher = DynamicBatcher(
        jax.jit(_fn), max_batch_size=4, max_delay_us=30_000,
        length_buckets=(16,), name=f"chaos_b{seed}")

    @jax.jit
    def step(tokens, positions):
        return tokens + 1

    from brpc_tpu.ici.block_pool import get_block_pool
    pool = get_block_pool(jax.devices()[0])

    def occupancy():
        with pool._lock:
            return {c: len(pool._free[c]) for c in pool._free}

    free0 = occupancy()
    import gc

    from brpc_tpu import native_path
    gc.collect()
    ring0 = native_path.tokring_live()
    engine = DecodeEngine(step, num_slots=2, kv_bytes_per_slot=1024,
                          pool=pool, name=f"chaos_e{seed}")
    s = brpc.Server()
    register_serving(s, batcher=batcher, engine=engine,
                     http_generate_path=None)
    s.start("127.0.0.1", 0)
    # max_retry=0: the injected batch failure must surface as the
    # definite error it is, not be papered over by a client retry
    ch = brpc.Channel(f"127.0.0.1:{s.port}", timeout_ms=10_000,
                      max_retry=0)
    try:
        plan = fault.FaultPlan(seed)
        plan.on("serving.batch", fault.ERROR, times=1)
        plan.on("serving.slot_alloc", fault.ERROR, times=1)
        with fault.injected(plan):
            # ---- phase 1: mid-batch failure over real RPC ----
            n = 12
            outcomes = []
            mu = threading.Lock()

            def one():
                try:
                    r = ch.call_sync("Serving", "Score", {"x": [1.0, 2.0]},
                                     serializer="json")
                    code = 0
                    assert r["y"] == 3.0
                except errors.RpcError as e:
                    code = e.code
                with mu:
                    outcomes.append(code)

            ts = [threading.Thread(target=one) for _ in range(n)]
            [t.start() for t in ts]
            [t.join(30) for t in ts]
            # exactly once each: every call has ONE definite outcome
            assert len(outcomes) == n, f"{n - len(outcomes)} calls hung"
            assert plan.injected["serving.batch"] == 1
            nerr = sum(1 for c in outcomes if c != 0)
            assert nerr >= 1, "injected batch failure reached no caller"
            assert all(c in (0, errors.EINTERNAL) for c in outcomes)
            st = batcher.stats()
            assert st["queued"] == 0
            assert st["completed"] + st["errors"] == n

            # ---- phase 2: KV slot lease failure mid-admission ----
            sinks = []
            for i in range(4):
                done = threading.Event()
                toks = []
                errbox = []
                sinks.append((done, toks, errbox))
                engine.submit(
                    [i * 10], 3, toks.append,
                    lambda err, d=done, eb=errbox: (eb.append(err),
                                                    d.set()))
            for done, _, _ in sinks:
                assert done.wait(30), "engine request hung"
            assert plan.injected["serving.slot_alloc"] == 1
            errs = [eb[0] for _, _, eb in sinks]
            failed = [e for e in errs if e is not None]
            assert len(failed) == 1 and failed[0].code == errors.ELIMIT
            for (_, toks, eb), i in zip(sinks, range(4)):
                if eb[0] is None:
                    assert toks == [i * 10 + 1, i * 10 + 2, i * 10 + 3]
        # post-chaos: occupancy back to baseline, engine still serves
        assert engine.join_idle(10)
        assert wait_until(lambda: occupancy() == free0, 10), \
            f"KV blocks leaked: {occupancy()} != {free0}"
        done = threading.Event()
        toks = []
        engine.submit([7], 2, toks.append, lambda err: done.set())
        assert done.wait(20) and toks == [8, 9]
    finally:
        s.stop()
        s.join()
        batcher.close()
        engine.close()
        assert wait_until(lambda: occupancy() == free0, 10)
        # ISSUE 9: zero leaked native emit rings across the wave
        assert wait_until(
            lambda: (gc.collect(), native_path.tokring_live())[1]
            <= ring0, 10), "native emit rings leaked"


# ---------------------------------------------------------------------------
# scenario 13: cross-host KV migration faults + prefill-process death ->
# standby failover (ISSUE 7)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_migration_faults_exactly_once_with_recompute_fallback(seed):
    """Injected faults at every migration site mid-disagg uphold the
    data-plane invariants (ISSUE 7):

    * `dcn.migrate_send` / `dcn.migrate_recv` / `migrate.splice` fire
      mid-migration -> the SOURCE's pinned pages are released (refcounts
      and occupancy to baseline), the DESTINATION either fully splices
      or fully rolls back (its radix tree never serves a half-imported
      chain), and every generation completes exactly once, bit-exact,
      via the recompute fallback;
    * after the chaos window, migration works again and both pools
      return to block baseline once caches drop.
    """
    import jax

    from brpc_tpu.kvcache import KVCacheStore
    from brpc_tpu.migrate import (DisaggCoordinator,
                                  register_disagg_decode,
                                  register_disagg_prefill)
    from brpc_tpu.serving import DecodeEngine

    PT = 4

    @jax.jit
    def step(tokens, positions, pages):
        return (tokens * 7 + positions) % 997

    def expected(prompt, n):
        last, pos, out = prompt[-1], len(prompt), []
        for _ in range(n):
            last = (last * 7 + pos) % 997
            out.append(last)
            pos += 1
        return out

    dstore = KVCacheStore(page_tokens=PT, page_bytes=256, max_blocks=32,
                          name=f"mig_chaos_dec{seed}")
    device_pool = dstore.pagepool.pool

    def occupancy():
        with device_pool._lock:
            return {c: len(device_pool._free[c])
                    for c in device_pool._free}

    free0 = occupancy()
    eng = DecodeEngine(step, num_slots=4, store=dstore,
                       max_pages_per_slot=32,
                       name=f"mig_chaos_eng{seed}")
    dsrv = brpc.Server(enable_dcn=True)
    register_disagg_decode(dsrv, dstore, eng)
    dsrv.start("127.0.0.1", 0)
    pstore = KVCacheStore(page_tokens=PT, page_bytes=256, max_blocks=32,
                          name=f"mig_chaos_pre{seed}")
    psrv = brpc.Server(enable_dcn=True)
    replica = register_disagg_prefill(psrv, pstore,
                                      f"127.0.0.1:{dsrv.port}")
    psrv.start("127.0.0.1", 0)
    try:
        co = DisaggCoordinator(f"127.0.0.1:{psrv.port}",
                               f"127.0.0.1:{dsrv.port}")
        # warm the jit cache outside the fault window
        warm = [9_000_000 + seed, 1, 2]
        out = co.generate(warm, 1)
        assert out["error"] is None

        n = 8
        prompts = [[seed * 100 + 1000 * g + j for j in range(13)]
                   for g in range(n)]
        # one fault per site, staggered by seeded offsets so different
        # migrations (and different phases) take the hit each seed
        plan = fault.FaultPlan(seed)
        plan.on("dcn.migrate_send", fault.ERROR, times=1,
                after=seed % 3)
        plan.on("dcn.migrate_recv", fault.ERROR, times=2,
                after=(seed // 3) % 3)
        plan.on("migrate.splice", fault.ERROR, times=2,
                after=1 + seed % 2)
        fallbacks = 0
        with fault.injected(plan):
            for p in prompts:
                out = co.generate(p, 5, timeout_s=60)
                # exactly-once + bit-exact REGARDLESS of what the
                # migration plane suffered: a failed page stream means
                # recompute, never a wrong or missing token
                assert out["error"] is None
                assert out["tokens"] == expected(p, 5), \
                    "stream diverged under migration chaos"
                if out["prefill"]["recompute_fallback"]:
                    fallbacks += 1
        fired = sum(plan.injected.values())
        assert fired >= 3, f"chaos never fired: {plan.injected}"
        # the destination never serves a HALF-imported chain: each
        # prompt's prefix probe is all-or-nothing at full pages
        for p in prompts:
            hit = dstore.probe(p + [1])
            assert hit in (0, 3 * PT) or hit % PT == 0
        # source pins were released under every outcome: with no live
        # sequence, every page's only ref is the radix tree's — a
        # leaked export pin would show as refs > 1
        with pstore.pagepool._mu:
            extra = [p for _, pages in pstore.pagepool._blocks.values()
                     for p in pages if p.refs > 1]
        assert not extra, \
            f"migration chaos leaked source page pins: {extra}"
        pstore.pagepool.assert_consistent()
        dstore.pagepool.assert_consistent()
        # post-chaos the plane works again
        clean = [seed * 100 + 77_000 + j for j in range(13)]
        out = co.generate(clean, 3)
        assert out["error"] is None
        assert out["tokens"] == expected(clean, 3)
        assert out["prefill"]["recompute_fallback"] is False
        assert out["prefill"]["migrated_pages"] == 3
        # baseline once the caches drop, on BOTH ends
        assert eng.join_idle(10)
        pstore.clear()
        dstore.clear()
        assert pstore.pagepool.blocks_leased() == 0
        assert dstore.pagepool.blocks_leased() == 0
        assert wait_until(lambda: occupancy() == free0, 10), \
            f"migration chaos leaked blocks: {occupancy()} != {free0}"
    finally:
        eng.close()
        psrv.stop()
        psrv.join()
        dsrv.stop()
        dsrv.join()
        pstore.close()
        dstore.close()


@pytest.mark.parametrize("seed", SEEDS)
def test_primary_death_standby_completes_exactly_once(seed):
    """ISSUE 7 acceptance: killing the primary process mid-generation
    (simulated by a seeded `serving.step` crash of its unsupervised
    engine — the in-process analog of process death, like scenario 11's
    engine crash) yields an exactly-once, BIT-EXACT token stream
    completed by the standby side, with `migrated_from`-linked spans
    visible on /rpcz?trace_id= for the generation's trace."""
    import jax

    from brpc_tpu import rpcz
    from brpc_tpu.kvcache import KVCacheStore
    from brpc_tpu.migrate import StandbySync, register_standby
    from brpc_tpu.migrate.disagg import assume_stream
    from brpc_tpu.serving import DecodeEngine

    PT = 4

    @jax.jit
    def step(tokens, positions, pages):
        return (tokens * 7 + positions) % 997

    def expected(prompt, n):
        last, pos, out = prompt[-1], len(prompt), []
        for _ in range(n):
            last = (last * 7 + pos) % 997
            out.append(last)
            pos += 1
        return out

    sstore = KVCacheStore(page_tokens=PT, page_bytes=256, max_blocks=32,
                          name=f"sb_chaos_s{seed}")
    seng = DecodeEngine(step, num_slots=4, store=sstore,
                        max_pages_per_slot=32,
                        name=f"sb_chaos_se{seed}")
    ssrv = brpc.Server(enable_dcn=True)
    replica = register_standby(ssrv, sstore, seng)
    ssrv.start("127.0.0.1", 0)
    standby_addr = f"127.0.0.1:{ssrv.port}"
    pstore = KVCacheStore(page_tokens=PT, page_bytes=256, max_blocks=32,
                          commit_live_pages=True,
                          name=f"sb_chaos_p{seed}")
    peng = DecodeEngine(step, num_slots=4, store=pstore,
                        max_pages_per_slot=32,
                        name=f"sb_chaos_pe{seed}")
    sync = StandbySync(pstore, standby_addr, submit_fn=peng.submit,
                       name=f"sb_chaos_sync{seed}")
    was = (rpcz.enabled(), rpcz.sample_rate())
    rpcz.set_enabled(True, 1.0)
    try:
        prompt = [seed * 10 + j for j in range(13)]
        budget = 9
        got, errs = [], []
        done = threading.Event()
        # the primary's engine crashes at a seeded step mid-generation
        plan = fault.FaultPlan(seed).on("serving.step", fault.ERROR,
                                        times=1, after=2 + seed % 4)
        root = rpcz.new_span("client", "Chaos", "Failover")
        rpcz.set_current_span(root)
        try:
            with fault.injected(plan):
                sid = sync.submit(prompt, budget, got.append,
                                  lambda e: (errs.append(e), done.set()))
                assert done.wait(60), "primary terminal never fired"
        finally:
            rpcz.set_current_span(None)
            rpcz.submit(root)
        assert plan.injected["serving.step"] == 1
        assert errs[0] is not None and errs[0].code == errors.EINTERNAL
        n_before = len(got)
        assert n_before < budget, "crash fired after the budget"
        sync.flush(15)

        out = assume_stream(standby_addr, sid, n_before, timeout_s=60)
        assert out["error"] is None
        full = got + out["tokens"]
        # exactly-once and bit-exact across the process seam
        assert full == expected(prompt, budget), \
            f"seed {seed}: stream diverged across failover"
        assert len(out["tokens"]) == budget - n_before
        assert replica.stats()["assumed"] == 1
        # the migrated pages made the resume a partial re-decode
        # whenever at least one full page had shipped
        if n_before + len(prompt) >= 2 * PT:
            assert out.get("resume_prefix_hit", 0) >= PT

        # migrated_from-linked spans are on the generation's trace and
        # the console timeline renders the link
        spans = rpcz.recent_spans(4096, root.trace_id)
        linked = [s for s in spans if s.migrated_from]
        assert linked, "no migrated_from-linked span on the trace"
        import http.client
        c = http.client.HTTPConnection("127.0.0.1", ssrv.port,
                                       timeout=10)
        c.request("GET", f"/rpcz?trace_id={root.trace_id}")
        r = c.getresponse()
        body = r.read().decode()
        c.close()
        assert r.status == 200
        assert "migrated_from=span" in body
        # baseline on both ends
        assert seng.join_idle(10)
        pstore.clear()
        sstore.clear()
        pstore.pagepool.assert_consistent()
        sstore.pagepool.assert_consistent()
        assert pstore.pagepool.blocks_leased() == 0
        assert sstore.pagepool.blocks_leased() == 0
    finally:
        rpcz.set_enabled(*was)
        sync.close()
        peng.close()
        seng.close()
        ssrv.stop()
        ssrv.join()
        pstore.close()
        sstore.close()


# ---------------------------------------------------------------------------
# scenario 14: replica kill mid-generation under a client that also drops
# and reconnects through the cluster front door (ISSUE 8)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_router_replica_kill_client_drop_resume(seed):
    """The cluster front door's failure drill: while a generation
    streams through the ClusterRouter, an injected ``router.forward``
    fault forces one re-route, the SERVING replica is killed
    mid-decode, and the client drops its connection too.  Invariants:

    * the reconnecting client (session_id + cursor) receives EXACTLY
      the tokens past its cursor — the assembled stream is bit-exact
      (token-for-token equal to an uninterrupted run), never a
      duplicate, never a hole;
    * the resume rode the buddy page migration: ``re_decoded_tokens``
      is strictly less than the generation's total tokens;
    * the killed replica is quarantined and its prefixes REMAPPED (the
      affinity ring now answers with a healthy replica);
    * pools and refcounts return to baseline on the survivor.
    """
    import random

    import numpy as np

    from brpc_tpu.kvcache import KVCacheStore
    from brpc_tpu.migrate import register_migration
    from brpc_tpu.serving import (ClusterRouter, DecodeEngine,
                                  ReplicaHandle, RouterClient,
                                  SessionTable, register_router,
                                  register_serving)

    PT = 4

    def step(tokens, positions, pages=None):
        time.sleep(0.03)           # slow decode: the kill lands mid-gen
        return (np.asarray(tokens) * 7 + np.asarray(positions)) % 997

    def expected(prompt, n):
        last, pos, out = prompt[-1], len(prompt), []
        for _ in range(n):
            last = (last * 7 + pos) % 997
            out.append(last)
            pos += 1
        return out

    replicas = []
    for tag in ("a", "b"):
        store = KVCacheStore(page_tokens=PT, page_bytes=256,
                             max_blocks=32,
                             name=f"rt_chaos_{tag}{seed}",
                             commit_live_pages=True)
        eng = DecodeEngine(step, num_slots=2, store=store,
                           max_pages_per_slot=32,
                           name=f"rt_chaos_eng_{tag}{seed}")
        srv = brpc.Server(enable_dcn=True)
        register_serving(srv, engine=eng)
        register_migration(srv, store)
        srv.start("127.0.0.1", 0)
        replicas.append((store, eng, srv,
                         f"127.0.0.1:{srv.port}"))

    table = SessionTable()
    router = ClusterRouter(
        [ReplicaHandle(addr, name=f"rt_{tag}", engine=eng, store=st,
                       server=srv)
         for (st, eng, srv, addr), tag in zip(replicas, "ab")],
        sessions=table, page_tokens=PT, replicate_sessions=True,
        quarantine_after=1, name=f"rt_chaos_router{seed}",
        check_interval_s=0.02)
    rsrv = brpc.Server()
    register_router(rsrv, router)
    rsrv.start("127.0.0.1", 0)
    cli = RouterClient(f"127.0.0.1:{rsrv.port}")

    rng = random.Random(seed)
    base = rng.randrange(100, 800)
    prompt = [base + i for i in range(13)]      # 3 full pages
    budget = 10
    plan = fault.FaultPlan(seed=seed)
    plan.on("router.forward", fault.ERROR, times=1)
    victim = survivor = None
    try:
        with fault.injected(plan):
            gen = cli.start(prompt, budget)
            assert gen.wait_tokens(3, timeout_s=20), \
                f"seed {seed}: no tokens before the kill"
            sid = gen.session_id
            s = table.get(sid)
            assert wait_until(lambda: s.replicated_pages > 0, 10), \
                f"seed {seed}: no buddy replication before the kill"
            cursor, seen = gen.cursor, gen.tokens
            victim = next(r for r in replicas
                          if r[3] == s.replica
                          or str(ReplicaHandle(r[3]).endpoint)
                          == s.replica)
            survivor = next(r for r in replicas if r is not victim)
            gen.drop()                      # the client dies...
            victim[2].stop()                # ...and the replica too
            victim[2].join()
            victim[1].close(timeout_s=2.0)
            assert wait_until(
                lambda: s.state in ("finished", "failed"), 30), \
                f"seed {seed}: session never completed after the kill"
            assert s.state == "finished", \
                f"seed {seed}: session failed E{s.error_code}"
            assert plan.injected.get("router.forward", 0) == 1
            assert s.resumes >= 2           # injected re-route + kill
            out = cli.resume_wait(sid, cursor, timeout_s=20)
        assert out["error"] is None
        full = seen[:cursor] + out["tokens"]
        assert full == expected(prompt, budget), \
            f"seed {seed}: stream diverged across the router seam"
        # exactly-once: a later reconnect replays the same suffix, no
        # token appears twice
        again = cli.resume_wait(sid, cursor, timeout_s=10)
        assert again["tokens"] == out["tokens"]
        total = len(prompt) + budget
        assert 0 < s.re_decoded_tokens < total, \
            f"seed {seed}: re_decoded={s.re_decoded_tokens} of {total}"
        # quarantine + remap: the ring no longer answers with the dead
        # replica for this prefix
        from brpc_tpu.policy.health_check import is_broken
        from brpc_tpu.policy.load_balancer import prefix_fingerprint
        victim_ep = ReplicaHandle(victim[3]).endpoint
        assert is_broken(victim_ep), \
            f"seed {seed}: killed replica not quarantined"
        remapped = router._lb.select_server(
            request_code=prefix_fingerprint(prompt))
        assert remapped != victim_ep
        # survivor baseline: no leaked sequences, pools consistent
        sstore = survivor[0]
        assert wait_until(
            lambda: sstore.stats()["live_seqs"] == 0, 10)
        sstore.clear()
        sstore.pagepool.assert_consistent()
        assert sstore.pagepool.blocks_leased() == 0
    finally:
        router.close(timeout_s=3.0)
        rsrv.stop()
        rsrv.join()
        for st, eng, srv, _addr in replicas:
            try:
                eng.close(timeout_s=2.0)
            except Exception:
                pass
            try:
                srv.stop()
                srv.join()
            except Exception:
                pass
            st.clear()
            st.close()


# ---------------------------------------------------------------------------
# scenario 15 (ISSUE 11): crash MID-VERIFY in the speculative engine ->
# supervisor resumes every stream bit-exact vs the plain-decode oracle
# with ZERO leaked draft pages
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_spec_verify_crash_resumes_bit_exact_no_draft_leaks(seed):
    """The speculative engine under supervision, crash injected at the
    ``serving.spec_verify`` fault site — between the draft LEASES
    (in-seq cursor pages + side-branch forks) being taken and the
    verify committing any of them:

    * every stream completes exactly-once and matches the plain greedy
      dense oracle token for token (speculation changes cost, never
      output — including across a crash/restart seam);
    * ZERO leaked draft pages: live_seqs, page refcounts, the page
      free-list, HBM block occupancy and the native emit rings all
      return to baseline (a rejected-or-crashed draft lease releases
      like any other holder);
    * the resumed decode was cheaper than a from-scratch replay
      (committed pages prefix-hit across the restart).
    """
    import gc

    from brpc_tpu import native_path
    from brpc_tpu.models.runner import (TransformerRunner,
                                        make_store_for)
    from brpc_tpu.serving import (DecodeEngine, EngineSupervisor,
                                  NGramProposer)

    m = _mr_chaos_model()
    cfg, params = m["cfg"], m["params"]
    store = make_store_for(cfg, page_tokens=4, max_blocks=32,
                           name=f"spec_chaos_kv{seed}")
    device_pool = store.pagepool.pool

    def occupancy():
        with device_pool._lock:
            return {c: len(device_pool._free[c])
                    for c in device_pool._free}

    free0 = occupancy()
    gc.collect()
    ring0 = native_path.tokring_live()
    runner = TransformerRunner(params, cfg, store=store,
                               name=f"spec_chaos_m{seed}")
    calm = ({"queue_delay_us": float("inf"), "pool_ratio": 9.9,
             "queue_depth": 1e9},) * 3
    sup = EngineSupervisor(
        lambda: DecodeEngine(runner=runner, num_slots=2, store=store,
                             max_pages_per_slot=24,
                             prefill_buckets=(8, 16),
                             draft_runner=NGramProposer(width=2),
                             draft_len=4,
                             name=f"spec_chaos_e{seed}"),
        store=store, heartbeat_deadline_s=10.0, check_interval_s=0.02,
        ladder=calm, name=f"spec_chaos{seed}")
    try:
        # jit warm + commit a shared 2-page prefix into the radix tree
        shared = [50, 61, 12, 73, 24, 85, 36, 97]
        done = threading.Event()
        sup.submit(shared + [1], 2, lambda t: None,
                   lambda e: done.set())
        assert done.wait(180)
        assert sup.join_idle(30)
        h0 = store.hit_tokens.get_value()
        p0 = store.prompt_tokens.get_value()

        plan = fault.FaultPlan(seed)
        plan.on("serving.spec_verify", fault.ERROR, times=1, after=2)
        prompts = [shared + [100 + i] for i in range(4)]
        sinks = []
        with fault.injected(plan):
            for p in prompts:
                ev = threading.Event()
                toks: list = []
                errs: list = []
                sinks.append((ev, toks, errs))
                sup.submit(p, 6, toks.append,
                           lambda e, ev=ev, errs=errs: (errs.append(e),
                                                        ev.set()))
            for ev, _, _ in sinks:
                assert ev.wait(240), \
                    "generation hung across the mid-verify crash"
        assert plan.injected["serving.spec_verify"] == 1
        st = sup.stats()
        assert st["restarts"] == 1
        assert st["last_recovery"]["stolen_slots"] >= 1
        # exactly-once + bit-exact vs the plain greedy oracle across
        # the crash seam
        for p, (ev, toks, errs) in zip(prompts, sinks):
            assert errs == [None], f"{p[-1]}: {errs}"
            assert toks == _mr_expected(p, 6), \
                f"req {p[-1]}: speculative stream diverged at the seam"
        # the resume prefix-hit committed pages (cheaper than replay)
        dp = store.prompt_tokens.get_value() - p0
        dh = store.hit_tokens.get_value() - h0
        assert dp > 0 and (dp - dh) / dp < 1.0, \
            "recovery re-decoded as much as a from-scratch replay"
        # zero leaked draft pages: every lease (in-seq cursor, forks)
        # released across crash + takeover + rebuild
        assert sup.join_idle(30)
        assert wait_until(
            lambda: store.stats()["live_seqs"] == 0, 10), \
            "a draft lease (fork or main seq) out-lived its request"
        store.clear()
        store.pagepool.assert_consistent()
        assert store.pagepool.blocks_leased() == 0
        assert wait_until(lambda: occupancy() == free0, 10), \
            f"KV blocks leaked: {occupancy()} != {free0}"
    finally:
        sup.close()
        store.close()
    assert wait_until(
        lambda: (gc.collect(), native_path.tokring_live())[1] <= ring0,
        10), "native emit rings leaked across the speculative restart"


# ---------------------------------------------------------------------------
# scenario 16 (ISSUE 12): partition failures mid-fanout over the sharded
# parameter-server service -> PartitionChannel sub-call retry gives
# exactly-once apply (version counters prove no double scatter-add)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_psserve_partition_faults_exactly_once_apply(seed):
    """Injected `psserve.lookup` / `psserve.update` faults fail
    individual PARTITION sub-calls mid-fanout (pre-apply failures AND
    post-apply ack drops).  The client's partition-level retry must
    heal every request, and the invariants hold:

    * every Update applies EXACTLY once — the per-shard version
      counters advance once per distinct update_id, post-apply retries
      dedup (dup counter > 0 when an ack dropped), and the final table
      is bit-identical to applying each acked update once;
    * every Lookup eventually serves rows bit-identical to the oracle;
    * pools return to baseline: batcher queues drain to zero and the
      shards' applied-id sets hold exactly the distinct updates.
    """
    import numpy as np

    from brpc_tpu.psserve import (EmbeddingShardServer, PSClient,
                                  init_embedding_table, register_psserve,
                                  unregister_psserve)
    from brpc_tpu.rpc.combo_channels import PartitionChannel

    V, D, P = 64, 8, 4
    # integer base + integer grads: every association of float32 adds
    # is exact, so exactly-once shows up as bit-identity
    base = np.round(init_embedding_table(V, D, seed=3) * 100)
    servers, svcs, shards = [], [], []
    pc = PartitionChannel(P)
    for i in range(P):
        sh = EmbeddingShardServer(i, P, V, D, table=base,
                                  name=f"chaos16_{seed}")
        shards.append(sh)
        s = brpc.Server()
        svcs.append(register_psserve(s, sh, max_delay_us=500,
                                     name=f"c16_{seed}_{i}"))
        s.start("127.0.0.1", 0)
        servers.append(s)
        # channel retry OFF: the injected sub-call failure must be
        # healed by the PARTITION-level retry under test, not papered
        # over inside the socket channel
        pc.add_partition(i, brpc.Channel(f"127.0.0.1:{s.port}",
                                         timeout_ms=10_000, max_retry=0))
    rng = np.random.default_rng(seed)
    n_threads, n_updates = 4, 3
    keysets = [rng.integers(0, V, size=6).astype(np.int64)
               for _ in range(n_threads)]
    gradsets = [rng.integers(-3, 4, (6, D)).astype(np.float32)
                for _ in range(n_threads)]
    plan = fault.FaultPlan(seed)
    # drift the firing point with the seed so pre-apply failures,
    # post-apply ack drops and lookup failures all get coverage
    plan.on("psserve.update", fault.ERROR, times=2, after=seed % 3)
    plan.on("psserve.lookup", fault.ERROR, times=2, after=seed % 2)
    results: dict = {}
    mu = threading.Lock()
    try:
        with fault.injected(plan):
            def worker(t):
                cli = PSClient(pc, vocab=V, dim=D, max_retry=3,
                               name=f"c16cli_{seed}_{t}")
                try:
                    for _ in range(n_updates):
                        cli.update(keysets[t], gradsets[t])
                        cli.lookup(keysets[t])
                    with mu:
                        results[t] = (cli.n_retries, cli.n_stale_reads)
                except errors.RpcError as e:   # pragma: no cover
                    with mu:
                        results[t] = e

            ts = [threading.Thread(target=worker, args=(t,))
                  for t in range(n_threads)]
            [t.start() for t in ts]
            [t.join(60) for t in ts]
        # every request healed: no worker surfaced an error or hung
        assert len(results) == n_threads
        failed = {t: r for t, r in results.items()
                  if isinstance(r, Exception)}
        assert not failed, f"workers failed despite retries: {failed}"
        # the schedule actually fired
        assert sum(plan.injected.values()) >= 1
        # exactly-once: version counters advance once per DISTINCT
        # update (n_threads * n_updates sub-applies per owning shard),
        # and any post-apply ack drop shows up as a dedup, never a
        # double add
        import jax.numpy as jnp
        want = jnp.asarray(base)
        for t in range(n_threads):
            for _ in range(n_updates):
                want = want.at[keysets[t]].add(jnp.asarray(gradsets[t]))
        got = np.concatenate([sh.snapshot_rows() for sh in shards])
        np.testing.assert_array_equal(got, np.asarray(want))
        total_applies = sum(sh.n_updates for sh in shards)
        total_version = sum(sh.version for sh in shards)
        assert total_version == total_applies, \
            "version advanced without a distinct apply (double add?)"
        # read-your-writes held through the chaos
        assert all(r[1] == 0 for r in results.values())
        # quiescent lookups (all writers joined) bit-identical to the
        # oracle — through the service, not snapshot_rows
        wantn = np.asarray(want)
        final_cli = PSClient(pc, vocab=V, dim=D, max_retry=3,
                             name=f"c16fin_{seed}")
        for t in range(n_threads):
            np.testing.assert_array_equal(final_cli.lookup(keysets[t]),
                                          wantn[keysets[t]])
        # pools/refcounts to baseline: queues drained, applied-id sets
        # hold exactly the distinct applies (every dup was served from
        # the set, not re-added)
        for svc in svcs:
            for b in (svc._lookup_b, svc._update_b):
                assert wait_until(
                    lambda b=b: b.stats()["queued"] == 0, 10)
        assert sum(len(sh._applied) for sh in shards) == total_applies
    finally:
        for svc in svcs:
            unregister_psserve(svc)
        for s in servers:
            s.stop()
            s.join()
        pc.close()


# ---------------------------------------------------------------------------
# scenario 17 (ISSUE 16): the ROUTER PROCESS dies (SIGKILL, no goodbye)
# plus a replica kill -> a successor process adopts the session WAL and
# every session resumes bit-exact, exactly once, over buddy-warm pages;
# a superseded router's floor pushes are fenced by epoch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_router_process_kill_wal_adoption_exactly_once(seed):
    """The durable control plane's acceptance drill: N=8 generations
    stream through a router running as its OWN OS PROCESS over a
    session WAL; mid-generation the harness SIGKILLs the router AND
    kills one serving replica.  A successor (fresh process w.r.t. the
    dead router) adopts the fleet from the WAL.  Invariants:

    * every session resumes from the client-held cursor and the
      assembled stream is bit-exact vs the uninterrupted oracle —
      zero duplicate tokens across the adoption seam, zero holes;
    * resumes ride the N-way buddy pages: ``re_decoded_tokens`` is
      strictly less than the generation's total on buddy-warm resumes;
    * the successor's epoch strictly supersedes the dead router's, and
      a floor push carrying the OLD epoch is refused ('stale epoch');
    * the killed replica is quarantined by the survivors;
    * survivor pools and refcounts return to baseline.
    """
    import random

    from brpc_tpu.serving import (ClusterRouter, ReplicaHandle,
                                  RouterClient, SessionTable,
                                  register_router)
    from brpc_tpu.serving.router_proc import spawn_router
    from brpc_tpu.tools.rpc_press import (spin_up_replicas,
                                          tear_down_replicas)

    PT = 4
    N = 8
    budget = 10

    def expected(prompt, n):
        last, pos, out = prompt[-1], len(prompt), []
        for _ in range(n):
            last = (last * 7 + pos) % 997
            out.append(last)
            pos += 1
        return out

    replicas = spin_up_replicas(
        3, page_tokens=PT, step_delay_s=0.03, num_slots=8,
        commit_live_pages=True, name_prefix=f"c17_{seed}")
    addrs = [addr for *_, addr in replicas]
    import tempfile
    wal_dir = tempfile.mkdtemp(prefix=f"chaos17_{seed}_")
    wal_path = os.path.join(wal_dir, "sessions.wal")
    proc, raddr = spawn_router(
        wal_path, addrs, replicate_sessions=True,
        replication_factor=3, page_tokens=PT, check_interval_s=0.02)

    rng = random.Random(seed)
    successor = rsrv2 = None
    try:
        cli = RouterClient(raddr, timeout_ms=20_000)
        gens = []
        for k in range(N):
            base = rng.randrange(100, 800)
            prompt = [base + k + i for i in range(13)]   # 3 full pages
            gens.append((prompt, cli.start(prompt, budget)))
        for prompt, g in gens:
            assert g.wait_tokens(3, timeout_s=30), \
                f"seed {seed}: no tokens before the kill"
        # buddy replication visible through the subprocess router's
        # Stats RPC before the kill
        from brpc_tpu.rpc.channel import Channel

        def _warm():
            st = Channel(raddr, timeout_ms=5000).call_sync(
                "Router", "Stats", {}, serializer="json",
                response_serializer="json")
            return sum(1 for r in st["session_rows"]
                       if r["replicated_pages"] > 0)
        assert wait_until(lambda: _warm() >= 1, 15), \
            f"seed {seed}: no buddy replication before the kill"
        old_epoch = Channel(raddr, timeout_ms=5000).call_sync(
            "Router", "Stats", {}, serializer="json",
            response_serializer="json")["epoch"]

        # -- the crash: router PROCESS and one replica die together --
        proc.kill()
        proc.wait()
        vstore, veng, vsrv, vaddr = replicas[0]
        vsrv.stop()
        vsrv.join()
        veng.close(timeout_s=2.0)

        # client-held cursors (the WAL, by write-ahead, is >= these)
        held = []
        for prompt, g in gens:
            g.drop()
            held.append((prompt, g.session_id, g.cursor, g.tokens))

        # -- adoption: a successor over the same WAL --
        table = SessionTable.recover(wal_path)
        assert table.replay_stats["sessions"] >= N
        assert table.replay_stats["live"] >= 1
        successor = ClusterRouter(
            [ReplicaHandle(a) for a in addrs], sessions=table,
            replicate_sessions=True, replication_factor=3,
            page_tokens=PT, quarantine_after=1,
            name=f"c17_successor{seed}", check_interval_s=0.02)
        assert successor.epoch > old_epoch
        rsrv2 = brpc.Server()
        register_router(rsrv2, successor)
        rsrv2.start("127.0.0.1", 0)
        cli2 = RouterClient(f"127.0.0.1:{rsrv2.port}",
                            timeout_ms=30_000)

        warm_resumes = 0
        for prompt, sid, cursor, seen in held:
            out = cli2.resume_wait(sid, cursor, timeout_s=60)
            assert out["error"] is None, \
                f"seed {seed}: resume failed E{out['error']}"
            full = seen[:cursor] + out["tokens"]
            assert full == expected(prompt, budget), \
                f"seed {seed}: stream diverged across the adoption seam"
            assert len(full) == budget    # zero dups, zero holes
            s = table.get(sid)
            total = len(prompt) + budget
            assert s.re_decoded_tokens < total, \
                f"seed {seed}: resume recomputed everything"
            if s.re_decoded_tokens < total - len(prompt):
                warm_resumes += 1
        assert warm_resumes >= 1, \
            f"seed {seed}: no buddy-warm resume rode the shipped pages"

        # -- epoch fencing: the dead router's epoch is refused --
        ctrl = replicas[1][2]._services["_cluster"]
        assert wait_until(lambda: ctrl.epoch >= successor.epoch, 10), \
            f"seed {seed}: successor floor push never reached replica"
        with pytest.raises(brpc.RpcError) as ei:
            Channel(replicas[1][3], timeout_ms=2000).call_sync(
                "_cluster", "SetFloor",
                {"epoch": old_epoch, "level": 4, "router": "zombie"},
                serializer="tensorframe",
                response_serializer="tensorframe")
        assert ei.value.code == errors.EREQUEST
        assert "stale epoch" in (ei.value.text or "")

        # -- the victim is quarantined by the survivors --
        from brpc_tpu.policy.health_check import is_broken
        victim_ep = ReplicaHandle(vaddr).endpoint
        assert wait_until(lambda: is_broken(victim_ep), 15), \
            f"seed {seed}: killed replica not quarantined"

        # -- survivor baseline: pools and refcounts drain --
        for store, _eng, _srv, _addr in replicas[1:]:
            assert wait_until(
                lambda s=store: s.stats()["live_seqs"] == 0, 15), \
                f"seed {seed}: leaked live sequences on a survivor"
            store.clear()
            store.pagepool.assert_consistent()
            assert store.pagepool.blocks_leased() == 0
    finally:
        try:
            proc.kill()
            proc.wait()
        except Exception:
            pass
        if successor is not None:
            successor.close(timeout_s=3.0)
        if rsrv2 is not None:
            rsrv2.stop()
            rsrv2.join()
        tear_down_replicas(replicas)
        try:
            os.unlink(wal_path)
            os.rmdir(wal_dir)
        except OSError:
            pass


@pytest.mark.parametrize("seed", SEEDS)
def test_durable_control_plane_fault_sites(seed):
    """The three ISSUE 16 fault sites, driven end to end:

    * ``router.wal_append`` — appends fail (un-durable tail), the
      router process 'dies' without healing them, and the successor
      still serves EXACTLY ONCE: the client's cursor outran the WAL,
      the gap is re-decoded bit-exact and never re-delivered;
    * ``cluster.floor_push`` — a dropped push is simply re-pushed next
      tick: the remote floor converges, drops are counted;
    * ``migrate.prefix_fetch`` — a failing pull falls back to
      recompute (generation completes, prefix_hit == 0), pools and
      refcounts at baseline after; the next fetch (no fault) works.
    """
    import random
    import tempfile

    from brpc_tpu.kvcache import KVCacheStore
    from brpc_tpu.migrate import make_prefix_fetcher, register_migration
    from brpc_tpu.serving import (ClusterRouter, DecodeEngine,
                                  ReplicaHandle, SessionTable,
                                  register_cluster_control,
                                  register_serving)

    PT = 4
    rng = random.Random(seed)

    def step(tokens, positions, pages=None):
        time.sleep(0.005)
        return (np.asarray(tokens) * 7 + np.asarray(positions)) % 997

    def expected(prompt, n):
        last, pos, out = prompt[-1], len(prompt), []
        for _ in range(n):
            last = (last * 7 + pos) % 997
            out.append(last)
            pos += 1
        return out

    # ---- (1) WAL append failure -> exactly-once across adoption ----
    wal_dir = tempfile.mkdtemp(prefix=f"c17b_{seed}_")
    wal_path = os.path.join(wal_dir, "s.wal")
    store = KVCacheStore(page_tokens=PT, page_bytes=256, max_blocks=32,
                         name=f"c17b_{seed}", commit_live_pages=True)
    eng = DecodeEngine(step, num_slots=4, store=store,
                       max_pages_per_slot=32,
                       name=f"c17b_eng_{seed}")
    srv = brpc.Server(enable_dcn=True)
    register_serving(srv, engine=eng)
    register_migration(srv, store)
    srv.start("127.0.0.1", 0)
    addr = f"127.0.0.1:{srv.port}"

    table = SessionTable(wal=wal_path)
    router = ClusterRouter(
        [ReplicaHandle(addr, name="c17b", engine=eng, store=store,
                       server=srv)],
        sessions=table, page_tokens=PT, name=f"c17b_router{seed}",
        check_interval_s=0.02)
    successor = None
    budget = 8
    base = rng.randrange(100, 800)
    prompt = [base + i for i in range(9)]
    try:
        plan = fault.FaultPlan(seed=seed)
        # fail every append after the first few: the tail of the
        # stream is never durable
        plan.on("router.wal_append", fault.ERROR, times=100, after=4)
        got = []
        with fault.injected(plan):
            s = router.open_session(prompt, budget)
            router.attach(s.sid, 0, got.append)
            assert wait_until(lambda: s.state == "finished", 30), \
                f"seed {seed}: generation never finished under faults"
        assert got == expected(prompt, budget)
        assert plan.injected.get("router.wal_append", 0) >= 1
        wal_stats = table.wal.stats()
        assert wal_stats["append_failures"] >= 1
        client_cursor = len(got)
        sid = s.sid
        # the router process "dies" with the pending tail UNHEALED
        router.close(timeout_s=3.0)
        table.wal._pending.clear()     # simulate: heal never happened
        table.close()

        table2 = SessionTable.recover(wal_path)
        r = table2.get(sid)
        assert r is not None
        # the WAL is BEHIND the client (its tail appends failed) —
        # legal, because attach-ahead re-decodes and suppresses
        assert r.cursor <= client_cursor
        successor = ClusterRouter(
            [ReplicaHandle(addr, name="c17b2", engine=eng,
                           store=store, server=srv)],
            sessions=table2, page_tokens=PT,
            name=f"c17b_succ{seed}", check_interval_s=0.02)
        got2 = []
        done = threading.Event()
        successor.attach(sid, client_cursor, got2.append,
                         lambda err: done.set())
        assert done.wait(30), f"seed {seed}: resume never finished"
        # the client saw `got` then `got2`: exactly the oracle, no
        # token twice even though the gap was re-decoded
        assert got + got2 == expected(prompt, budget), \
            f"seed {seed}: duplicate or hole across the WAL gap"
        successor.close(timeout_s=3.0)
        successor = None
        table2.close()

        # ---- (2) dropped floor push -> re-pushed next tick ----
        ctrl = register_cluster_control  # noqa: F841  (site below)
        rep_srv = brpc.Server()
        ctrl_svc = register_cluster_control(rep_srv, engine=eng,
                                            store=store,
                                            name=f"c17b_ctrl{seed}")
        rep_srv.start("127.0.0.1", 0)
        wire_router = ClusterRouter(
            [f"127.0.0.1:{rep_srv.port}"], page_tokens=PT,
            name=f"c17b_wire{seed}", auto_tick=False, epoch=3)
        plan2 = fault.FaultPlan(seed=seed)
        plan2.on("cluster.floor_push", fault.ERROR, times=2)
        with fault.injected(plan2):
            wire_router._push_floor(2)     # dropped on the wire
            assert ctrl_svc.level == 0
            wire_router._push_floor(2)     # dropped again
            assert ctrl_svc.level == 0
            wire_router._push_floor(2)     # next tick: lands
            assert ctrl_svc.level == 2 and ctrl_svc.epoch == 3
        assert plan2.injected.get("cluster.floor_push", 0) == 2
        assert wire_router.floor_push_drops == 2
        rows = wire_router.remote_floor_table()
        assert rows[0]["drops"] == 2
        assert rows[0]["acked_level"] == 2
        wire_router.close(timeout_s=2.0)
        rep_srv.stop()
        rep_srv.join()

        # ---- (3) prefix fetch failure -> recompute fallback ----
        cold_store = KVCacheStore(page_tokens=PT, page_bytes=256,
                                  max_blocks=32,
                                  name=f"c17b_cold_{seed}",
                                  commit_live_pages=True)
        cold_eng = DecodeEngine(step, num_slots=4, store=cold_store,
                                max_pages_per_slot=32,
                                name=f"c17b_cold_eng_{seed}")
        cold_srv = brpc.Server(enable_dcn=True)
        cold_svc = register_serving(cold_srv, engine=cold_eng)
        cold_mig = register_migration(cold_srv, cold_store)
        cold_srv.start("127.0.0.1", 0)
        cold_addr = f"127.0.0.1:{cold_srv.port}"
        cold_svc.prefix_fetcher = make_prefix_fetcher(
            cold_mig.migrator, cold_addr)

        from brpc_tpu.rpc.channel import Channel
        from brpc_tpu.rpc.controller import Controller
        from brpc_tpu.rpc.stream import stream_create

        class _Drain:
            def __init__(self):
                self.done = threading.Event()

            def on_received_messages(self, stream, messages):
                import json as _json
                for m in messages:
                    if _json.loads(bytes(m)).get("done") is not None:
                        self.done.set()

            def on_closed(self, stream):
                self.done.set()

        warm_prompt = prompt    # replica `store` is warm from part 1
        plan3 = fault.FaultPlan(seed=seed)
        plan3.on("migrate.prefix_fetch", fault.ERROR, times=1)
        with fault.injected(plan3):
            d = _Drain()
            cntl = Controller(timeout_ms=15_000)
            stream_create(cntl, d)
            resp = Channel(cold_addr, timeout_ms=15_000).call_sync(
                "Serving", "Generate",
                {"prompt": warm_prompt, "max_new_tokens": 4,
                 "prefix_holders": [addr]},
                serializer="json", cntl=cntl)
            assert d.done.wait(15), \
                f"seed {seed}: generation hung on fetch failure"
        assert plan3.injected.get("migrate.prefix_fetch", 0) == 1
        # the fetch failed -> recompute fallback: no prefix served
        assert resp["prefix_hit"] == 0, resp
        assert cold_svc.prefix_fetches == 0
        mig_stats = cold_mig.migrator.stats()
        assert mig_stats["fetch_routes"][addr]["failed"] == 1
        # no fault: the same pull lands on a FRESH cold replica (the
        # first one's recompute fallback warmed its own cache, which
        # is exactly the point of the fallback)
        cold2_store = KVCacheStore(page_tokens=PT, page_bytes=256,
                                   max_blocks=32,
                                   name=f"c17b_cold2_{seed}",
                                   commit_live_pages=True)
        cold2_eng = DecodeEngine(step, num_slots=4, store=cold2_store,
                                 max_pages_per_slot=32,
                                 name=f"c17b_cold2_eng_{seed}")
        cold2_srv = brpc.Server(enable_dcn=True)
        cold2_svc = register_serving(cold2_srv, engine=cold2_eng)
        cold2_mig = register_migration(cold2_srv, cold2_store)
        cold2_srv.start("127.0.0.1", 0)
        cold2_addr = f"127.0.0.1:{cold2_srv.port}"
        cold2_svc.prefix_fetcher = make_prefix_fetcher(
            cold2_mig.migrator, cold2_addr)
        d2 = _Drain()
        cntl2 = Controller(timeout_ms=15_000)
        stream_create(cntl2, d2)
        resp2 = Channel(cold2_addr, timeout_ms=15_000).call_sync(
            "Serving", "Generate",
            {"prompt": warm_prompt, "max_new_tokens": 4,
             "prefix_holders": [addr]},
            serializer="json", cntl=cntl2)
        assert d2.done.wait(15)
        assert resp2["prefix_hit"] >= PT, resp2
        assert cold2_svc.prefix_fetches == 1
        assert cold2_svc.prefix_fetched_pages >= 1
        # baseline on the cold stores after drain
        for c_store, c_eng, c_srv in (
                (cold_store, cold_eng, cold_srv),
                (cold2_store, cold2_eng, cold2_srv)):
            assert wait_until(
                lambda s=c_store: s.stats()["live_seqs"] == 0, 10)
            c_eng.close(timeout_s=2.0)
            c_srv.stop()
            c_srv.join()
            c_store.clear()
            c_store.pagepool.assert_consistent()
            assert c_store.pagepool.blocks_leased() == 0
            c_store.close()
    finally:
        if successor is not None:
            successor.close(timeout_s=2.0)
        try:
            router.close(timeout_s=2.0)
        except Exception:
            pass
        try:
            eng.close(timeout_s=2.0)
        except Exception:
            pass
        try:
            srv.stop()
            srv.join()
        except Exception:
            pass
        store.clear()
        store.close()
        try:
            os.unlink(wal_path)
            os.rmdir(wal_dir)
        except OSError:
            pass


# ---------------------------------------------------------------------------
# scenario 18 (ISSUE 17): kill a shard SERVER mid-update-wave while the
# fleet carries all three traffic shapes -> the trainer heals via
# update_token partition retry (momentum steps exactly once), streamed
# generations stay bit-exact, RYW holds, and queues/pools drain to
# baseline after the restart
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_mixed_traffic_shard_kill_midwave_exactly_once(seed):
    """The training-plane chaos story end to end: one fleet serving
    zipf lookups + streamed generations + fused-optimizer update waves,
    and partition ``seed % 2``'s SERVER dies after at least two
    optimizer applies landed (mid-run, waves in flight) then comes back
    ~0.3s later over the same shard state.  Invariants:

    * exactly-once: every shard's version counter equals its distinct
      applies — the update_token replay dedup'd everything the killed
      server had already applied, so no momentum step ran twice;
    * the trainer completed every step (workers healed via partition
      retry, none died);
    * zero stale reads across every per-shape client (RYW);
    * generations under chaos are bit-exact against their quiesced
      reference streams;
    * batcher queues drain to zero and decode pools return to their
      post-reference baseline.
    """
    from brpc_tpu.train import MixedWorkloadHarness

    h = MixedWorkloadHarness(n_shards=2, vocab=48, dim=8,
                             n_replicas=1, lookup_workers=1,
                             gen_workers=1, gen_tokens=8,
                             train_workers=2, train_steps=4,
                             seed=seed, name=f"c18_{seed}")
    # chaos needs more patience than the default: the dead window is
    # ~0.3s and every retry backs off retry_backoff_s * attempt
    h.trainer.wave_max_retry = 10
    h.trainer.retry_backoff_s = 0.1
    victim = seed % 2
    killed = threading.Event()

    def killer():
        # strike only after the fused optimizer has actually applied
        # waves (mid-run, not before traffic exists)
        if not wait_until(
                lambda: sum(sh.n_opt_updates for sh in h.shards) >= 2,
                30):
            return
        h.kill_shard(victim)
        killed.set()
        time.sleep(0.3)
        h.restart_shard(victim)

    kt = threading.Thread(target=killer, daemon=True,
                          name=f"c18_killer_{seed}")
    try:
        kt.start()
        rep = h.run()
        kt.join(60)
        assert killed.is_set(), "the kill never fired (trainer " \
            "finished before two optimizer applies?)"
        # exactly-once momentum: version counters advance once per
        # DISTINCT apply on every shard, through the kill and replay
        assert all(rep["exactly_once"]), rep["shards"]
        # the replay discipline actually exercised: the trainer retried
        # waves, and any ack the killed server swallowed shows up as a
        # dedup rather than a double apply
        assert rep["train"]["wave_retries"] + \
            rep["train"]["io_retries"] >= 1
        # every worker finished every step (healed, not excused)
        assert rep["train"]["steps_done"] == 2 * 4
        assert rep["train"]["waves"] == 2 * 4
        assert rep["stale_reads"] == 0
        # generations under chaos bit-exact vs the quiesced reference
        gen = rep["shapes"]["generate"]
        assert gen["ok"] > 0 and gen["mismatch"] == 0
        assert rep["queues_drained"], rep["shards"]
        assert rep["pools_at_baseline"]
        # training stayed SANE through the chaos: no NaN, no blow-up
        # from a double-applied wave (the strict loss-decrease proof is
        # test_trainer_loss_decreases_through_service — four steps on a
        # held-out batch are not enough to demand monotonicity here)
        assert np.isfinite(rep["train"]["loss_final"])
        assert rep["train"]["loss_final"] < \
            rep["train"]["loss_first"] + 0.5
    finally:
        kt.join(5)
        h.close()


# ---------------------------------------------------------------------------
# scenario 19 (ISSUE 18): kill the only WARM replica of model B
# mid-decode in a two-model fleet -> B sessions fail over onto the
# LOADING replica serving B (bit-exact, exactly once), model A sessions
# never notice, stale-epoch deploy/undeploy pushes are refused, and no
# page ever crosses a model boundary
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_multimodel_warm_replica_kill_same_model_failover(seed):
    """The multi-model plane's acceptance drill (chaos scenario 19).
    Fleet of N=4 replicas: two serve only model A (warm), one serves
    only model B (warm — the victim), one serves only B but LOADING.
    Mid-decode the victim dies.  Invariants:

    * every B session finishes bit-exact against B's oracle, exactly
      once — the driver re-routed it to the loading B replica (its
      pages arrive by buddy ship or recompute fallback; either way the
      stream may not diverge, duplicate, or hole);
    * the loading replica flips WARM via the completed generations;
    * A sessions stream bit-exact with zero errors — a B-side crash
      is invisible to the other model;
    * ``Deploy``/``Undeploy`` carrying a superseded epoch are refused
      ('stale epoch'), and an injected ``cluster.deploy`` wire fault
      is survivable by retry;
    * zero cross-model page splices: no A-model store ever holds a B
      prompt's pages and vice versa; every misroute counter reads 0;
    * survivor pools/refcounts and the native emit rings return to
      baseline.
    """
    import gc

    from brpc_tpu import native_path
    from brpc_tpu.serving import RouterClient
    from brpc_tpu.serving.modelplane import (LOADING, WARM,
                                             cluster_deploy)
    from brpc_tpu.tools.rpc_press import (expected_model_tokens,
                                          spin_up_multimodel_cluster,
                                          tear_down_multimodel_cluster)

    PT = 4
    budget = 10
    MODELS = ["modela", "modelb"]
    layout = [["modela"], ["modela"], ["modelb"], ["modelb"]]
    replicas, mults, router, rsrv, raddr = spin_up_multimodel_cluster(
        4, MODELS, layout=layout, page_tokens=PT, step_delay_s=0.03,
        commit_live_pages=True, replicate_sessions=True,
        name_prefix=f"c19_{seed}")
    try:
        # replica 3 starts LOADING: it serves B but has not proven
        # itself — still a legal placement/failover target
        replicas[3]["deps"].deploy("modelb", state=LOADING)
        assert wait_until(
            lambda: any(r["state"] == LOADING
                        for r in router.catalog.snapshot().get(
                            replicas[3]["addr"], [])), 10), \
            f"seed {seed}: catalog never saw the loading state"
        ring0 = native_path.tokring_live()

        cli = RouterClient(raddr, timeout_ms=30_000)
        # DISJOINT prompt ranges per model, so a page crossing the
        # model boundary is detectable by probing the stores
        a_prompts = [[100 + 20 * k + i for i in range(13)]
                     for k in range(3)]
        b_prompts = [[500 + 20 * k + i for i in range(13)]
                     for k in range(4)]
        a_gens = [(p, cli.start(p, budget, model="modela"))
                  for p in a_prompts]
        b_gens = [(p, cli.start(p, budget, model="modelb"))
                  for p in b_prompts]
        for p, g in a_gens + b_gens:
            assert g.wait_tokens(3, timeout_s=30), \
                f"seed {seed}: no tokens before the kill"

        # -- the crash: the only WARM replica of model B dies --
        victim = replicas[2]
        victim["server"].stop()
        # Server.join is internally bounded by graceful_quit_timeout_s
        victim["server"].join()  # brpc-check: allow(wedge-hygiene)
        victim["engines"]["modelb"].close(timeout_s=2.0)

        # every stream finishes THROUGH the crash: B rides the driver's
        # same-model failover onto replica 3, A never re-routes
        for p, g in a_gens:
            assert g.wait(60), f"seed {seed}: model A stream hung"
            assert g.error is None, \
                f"seed {seed}: model A session broke (E{g.error})"
            assert g.tokens == expected_model_tokens(
                p, budget, mults["modela"]), \
                f"seed {seed}: model A stream diverged"
        for p, g in b_gens:
            assert g.wait(60), f"seed {seed}: model B stream hung"
            assert g.error is None, \
                f"seed {seed}: model B failover failed (E{g.error})"
            assert g.tokens == expected_model_tokens(
                p, budget, mults["modelb"]), \
                f"seed {seed}: model B stream diverged across failover"
            assert len(g.tokens) == budget    # zero dups, zero holes

        # the loading replica earned its warm state by serving
        assert replicas[3]["deps"].get("modelb")["state"] == WARM

        # -- lifecycle fencing on the wire (replica 3's _cluster) --
        r3addr = replicas[3]["addr"]
        E = router.epoch
        # a fault outlasting the channel's retry budget (4 attempts:
        # initial + max_retry=3) surfaces as EINTERNAL to the pusher
        plan = fault.FaultPlan(seed=seed)
        plan.on("cluster.deploy", fault.ERROR, times=4)
        with fault.injected(plan):
            with pytest.raises(errors.RpcError) as ei0:
                cluster_deploy(r3addr, epoch=E, model="modelb",
                               op="deploy", weight=2)
            assert ei0.value.code == errors.EINTERNAL
        assert plan.injected.get("cluster.deploy", 0) >= 1
        # ...but a ONE-SHOT wire fault is absorbed by the channel's
        # retry: the fault provably fired, yet the push landed — the
        # deploy path is idempotent so the retry is safe
        plan2 = fault.FaultPlan(seed=seed)
        plan2.on("cluster.deploy", fault.ERROR, times=1)
        with fault.injected(plan2):
            out = cluster_deploy(r3addr, epoch=E, model="modelb",
                                 op="deploy", weight=2, state="warm")
            assert out["applied"] and out["epoch"] == E
        assert plan2.injected.get("cluster.deploy", 0) == 1
        assert replicas[3]["deps"].get("modelb")["weight"] == 2
        for op in ("deploy", "undeploy"):
            with pytest.raises(errors.RpcError) as ei:
                cluster_deploy(r3addr, epoch=E - 1, model="modelb",
                               op=op)
            assert ei.value.code == errors.EREQUEST
            assert "stale epoch" in (ei.value.text or "")

        # -- zero cross-model page splices, three witnesses --
        assert router.stats()["wrong_model_routes"] == 0
        for r in replicas:
            assert r["serving"].n_model_misroutes == 0
            mig = r["server"]._services.get("_kvmig")
            if mig is not None:
                assert mig.n_model_refusals == 0
            if r is victim:
                continue
            for m, store in r["stores"].items():
                foreign = a_prompts if m == "modelb" else b_prompts
                for p in foreign:
                    assert store.probe(p) == 0, \
                        f"seed {seed}: {m} store holds a foreign " \
                        f"model's prefix"

        # -- survivor baselines: pools, refcounts, native rings --
        for r in replicas:
            if r is victim:
                continue
            for store in r["stores"].values():
                assert wait_until(
                    lambda s=store: s.stats()["live_seqs"] == 0, 15), \
                    f"seed {seed}: leaked live sequences on a survivor"
                # the router's ship thread still pushes the B sessions'
                # pages at the dead victim, and a push pins its source
                # pages while it is on the wire
                assert wait_until(
                    lambda s=store: (s.clear(),
                                     s.pagepool.blocks_leased())[1] == 0,
                    15), f"seed {seed}: leaked blocks on a survivor"
                store.pagepool.assert_consistent()
    finally:
        tear_down_multimodel_cluster(replicas, router, rsrv)
    # after the engines close, every request's native emit ring must be
    # gone — idle slots may pin their LAST request's ring while the
    # engine lives, so this check belongs after teardown
    assert wait_until(
        lambda: (gc.collect(), native_path.tokring_live())[1]
        <= ring0, 10), \
        f"seed {seed}: native emit rings leaked across the failover"


# ---------------------------------------------------------------------------
# chaos scenario 20: replica kill mid-collection — the telemetry plane
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_replica_kill_mid_collection_tombstones_and_slo_holds(seed):
    """The fleet telemetry plane's chaos drill (ISSUE 20).  A 2-replica
    canary fleet (m@v1 baseline / m@v2 canary, both warm on both
    replicas) streams under an attached SLO engine while the router's
    collector pulls at tick cadence.  Mid-collection one replica is
    killed.  Invariants:

    * the victim is TOMBSTONED on the collector — its series freeze
      and drop out of aggregates, never silently averaged in;
    * the SLO engine HOLDs every canary decision for the disruption
      window: a clean canary must NOT promote (and chaos-induced burn
      must not roll back) while the fleet is disrupted — the ramp
      stays ``ramping`` with ``holds`` ticking;
    * every in-flight stream finishes bit-exact against the oracle of
      whichever version the router bound it to, exactly once, through
      the failover — telemetry is observation, never a correctness
      dependency;
    * survivor pools/refcounts and the native emit rings return to
      baseline.
    """
    import gc

    from brpc_tpu import native_path
    from brpc_tpu.serving import RouterClient
    from brpc_tpu.serving.slo import (HOLD, RAMPING, Objective,
                                      SLOEngine)
    from brpc_tpu.tools.rpc_press import (expected_model_tokens,
                                          spin_up_multimodel_cluster,
                                          tear_down_multimodel_cluster)

    PT = 4
    budget = 10
    replicas, mults, router, rsrv, raddr = spin_up_multimodel_cluster(
        2, ["m@v1", "m@v2"], page_tokens=PT, step_delay_s=0.03,
        commit_live_pages=True, replicate_sessions=True,
        name_prefix=f"c20_{seed}")
    try:
        ring0 = native_path.tokring_live()
        eng = SLOEngine(
            "m", "m@v1", "m@v2",
            # generous targets: the canary is CLEAN — only the HOLD may
            # stop it; clean_windows is set far past this test's
            # horizon so the ramp is provably still open at kill time
            [Objective("ttft_p99_ms", 60_000.0),
             Objective("itl_p99_ms", 60_000.0)],
            short_window_s=0.3, long_window_s=0.8, clean_windows=1000)
        router.attach_slo(eng)
        # collection is live on BOTH replicas before the kill — the
        # crash lands mid-collection, not before it
        assert wait_until(
            lambda: all(r["pulls"] > 0
                        for r in router.collector.replica_table()), 10), \
            f"seed {seed}: collector never pulled both replicas"

        cli = RouterClient(raddr, timeout_ms=30_000)
        prompts = [[100 + 20 * k + i for i in range(13)]
                   for k in range(4)]
        gens = [(p, cli.start(p, budget, model="m")) for p in prompts]
        for p, g in gens:
            assert g.wait_tokens(3, timeout_s=30), \
                f"seed {seed}: no tokens before the kill"

        # -- the crash --
        victim = replicas[0]
        victim["server"].stop()
        victim["server"].join()  # brpc-check: allow(wedge-hygiene)
        for e in victim["engines"].values():
            e.close(timeout_s=2.0)

        # the collector tombstones the victim (consecutive pull
        # failures or the router's quarantine note — either path)
        assert wait_until(
            lambda: victim["addr"] in router.collector.tombstoned(),
            15), f"seed {seed}: victim never tombstoned"
        # the SLO engine HOLDs the ramp for the disruption
        assert wait_until(lambda: eng.holds > 0, 10), \
            f"seed {seed}: SLO never held during the disruption"
        assert eng.state == RAMPING, \
            f"seed {seed}: ramp decided during a disruption " \
            f"({eng.state}): {eng.trail()}"

        # every stream finishes THROUGH the crash, bit-exact against
        # the version the router bound it to
        for p, g in gens:
            assert g.wait(60), f"seed {seed}: stream hung"
            assert g.error is None, \
                f"seed {seed}: stream broke (E{g.error})"
            oracle_v1 = expected_model_tokens(p, budget, mults["m@v1"])
            oracle_v2 = expected_model_tokens(p, budget, mults["m@v2"])
            assert g.tokens in (oracle_v1, oracle_v2), \
                f"seed {seed}: stream matches NEITHER version's oracle"
            assert len(g.tokens) == budget    # zero dups, zero holes
        assert router.stats()["wrong_model_routes"] == 0

        # the hold persists while the tombstone is active
        assert eng.tick(router.collector, router) == HOLD
        assert eng.state == RAMPING

        # -- survivor baselines --
        surv = replicas[1]
        for store in surv["stores"].values():
            assert wait_until(
                lambda s=store: s.stats()["live_seqs"] == 0, 15), \
                f"seed {seed}: leaked live sequences on the survivor"
            store.clear()
            store.pagepool.assert_consistent()
            assert store.pagepool.blocks_leased() == 0
    finally:
        tear_down_multimodel_cluster(replicas, router, rsrv)
    assert wait_until(
        lambda: (gc.collect(), native_path.tokring_live())[1]
        <= ring0, 10), \
        f"seed {seed}: native emit rings leaked across the kill"
