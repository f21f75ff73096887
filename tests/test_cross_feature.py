"""Cross-feature integration: combinations that have historically hidden
bugs — compression under auth/limiter gates, tensor streams surviving
peer failure without leaking rail tickets, usercode pools under graceful
restart, and fiber-locals across deferred completion."""
import threading
import time

import jax
import jax.numpy as jnp
import pytest

import brpc_tpu as brpc
from brpc_tpu import errors


from testutil import wait_until as _wait


def test_grpc_compression_through_auth_gate():
    """Compressed gRPC requests pass the SAME auth gate as native
    traffic: missing AND wrong tokens are rejected, a right token
    round-trips gzip both ways."""
    from brpc_tpu.rpc.auth import TokenAuthenticator
    from brpc_tpu.rpc.h2 import GrpcChannel

    class Svc(brpc.Service):
        NAME = "XGate"

        @brpc.method(request="raw", response="raw")
        def Echo(self, cntl, req):
            return req

    srv = brpc.Server(brpc.ServerOptions(
        auth=TokenAuthenticator("sekrit")))
    srv.add_service(Svc())
    srv.start("127.0.0.1", 0)
    try:
        payload = b"compress-me " * 500
        ch = GrpcChannel(f"127.0.0.1:{srv.port}", compression="gzip")
        # no token: rejected
        with pytest.raises(errors.RpcError):
            ch.call("XGate", "Echo", payload)
        # wrong token: rejected too
        with pytest.raises(errors.RpcError):
            ch.call("XGate", "Echo", payload,
                    metadata=[("authorization", "wr0ng")])
        # with token (gRPC carries it as metadata the server verifies)
        out = ch.call("XGate", "Echo", payload,
                      metadata=[("authorization", "sekrit")])
        assert out == payload
        ch.close()
    finally:
        srv.stop()
        srv.join()


def test_tensor_stream_peer_death_releases_tickets():
    """Kill the transport under an active tensor stream: pending rail
    tickets must drain (withdraw-on-dead-stream + TTL), not pin HBM."""
    from brpc_tpu.ici import rail

    D0, D1 = jax.devices()[0], jax.devices()[1]
    received = []

    class S(brpc.Service):
        NAME = "XDeath"

        @brpc.method(request="json", response="json")
        def Open(self, cntl, req):
            cntl.accept_stream(lambda s, p: received.append(p), device=D1)
            return {"ok": True}

    srv = brpc.Server(brpc.ServerOptions(ici_device=D1))
    srv.add_service(S())
    srv.start("127.0.0.1", 0)
    try:
        ch = brpc.Channel(f"127.0.0.1:{srv.port}", timeout_ms=5000)
        cntl = brpc.Controller()
        stream = brpc.stream_create(cntl, None, device=D0)
        ch.call_sync("XDeath", "Open", {}, serializer="json", cntl=cntl)
        x = jax.device_put(jnp.arange(512, dtype=jnp.float32), D0)
        stream.write(x)
        assert _wait(lambda: len(received) == 1)
        # sever the transport out from under the stream
        from brpc_tpu.rpc.transport import Transport
        Transport.instance().close(stream._sid)
        # writes now fail cleanly (EEOF-family), not hang
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            try:
                stream.write(x, timeout_s=0.5)
            except errors.RpcError:
                break
            time.sleep(0.05)
        else:
            pytest.fail("write kept succeeding on a dead transport")
        assert _wait(lambda: rail.pending_tickets() == 0, timeout=5)
    finally:
        srv.stop()
        srv.join()


def test_usercode_pool_graceful_restart():
    """usercode_in_pthread across stop()/join()/start(): in-flight
    blocking handlers drain, the pool is recreated, and the restarted
    server serves again."""
    import time as _time

    started = []

    class B(brpc.Service):
        NAME = "XRestart"

        @brpc.method(request="raw", response="raw")
        def Nap(self, cntl, req):
            started.append(1)
            _time.sleep(0.15)
            return b"ok"

    s = brpc.Server(brpc.ServerOptions(usercode_in_pthread=True))
    s.add_service(B())
    s.start("127.0.0.1", 0)
    ch = brpc.Channel(f"127.0.0.1:{s.port}", timeout_ms=8000)
    inflight = [ch.call("XRestart", "Nap", b"") for _ in range(6)]
    # all six handlers must be RUNNING before stop() (the 64-wide pool
    # runs them concurrently) — anything weaker races the stopping gate
    # and the gate correctly ELOGOFFs stragglers
    assert _wait(lambda: len(started) == 6, timeout=10)
    s.stop()
    s.join()                          # waits for the six
    for c in inflight:
        c.join()
        assert not c.failed() and c.response == b"ok"
    s.start("127.0.0.1", 0)
    try:
        ch2 = brpc.Channel(f"127.0.0.1:{s.port}", timeout_ms=8000)
        assert ch2.call_sync("XRestart", "Nap", b"") == b"ok"
    finally:
        s.stop()
        s.join()


def test_fiber_local_survives_deferred_completion():
    """A span/fiber-local set in the handler is visible to work spawned
    via fiber_local even when the RPC completes through defer() on
    another thread."""
    from brpc_tpu import rpcz
    from brpc_tpu.butil import fiber_local

    rpcz.set_enabled(True)
    key = fiber_local.key_create()
    seen = {}
    done_evt = threading.Event()

    class D(brpc.Service):
        NAME = "XDefer"

        @brpc.method(request="json", response="json")
        def Go(self, cntl, req):
            fiber_local.set_specific(key, "per-call-state")
            trace = rpcz.current_trace()
            done = cntl.defer()

            def finish():
                seen["local"] = fiber_local.get_specific(key)
                seen["trace"] = rpcz.current_trace()
                done({"trace": trace[0]})
                done_evt.set()

            fiber_local.spawn(finish)
            return None

    srv = brpc.Server()
    srv.add_service(D())
    srv.start("127.0.0.1", 0)
    try:
        ch = brpc.Channel(f"127.0.0.1:{srv.port}", timeout_ms=8000)
        resp = ch.call_sync("XDefer", "Go", {}, serializer="json")
        assert done_evt.wait(5)
        assert seen["local"] == "per-call-state"
        assert seen["trace"][0] == resp["trace"] != 0
    finally:
        srv.stop()
        srv.join()
        rpcz.set_enabled(False)
        fiber_local.key_delete(key)


def test_mixed_protocol_soak():
    """ONE server, five client lanes hammering CONCURRENTLY: TRPC
    unary, gRPC unary through the native plane, gRPC server-streaming,
    unified stream writes (bytes + tensors), and console HTTP.  The
    multi-protocol socket core, the lean gRPC pool, the stream reorder
    layer and the console must coexist without cross-talk: zero
    unexpected errors, every lane completes its calls while the others
    run, and no rail tickets or inflight window bytes remain at the end.
    The point is the mix, not a rate: the lanes go round by round, one
    call each at the same time, so no lane runs ahead (left alone the
    stream lane made 4,000 writes while a gRPC call took a second, and
    its backlog starved the others into their deadlines)."""
    import urllib.request

    from brpc_tpu.ici import rail
    from brpc_tpu.rpc.h2 import GrpcChannel

    dev = jax.devices()[1]
    stream_got = [0]

    class Svc(brpc.Service):
        NAME = "soak.Svc"

        @brpc.method(request="json", response="json")
        def Echo(self, cntl, req):
            return {"n": req["n"]}

        @brpc.method(request="raw", response="raw")
        def GEcho(self, cntl, req):
            return bytes(req)

        @brpc.method(request="json", response="raw")
        def Count(self, cntl, req):
            return (b"i%d" % i for i in range(int(req["n"])))

        @brpc.method(request="json", response="json")
        def Open(self, cntl, req):
            cntl.accept_stream(lambda st, p: stream_got.__setitem__(
                0, stream_got[0] + 1), max_buf_size=32 << 20, device=dev)
            return {"ok": True}

    srv = brpc.Server(brpc.ServerOptions(ici_device=dev))
    srv.add_service(Svc())
    srv.start("127.0.0.1", 0)
    port = srv.port
    per_lane = 100
    counts = {"trpc": 0, "grpc": 0, "gstream": 0, "stream": 0, "http": 0}
    failures: list = []
    rounds = threading.Barrier(len(counts))

    def lane(name, body):
        try:
            for _ in range(per_lane):
                rounds.wait(timeout=60)
                body()
                counts[name] += 1
        except Exception as e:   # pragma: no cover - the assertion prints it
            failures.append((name, repr(e)))
            rounds.abort()

    ch = brpc.Channel(f"127.0.0.1:{port}", timeout_ms=10000)
    gch = GrpcChannel(f"127.0.0.1:{port}", timeout_ms=10000)
    cntl = brpc.Controller()
    stream = brpc.stream_create(cntl, None, max_buf_size=32 << 20,
                                device=dev)
    ch.call_sync("soak.Svc", "Open", {}, serializer="json", cntl=cntl)
    chunk = jnp.ones((2048,), jnp.float32)

    def trpc():
        n = counts["trpc"]
        assert ch.call_sync("soak.Svc", "Echo", {"n": n},
                            serializer="json")["n"] == n

    def grpc():
        assert gch.call("soak.Svc", "GEcho", b"g") == b"g"

    def gstream():
        assert len(list(gch.call_stream(
            "soak.Svc", "Count", b'{"n": 5}'))) == 5

    def stream_lane():
        stream.write(b"host-bytes", timeout_s=10)
        stream.write(chunk, timeout_s=10)

    def http():
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/health", timeout=5) as r:
            assert r.read() == b"OK\n"

    threads = [threading.Thread(target=lane, args=a) for a in
               (("trpc", trpc), ("grpc", grpc), ("gstream", gstream),
                ("stream", stream_lane), ("http", http))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not failures, failures
    assert all(c == per_lane for c in counts.values()), counts
    # stream deliveries caught up; nothing left parked anywhere
    assert _wait(lambda: stream_got[0] >= counts["stream"] * 2, timeout=30)
    assert _wait(lambda: rail.pending_tickets() == 0, timeout=15)
    stream.close()
    gch.close()
    srv.stop()
    srv.join()
