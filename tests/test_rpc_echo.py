"""End-to-end RPC tests over loopback — the analog of
brpc_channel_unittest / brpc_server_unittest (SURVEY.md §4): real servers on
127.0.0.1 inside the test process, called through real Channels."""
import threading
import time

import numpy as np
import pytest

import brpc_tpu as brpc
from brpc_tpu import errors


class EchoService(brpc.Service):
    NAME = "EchoService"

    @brpc.method(request="json", response="json")
    def Echo(self, cntl, req):
        return {"msg": req["msg"], "server": "py"}

    @brpc.method(request="raw", response="raw")
    def EchoRaw(self, cntl, req):
        cntl.response_attachment = cntl.request_attachment
        return req

    @brpc.method(request="tensor", response="tensor")
    def EchoTensor(self, cntl, req):
        return req * 2

    @brpc.method(request="json", response="json")
    def Fail(self, cntl, req):
        cntl.set_failed(errors.EINTERNAL, "deliberate failure")
        return None

    @brpc.method(request="json", response="json")
    def Slow(self, cntl, req):
        time.sleep(req.get("sleep_s", 1.0))
        return {"ok": True}


@pytest.fixture(scope="module")
def server():
    s = brpc.Server()
    s.add_service(EchoService())
    s.start("127.0.0.1", 0)
    yield s
    s.stop()
    s.join()


@pytest.fixture(scope="module")
def channel(server):
    return brpc.Channel(f"127.0.0.1:{server.port}", timeout_ms=5000)


class TestUnaryRpc:
    def test_sync_json_echo(self, channel):
        resp = channel.call_sync("EchoService", "Echo", {"msg": "hello"},
                                 serializer="json")
        assert resp == {"msg": "hello", "server": "py"}

    def test_raw_with_attachment(self, channel):
        cntl = brpc.Controller()
        cntl.request_attachment = b"ATTACHMENT-BYTES"
        resp = channel.call_sync("EchoService", "EchoRaw", b"payload",
                                 serializer="raw", cntl=cntl)
        assert resp == b"payload"
        assert cntl.response_attachment == b"ATTACHMENT-BYTES"

    def test_tensor_roundtrip(self, channel):
        x = np.arange(1000, dtype=np.float32).reshape(10, 100)
        resp = channel.call_sync("EchoService", "EchoTensor", x,
                                 serializer="tensor")
        np.testing.assert_array_equal(resp, x * 2)
        assert resp.dtype == np.float32

    def test_async_with_done(self, channel):
        done = threading.Event()
        result = {}

        def on_done(cntl):
            result["resp"] = cntl.response
            result["failed"] = cntl.failed()
            done.set()

        channel.call("EchoService", "Echo", {"msg": "async"},
                     serializer="json", done=on_done)
        assert done.wait(5)
        assert not result["failed"]
        assert result["resp"]["msg"] == "async"

    def test_server_side_failure(self, channel):
        with pytest.raises(errors.RpcError) as ei:
            channel.call_sync("EchoService", "Fail", {}, serializer="json")
        assert ei.value.code == errors.EINTERNAL
        assert "deliberate" in ei.value.text

    def test_no_such_method(self, channel):
        with pytest.raises(errors.RpcError) as ei:
            channel.call_sync("EchoService", "Nope", {}, serializer="json")
        assert ei.value.code == errors.ENOMETHOD

    def test_no_such_service(self, channel):
        with pytest.raises(errors.RpcError) as ei:
            channel.call_sync("NoService", "Echo", {}, serializer="json")
        assert ei.value.code == errors.ENOSERVICE

    def test_timeout(self, server):
        ch = brpc.Channel(f"127.0.0.1:{server.port}", timeout_ms=100,
                          max_retry=0)
        cntl = brpc.Controller()
        start = time.monotonic()
        with pytest.raises(errors.RpcError) as ei:
            ch.call_sync("EchoService", "Slow", {"sleep_s": 2.0},
                         serializer="json", cntl=cntl)
        elapsed = time.monotonic() - start
        assert ei.value.code == errors.ERPCTIMEDOUT
        assert elapsed < 1.5  # did not wait for the server

    def test_connection_refused_fails(self):
        ch = brpc.Channel("127.0.0.1:1", timeout_ms=500, max_retry=2)
        with pytest.raises(errors.RpcError) as ei:
            ch.call_sync("EchoService", "Echo", {}, serializer="json")
        assert ei.value.code in (errors.ECONNREFUSED, errors.EFAILEDSOCKET)

    def test_concurrent_calls(self, channel):
        n = 64
        out = []
        lock = threading.Lock()

        def worker(i):
            r = channel.call_sync("EchoService", "Echo", {"msg": f"m{i}"},
                                  serializer="json")
            with lock:
                out.append(r["msg"])

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sorted(out) == sorted(f"m{i}" for i in range(n))

    def test_compression(self, server):
        from brpc_tpu.rpc import meta as M
        ch = brpc.Channel(f"127.0.0.1:{server.port}", timeout_ms=5000)
        cntl = brpc.Controller(compress_type=M.COMPRESS_GZIP)
        resp = ch.call_sync("EchoService", "Echo", {"msg": "x" * 10000},
                            serializer="json", cntl=cntl)
        assert resp["msg"] == "x" * 10000

    def test_method_status_metrics(self, server, channel):
        channel.call_sync("EchoService", "Echo", {"msg": "m"},
                          serializer="json")
        st = server.method_statuses[("EchoService", "Echo")]
        # the server counts a call after it has written the reply
        deadline = time.monotonic() + 5
        while st.latency_rec.count() < 1 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert st.latency_rec.count() >= 1
        assert st.latency_rec.latency_percentile(0.5) > 0


class TestStreaming:
    def test_stream_roundtrip(self, server, channel):
        received = []
        got_all = threading.Event()

        class Upper(brpc.Service):
            NAME = "UpperStream"

            @brpc.method(request="json", response="json")
            def Start(self, cntl, req):
                def on_msg(stream, data):
                    stream.write(data.upper())
                cntl.accept_stream(on_msg)
                return {"accepted": True}

        srv = brpc.Server()
        srv.add_service(Upper())
        srv.start("127.0.0.1", 0)
        try:
            ch = brpc.Channel(f"127.0.0.1:{srv.port}", timeout_ms=5000)
            cntl = brpc.Controller()

            def on_reply(stream, data):
                received.append(data)
                if len(received) == 10:
                    got_all.set()

            stream = brpc.stream_create(cntl, on_reply)
            resp = ch.call_sync("UpperStream", "Start", {}, serializer="json",
                                cntl=cntl)
            assert resp == {"accepted": True}
            for i in range(10):
                stream.write(b"chunk-%d" % i)
            assert got_all.wait(10), f"got {len(received)}/10"
            assert received == [b"CHUNK-%d" % i for i in range(10)]
            stream.close()
        finally:
            srv.stop()
            srv.join()

    def test_stream_ordering_stress(self, server, channel):
        """500 small frames must be delivered in write order even though the
        native core dispatches each parsed message onto the work-stealing
        executor (the per-stream ExecutionQueue guarantee, stream_impl.h:133)."""
        N = 500
        received = []
        got_all = threading.Event()

        class EchoStream(brpc.Service):
            NAME = "OrderStream"

            @brpc.method(request="json", response="json")
            def Start(self, cntl, req):
                cntl.accept_stream(lambda stream, data: stream.write(data))
                return {"ok": True}

        srv = brpc.Server()
        srv.add_service(EchoStream())
        srv.start("127.0.0.1", 0)
        try:
            ch = brpc.Channel(f"127.0.0.1:{srv.port}", timeout_ms=5000)
            cntl = brpc.Controller()

            def on_reply(stream, data):
                received.append(data)
                if len(received) == N:
                    got_all.set()

            stream = brpc.stream_create(cntl, on_reply)
            ch.call_sync("OrderStream", "Start", {}, serializer="json",
                         cntl=cntl)
            for i in range(N):
                stream.write(b"%06d" % i)
            assert got_all.wait(30), f"got {len(received)}/{N}"
            assert received == [b"%06d" % i for i in range(N)]
            stream.close()
        finally:
            srv.stop()
            srv.join()
