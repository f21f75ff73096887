"""Usercode admission control + inline event-loop mode (VERDICT r4 #4).

The reference sheds excess load with ELIMIT via its ConcurrencyLimiter
(server.h max_concurrency); here the bound is a LATENCY budget: when the
estimated wait for the GIL-serialized Python lane exceeds
ServerOptions.usercode_latency_budget_ms, requests are answered ELIMIT
natively (net/rpc.cc, the request never reaches Python).
usercode_inline runs non-blocking handlers directly on the dispatcher
thread (single-threaded event loop).

What the budget bounds is the lane's QUEUE WAIT, not the handler: the
shed path in net/rpc.cc fires only when more than two upcalls are
pending and the EMA of the measured wait from a frame's cut to the start
of its upcall (``core.brpc_usercode_ema_us()``) exceeds the budget.  A
request waits only while every executor worker is inside a handler, so
the storm is sized from ``core.brpc_executor_num_workers()``."""
import threading
import time

import brpc_tpu as brpc
from brpc_tpu import errors
from brpc_tpu._core import core


def test_inline_mode_roundtrip_and_reset():
    class Echo(brpc.Service):
        @brpc.method(request="raw", response="raw")
        def Echo(self, cntl, req):
            return bytes(req)

    srv = brpc.Server(brpc.ServerOptions(usercode_inline=True))
    srv.add_service(Echo())
    srv.start("127.0.0.1", 0)
    try:
        assert core.brpc_usercode_inline() == 1
        ch = brpc.Channel(f"127.0.0.1:{srv.port}", timeout_ms=5000)
        # small (flat fast path), empty, and split/large (IOBuf path)
        for sz in (0, 128, 70000):
            payload = b"q" * sz
            got = ch.call_sync("Echo", "Echo", payload, serializer="raw")
            assert bytes(got) == payload
    finally:
        srv.stop()
        srv.join()
    # inline is process-wide native state; join() must clear it
    assert core.brpc_usercode_inline() == 0


def test_latency_budget_sheds_with_elimit():
    class Slow(brpc.Service):
        @brpc.method(request="raw", response="raw")
        def Work(self, cntl, req):
            time.sleep(0.005)
            return b"done"

    srv = brpc.Server(brpc.ServerOptions(usercode_latency_budget_ms=2.0))
    srv.add_service(Slow())
    srv.start("127.0.0.1", 0)
    addr = f"127.0.0.1:{srv.port}"
    # plain reads of native atomics
    workers = core.brpc_executor_num_workers()  # brpc-check: allow(wedge-hygiene)
    ema_us = core.brpc_usercode_ema_us  # brpc-check: allow(wedge-hygiene)
    oks, errs = [], []

    def storm(n_callers, calls):
        gate = threading.Barrier(n_callers)

        def caller():
            ch = brpc.Channel(addr, timeout_ms=8000, max_retry=0)
            gate.wait(timeout=10)
            for _ in range(calls):
                try:
                    oks.append(ch.call_sync("Slow", "Work", b"x",
                                            serializer="raw"))
                except errors.RpcError as e:
                    errs.append(e.code)

        threads = [threading.Thread(target=caller)
                   for _ in range(n_callers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)

    try:
        # two callers never have more than two upcalls pending: the
        # handler is 2.5x the budget, and nothing is shed
        storm(2, 4)
        assert not errs, f"shed from a near-empty lane: {errs[:5]}"
        # the estimate is the process's, and may stand where another
        # server's load left it: a near-empty lane is always admitted
        # and its own waits bring it back under the budget
        ch = brpc.Channel(addr, timeout_ms=8000, max_retry=0)
        for _ in range(40):
            if ema_us() < 2000.0:
                break
            ch.call_sync("Slow", "Work", b"x", serializer="raw")
        assert ema_us() < 2000.0
        # three callers a worker: two thirds of a wave wait 5-10 ms for
        # a worker, and what arrives after that is shed
        storm(3 * workers, 6)
    finally:
        srv.stop()
        srv.join()
    assert oks, "some calls must succeed"
    assert errs and set(errs) == {errors.ELIMIT}, (len(oks), errs[:5])
    assert core.brpc_usercode_shed_count() >= len(errs)
    # budget cleared for later servers/tests
    assert core.brpc_usercode_budget_us() == 0


def test_budget_zero_never_sheds():
    class Echo(brpc.Service):
        @brpc.method(request="raw", response="raw")
        def Echo(self, cntl, req):
            return bytes(req)

    before = core.brpc_usercode_shed_count()
    srv = brpc.Server()
    srv.add_service(Echo())
    srv.start("127.0.0.1", 0)
    try:
        ch = brpc.Channel(f"127.0.0.1:{srv.port}", timeout_ms=5000)
        for i in range(50):
            assert bytes(ch.call_sync("Echo", "Echo", b"x%d" % i,
                                      serializer="raw")) == b"x%d" % i
    finally:
        srv.stop()
        srv.join()
    assert core.brpc_usercode_shed_count() == before
