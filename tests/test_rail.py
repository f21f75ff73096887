"""ICI rail inside the ordinary RPC data path (ici/rail.py).

Reference parity: RdmaEndpoint::CutFromIOBufList replacing
cut_into_file_descriptor inside Socket::StartWrite/KeepWrite
(src/brpc/socket.cpp:1751-1757, rdma/rdma_endpoint.h:82) — an ordinary
Channel.call's payload rides the device interconnect while TCP carries
only control frames.  The proof obligations (VERDICT r2 task 1): values
round-trip (checksum), results live on the right device, and the
host-copy counter stays ZERO for the whole RPC.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import brpc_tpu as brpc
from brpc_tpu.ici import rail
from brpc_tpu.ici.block_pool import get_block_pool


def _pool_free_counts(device):
    pool = get_block_pool(device)
    return {cls: len(pool._free[cls]) for cls in pool._free}


@pytest.fixture()
def rail_server():
    dev = jax.devices()[1]

    class TensorSvc(brpc.Service):
        def __init__(self):
            super().__init__()
            self.seen_devices = []

        @brpc.method(request="tensor", response="tensor")
        def Double(self, cntl, req):
            if isinstance(req, jax.Array):
                self.seen_devices.append(next(iter(req.devices())))
            return req * 2

        @brpc.method(request="tensor", response="tensor")
        def SumPair(self, cntl, req):
            a, b = req
            return [a + b, a - b]

    svc = TensorSvc()
    s = brpc.Server(ici_device=dev)
    s.add_service(svc)
    s.start("127.0.0.1", 0)
    yield s, svc, dev
    s.stop()
    s.join()


def test_rail_roundtrip_zero_host_copies(rail_server):
    s, svc, dev = rail_server
    src = jax.devices()[0]
    x = jax.device_put(jnp.arange(4096, dtype=jnp.float32), src)
    ch = brpc.Channel(f"127.0.0.1:{s.port}", timeout_ms=5000)

    before_hc = rail.host_copy_count()
    before_pl = rail.rail_payloads.get_value()
    out = ch.call_sync("TensorSvc", "Double", x, serializer="tensor")

    # checksum: compare entirely on device (scalar bool readback only)
    assert isinstance(out, jax.Array)
    assert bool(jnp.array_equal(out, x * 2))
    # device assertions: handler saw the server's device, the response
    # landed back on the requester's device
    assert svc.seen_devices == [dev]
    assert out.devices() == {src}
    # the heart of the matter: no payload byte ever existed on the host
    assert rail.host_copy_count() - before_hc == 0
    # both directions rode the rail
    assert rail.rail_payloads.get_value() - before_pl == 2


def test_rail_multi_array_payload(rail_server):
    s, svc, dev = rail_server
    src = jax.devices()[0]
    a = jax.device_put(jnp.ones((64, 64), jnp.float32), src)
    b = jax.device_put(jnp.full((64, 64), 3.0, jnp.float32), src)
    ch = brpc.Channel(f"127.0.0.1:{s.port}", timeout_ms=5000)

    before_hc = rail.host_copy_count()
    out = ch.call_sync("TensorSvc", "SumPair", [a, b], serializer="tensor")
    assert isinstance(out, list) and len(out) == 2
    assert bool(jnp.array_equal(out[0], a + b))
    assert bool(jnp.array_equal(out[1], a - b))
    assert all(o.devices() == {src} for o in out)
    assert rail.host_copy_count() - before_hc == 0


def test_rail_large_array_multiblock(rail_server):
    """> 2MB payloads span several BlockPool slots; chunking/reassembly is
    all on-device."""
    s, svc, dev = rail_server
    src = jax.devices()[0]
    x = jax.device_put(
        jnp.arange(3 * 1024 * 1024 // 4 + 13, dtype=jnp.float32), src)
    ch = brpc.Channel(f"127.0.0.1:{s.port}", timeout_ms=10000)
    before_hc = rail.host_copy_count()
    out = ch.call_sync("TensorSvc", "Double", x, serializer="tensor")
    assert bool(jnp.array_equal(out, x * 2))
    assert out.devices() == {src}
    assert rail.host_copy_count() - before_hc == 0


def test_rail_no_block_leaks(rail_server):
    """Every staged block returns to its pool after the call: request
    blocks freed by the server's claim, response blocks by the client's."""
    s, svc, dev = rail_server
    src = jax.devices()[0]
    free_src = _pool_free_counts(src)
    free_dst = _pool_free_counts(dev)
    ch = brpc.Channel(f"127.0.0.1:{s.port}", timeout_ms=5000)
    for i in range(4):
        x = jax.device_put(jnp.full((256,), float(i), jnp.float32), src)
        ch.call_sync("TensorSvc", "Double", x, serializer="tensor")
    assert rail.pending_tickets() == 0
    assert _pool_free_counts(src) == free_src
    assert _pool_free_counts(dev) == free_dst


def test_host_fallback_without_advertisement():
    """A server that never advertised a device still serves tensor RPCs —
    through the host serializer (the non-RDMA socket path)."""
    class TensorSvc(brpc.Service):
        @brpc.method(request="tensor", response="tensor")
        def Double(self, cntl, req):
            return req * 2

    s = brpc.Server()
    s.add_service(TensorSvc())
    s.start("127.0.0.1", 0)
    try:
        x = jnp.arange(128, dtype=jnp.float32)
        ch = brpc.Channel(f"127.0.0.1:{s.port}", timeout_ms=5000)
        before_fb = rail.rail_fallbacks.get_value()
        before_hc = rail.host_copy_count()
        out = ch.call_sync("TensorSvc", "Double", x, serializer="tensor")
        assert np.allclose(np.asarray(out), np.arange(128) * 2)
        assert rail.rail_fallbacks.get_value() - before_fb >= 1
        assert rail.host_copy_count() - before_hc > 0  # honest accounting
    finally:
        s.stop()
        s.join()


def test_numpy_payload_takes_host_path(rail_server):
    """Host-resident numpy payloads aren't railable; they serialize as
    before even when the server advertises a device."""
    s, svc, dev = rail_server
    x = np.arange(64, dtype=np.float32)
    ch = brpc.Channel(f"127.0.0.1:{s.port}", timeout_ms=5000)
    out = ch.call_sync("TensorSvc", "Double", x, serializer="tensor")
    assert np.allclose(np.asarray(out), x * 2)


def test_timeout_withdraws_staged_payload():
    """An attempt that dies before the server claims it must not leak its
    staged blocks: _finish withdraws every unclaimed ticket, and a stale
    rail response arriving later is withdrawn on the drop path."""
    dev = jax.devices()[2]

    class SlowSvc(brpc.Service):
        @brpc.method(request="tensor", response="tensor")
        def Slow(self, cntl, req):
            time.sleep(0.5)
            return req

    s = brpc.Server(ici_device=dev)
    s.add_service(SlowSvc())
    s.start("127.0.0.1", 0)
    try:
        src = jax.devices()[0]
        free_src = _pool_free_counts(src)
        free_dst = _pool_free_counts(dev)
        x = jax.device_put(jnp.ones((512,), jnp.float32), src)
        ch = brpc.Channel(f"127.0.0.1:{s.port}", timeout_ms=100, max_retry=0)
        with pytest.raises(brpc.RpcError):
            ch.call_sync("SlowSvc", "Slow", x, serializer="tensor")
        # wait for the slow handler to finish + its stale response to be
        # dropped (and its ticket withdrawn)
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if (rail.pending_tickets() == 0
                    and _pool_free_counts(src) == free_src
                    and _pool_free_counts(dev) == free_dst):
                break
            time.sleep(0.05)
        assert rail.pending_tickets() == 0
        assert _pool_free_counts(src) == free_src
        assert _pool_free_counts(dev) == free_dst
    finally:
        s.stop()
        s.join()


def test_ship_many_mixed_oversize_and_small(monkeypatch):
    """ship_many with payloads straddling the endpoint window: oversize
    arrays ride the block pipe, small ones share batched direct sends,
    and every payload still gets its own claimable ticket with values
    and order intact."""
    from brpc_tpu.ici import rail as r
    dev = jax.devices()[1]
    src = jax.devices()[0]
    # shrink the endpoint window so a modest array counts as oversize —
    # but keep it >= the block pool's largest class (2MB), the block
    # pipe's minimum transfer unit
    ep = r._endpoint_for(dev)
    monkeypatch.setattr(ep, "window_bytes", 4 * 1024 * 1024)
    small = [jax.device_put(jnp.full((128,), i, jnp.float32), src)
             for i in range(5)]
    big = jax.device_put(jnp.arange(2 * 1024 * 1024, dtype=jnp.float32),
                         src)                      # 8MB > 4MB window
    payloads = [small[0], small[1], big, small[2],
                [small[3], small[4]]]          # list payload stays a list
    tickets = r.ship_many(payloads, dev)
    assert len(tickets) == len(payloads)
    out = [r.claim(t) for t in tickets]
    for i in (0, 1, 3):
        np.testing.assert_array_equal(
            np.asarray(out[i]), np.asarray(payloads[i]))
        assert next(iter(out[i].devices())) == dev
    np.testing.assert_array_equal(np.asarray(out[2]), np.asarray(big))
    assert isinstance(out[4], list) and len(out[4]) == 2
    np.testing.assert_array_equal(np.asarray(out[4][1]),
                                  np.asarray(small[4]))


def test_ship_many_power_of_two_decomposition(monkeypatch):
    """A 27-message batch dispatches as 16+8+2+1 (bounded arity set), and
    a batch above the cap never exceeds _MAX_ARITY per dispatch."""
    from brpc_tpu.ici import rail as r
    dev = jax.devices()[1]
    src = jax.devices()[0]
    ep = r._endpoint_for(dev)
    sizes = []
    real = ep.send_batch

    def spy(arrays, timeout_s=30.0):
        sizes.append(len(list(arrays)))
        return real(arrays, timeout_s=timeout_s)

    monkeypatch.setattr(ep, "send_batch", spy)
    arrs = [jax.device_put(jnp.full((64,), i, jnp.float32), src)
            for i in range(27)]
    tickets = r.ship_many(arrs, dev)
    assert sizes == [16, 8, 2]       # + one single-array ep.send for the 1
    out = [r.claim(t) for t in tickets]
    for i, o in enumerate(out):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(arrs[i]))
    assert all(s <= r._MAX_ARITY for s in sizes)
