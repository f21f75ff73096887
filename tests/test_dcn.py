"""DCN groundwork tests (VERDICT r2 task 4): TCP handshake exchanging
device topology between two processes, and a Channel in process A calling
a device service registered in process B.

Reference: RdmaEndpoint's TCP-assisted handshake (rdma_endpoint.h:112-115,
180) — magic preamble + capability exchange on the existing connection.
The child process runs its own jax runtime (virtual 8-device CPU mesh) —
genuinely a separate device world, like a second host across the DCN.
"""
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

SERVER_SCRIPT = r"""
import sys
sys.path.insert(0, {repo!r})
import jax
import jax.numpy as jnp
from brpc_tpu.ici.channel import register_device_service
from brpc_tpu.rpc.server import Server

def inc(x):
    return x + 1.0

def scale_sum(x):
    return jnp.sum(x) * 2.0

register_device_service("MatSvc", "Inc", inc)
register_device_service("MatSvc", "ScaleSum", scale_sum)
srv = Server(enable_dcn=True)
srv.start("127.0.0.1", 0)
print(f"PORT={{srv.port}}", flush=True)
srv.run_until_interrupt()
"""


@pytest.fixture(scope="module")
def remote_server():
    import selectors
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.Popen(
        [sys.executable, "-c", SERVER_SCRIPT.format(repo=repo)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env, text=True)
    port = None
    try:
        # selector-based read: a child that wedges without printing must
        # hit the deadline, not block readline() forever; and any startup
        # failure must kill the child, not orphan an 8-device jax runtime
        sel = selectors.DefaultSelector()
        sel.register(proc.stdout, selectors.EVENT_READ)
        deadline = time.monotonic() + 60
        buf = ""
        while time.monotonic() < deadline and port is None:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"server died: {proc.stderr.read()[-2000:]}")
            if sel.select(timeout=0.5):
                buf += os.read(proc.stdout.fileno(), 4096).decode(
                    "utf-8", "replace")
                for line in buf.splitlines():
                    if line.startswith("PORT="):
                        port = int(line.strip().split("=", 1)[1])
        assert port, "server never printed its port within 60s"
    except BaseException:
        proc.kill()
        proc.wait(timeout=10)
        raise
    yield port, proc
    proc.terminate()
    proc.wait(timeout=10)


class TestDcnHandshake:
    def test_topology_exchange(self, remote_server):
        from brpc_tpu.ici.dcn import DcnChannel
        port, proc = remote_server
        ch = DcnChannel(f"ici://127.0.0.1:{port}/0")
        topo = ch.handshake()
        assert topo["magic"] == "DCN1"
        # genuinely another process with its own 8-device runtime
        assert topo["pid"] != os.getpid()
        assert len(topo["devices"]) == 8
        assert topo["platform"] == "cpu"
        assert ch.remote_device_ids() == list(range(8))

    def test_bad_magic_rejected(self, remote_server):
        from brpc_tpu import errors
        from brpc_tpu.rpc.channel import Channel
        port, _ = remote_server
        ch = Channel(f"127.0.0.1:{port}", timeout_ms=10_000)
        with pytest.raises(errors.RpcError):
            ch.call_sync("_dcn", "Hello", {"magic": "nope"},
                         serializer="json", response_serializer="json")


class TestDcnDeviceCall:
    def test_call_device_service_cross_process(self, remote_server):
        """The VERDICT done bar: Channel on A calls a device service on
        B; B's handler runs on B's chip; result lands back on A."""
        from brpc_tpu.ici.dcn import DcnChannel
        port, _ = remote_server
        ch = DcnChannel(f"ici://127.0.0.1:{port}/3")
        x = jax.numpy.arange(16, dtype=jax.numpy.float32)
        out = ch.call_sync("MatSvc", "Inc", x)
        np.testing.assert_allclose(np.asarray(out), np.arange(16) + 1.0)
        # result is a local array in THIS process's runtime
        assert next(iter(out.devices())) in set(jax.devices())

    def test_per_chip_routing(self, remote_server):
        from brpc_tpu.ici.dcn import DcnChannel
        port, _ = remote_server
        ch = DcnChannel(f"ici://127.0.0.1:{port}")
        for chip in (0, 3, 7):
            out = ch.call_sync("MatSvc", "ScaleSum",
                               jax.numpy.ones((8,), jax.numpy.float32),
                               chip=chip)
            assert float(out) == 16.0

    def test_unknown_chip_rejected(self, remote_server):
        from brpc_tpu import errors
        from brpc_tpu.ici.dcn import DcnChannel
        port, _ = remote_server
        ch = DcnChannel(f"ici://127.0.0.1:{port}")
        with pytest.raises(errors.RpcError):
            ch.call_sync("MatSvc", "Inc",
                         jax.numpy.ones((2,)), chip=99)

    def test_unknown_service_errors(self, remote_server):
        from brpc_tpu import errors
        from brpc_tpu.ici.dcn import DcnChannel
        port, _ = remote_server
        ch = DcnChannel(f"ici://127.0.0.1:{port}/0")
        with pytest.raises(errors.RpcError):
            ch.call_sync("NoSvc", "Nope", jax.numpy.ones((2,)))


class TestDcnAddressParsing:
    def test_forms(self):
        from brpc_tpu.ici.dcn import parse_dcn_address
        assert parse_dcn_address("ici://h:80/3") == ("h", 80, 3)
        assert parse_dcn_address("ici://h:80") == ("h", 80, None)
        assert parse_dcn_address("h:80") == ("h", 80, None)


class TestDcnZeroCopyDataPlane:
    def test_zero_copy_pull_no_host_serialization(self, remote_server):
        """The real DCN data plane (VERDICT r3 #5): with both fabrics up,
        CallDevice payloads move device-to-device over
        jax.experimental.transfer — the socket carries control only, and
        the tensor serializer provably never touches the payload."""
        from brpc_tpu.ici.dcn import (DcnChannel, dcn_zero_copy_calls,
                                      transfer_address)
        from brpc_tpu.rpc import serialization

        port, _proc = remote_server
        ch = DcnChannel(f"ici://127.0.0.1:{port}/0")
        topo = ch.handshake()
        assert topo.get("xfer"), "server advertised no transfer fabric"
        assert transfer_address(), "local transfer fabric unavailable"
        x = jax.device_put(np.arange(64, dtype=np.float32),
                           jax.devices()[0])
        enc0 = serialization.tensor_host_encodes.get_value()
        dec0 = serialization.tensor_host_decodes.get_value()
        out = ch.call_sync("MatSvc", "Inc", x)
        np.testing.assert_allclose(np.asarray(out),
                                   np.arange(64, dtype=np.float32) + 1.0)
        # result landed on OUR device, straight from the fabric
        assert next(iter(out.devices())) == jax.devices()[0]
        # the host tensor serializer was never involved in this process
        assert serialization.tensor_host_encodes.get_value() == enc0
        assert serialization.tensor_host_decodes.get_value() == dec0

    def test_fallback_without_local_fabric(self, remote_server):
        """A client whose fabric failed still completes calls — host
        serialization, wire-compatible (the RDMA-unavailable fallback)."""
        from brpc_tpu.ici import dcn

        port, _proc = remote_server
        real_server = dcn.transfer_server
        dcn_mod_server = lambda: None
        dcn.transfer_server = dcn_mod_server
        try:
            ch = dcn.DcnChannel(f"ici://127.0.0.1:{port}/0")
            out = ch.call_sync("MatSvc", "Inc",
                               np.arange(8, dtype=np.float32))
            np.testing.assert_allclose(
                np.asarray(out), np.arange(8, dtype=np.float32) + 1.0)
        finally:
            dcn.transfer_server = real_server
