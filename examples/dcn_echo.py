"""Cross-process device RPC over the DCN groundwork (ici/dcn.py;
reference analog: RdmaEndpoint's TCP-assisted handshake,
rdma_endpoint.h:112-115).

Spawns a CHILD PROCESS with its own jax runtime serving a device
service, handshakes topologies over TCP, and calls the child's chip 3
from this process.

Run:  python examples/dcn_echo.py
"""
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

REPO = os.path.join(os.path.dirname(__file__), "..")

CHILD = f"""
import sys
sys.path.insert(0, {REPO!r})
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from brpc_tpu.ici.channel import register_device_service
from brpc_tpu.rpc.server import Server

register_device_service("Mat", "Scale", lambda x: x * 3.0)
srv = Server(enable_dcn=True)
srv.start("127.0.0.1", 0)
print(f"PORT={{srv.port}}", flush=True)
srv.run_until_interrupt()
"""


def main():
    env = dict(os.environ)
    # one process per chip: the parent may hold it, so the child gets the CPU
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    child = subprocess.Popen([sys.executable, "-c", CHILD],
                             stdout=subprocess.PIPE, env=env, text=True)
    port = None
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline and port is None:
        line = child.stdout.readline()
        if line.startswith("PORT="):
            port = int(line.strip().split("=")[1])
    assert port, "child never came up"

    import jax
    jax.config.update("jax_platforms", "cpu")
    from brpc_tpu.ici.dcn import DcnChannel

    ch = DcnChannel(f"ici://127.0.0.1:{port}/3")
    topo = ch.handshake()
    mode = "zero-copy fabric" if topo.get("xfer") else "host fallback"
    print(f"peer pid {topo['pid']}: {len(topo['devices'])} "
          f"{topo['platform']} devices; data plane: {mode} "
          f"(xfer addr {topo.get('xfer')})")
    from brpc_tpu.rpc import serialization
    enc0 = serialization.tensor_host_encodes.get_value()
    out = ch.call_sync("Mat", "Scale",
                       jax.numpy.arange(8, dtype=jax.numpy.float32))
    hc = serialization.tensor_host_encodes.get_value() - enc0
    print(f"Scale on remote chip 3 -> {list(map(float, out))} "
          f"({hc} host tensor encodes on the data path)")
    child.terminate()
    child.wait(10)


if __name__ == "__main__":
    main()
