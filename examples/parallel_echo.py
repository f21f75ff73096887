"""ParallelChannel fan-out demo (reference example/parallel_echo_c++) —
both over TCP servers and collective-lowered over the device mesh."""
import os, sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import brpc_tpu as brpc


class EchoService(brpc.Service):
    NAME = "EchoService"

    def __init__(self, tag):
        self._tag = tag

    @brpc.method(request="json", response="json")
    def Echo(self, cntl, req):
        return {"from": self._tag, "message": req["message"]}


def tcp_fanout():
    servers = []
    pc = brpc.ParallelChannel(fail_limit=1)
    for i in range(3):
        s = brpc.Server()
        s.add_service(EchoService(f"backend-{i}"))
        s.start("127.0.0.1", 0)
        servers.append(s)
        pc.add_channel(brpc.Channel(f"127.0.0.1:{s.port}", timeout_ms=2000))
    resp = pc.call_sync("EchoService", "Echo", {"message": "fan-out"},
                        serializer="json")
    print("tcp fan-out merged:", resp)
    for s in servers:
        s.stop()
        s.join()


def ici_fanout():
    import jax
    import jax.numpy as jnp
    from brpc_tpu.ici import IciChannel, register_device_service

    n = len(jax.devices())
    register_device_service("MatService", "Scale", lambda x: x * 3)
    pc = brpc.ParallelChannel(response_merger=brpc.SumMerger())
    for i in range(n):
        pc.add_channel(IciChannel(f"ici://slice0/{i}"))
    out = pc.call_sync("MatService", "Scale",
                       jnp.ones((4,), jnp.float32))
    print(f"ici fan-out over {n} chip(s), psum-merged:", out)


if __name__ == "__main__":
    tcp_fanout()
    ici_fanout()
