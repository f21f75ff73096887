"""Streaming RPC demo (reference example/streaming_echo_c++):
client attaches a stream to an RPC, pushes chunks, server echoes them back
through the same credit-windowed pipe.

Part 2 shows the ICI rail (the use_rdma analog, rdma_endpoint.h:82): the
server advertises a device, and an ordinary `Channel.call_sync` carrying a
jax device tensor moves its payload over BlockPool + IciEndpoint — zero
host copies, only the control frame touches the socket.

Part 3 is the unified StreamWrite: the SAME stream.write() that carried
bytes in part 1 carries jax device arrays HBM->HBM — tensors ride the
rail under the socket (socket.cpp:1751-1757's RDMA slide-under), the
socket sees only claim tickets, and host_copy_count() stays zero.
"""
import os, sys, threading
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

import jax.numpy as jnp

import brpc_tpu as brpc
from brpc_tpu.ici import rail


class StreamEcho(brpc.Service):
    @brpc.method(request="json", response="json")
    def Open(self, cntl, req):
        def on_msg(stream, data):
            stream.write(b"echo:" + data)
        cntl.accept_stream(on_msg)
        return {"accepted": True}

    @brpc.method(request="json", response="json")
    def OpenTensor(self, cntl, req):
        # tensor echo: receives device arrays on the advertised chip and
        # writes them straight back through the same stream
        def on_msg(stream, payload):
            stream.write(payload)
        cntl.accept_stream(on_msg, device=jax.devices()[-1])
        return {"accepted": True}

    @brpc.method(request="tensor", response="tensor")
    def Scale(self, cntl, req):
        # req arrives as a device array on the server's advertised chip;
        # the result rides the rail back to the caller's chip
        return req * 2


def main(n_chunks=20):
    devs = jax.devices()
    server = brpc.Server(ici_device=devs[-1])
    server.add_service(StreamEcho())
    server.start("127.0.0.1", 0)
    # generous deadline: the first call compiles the rail's programs
    ch = brpc.Channel(f"127.0.0.1:{server.port}", timeout_ms=180000)

    # --- part 1: byte streaming over the credit-windowed stream pipe ---
    got = []
    done = threading.Event()

    def on_reply(stream, data):
        got.append(data)
        if len(got) == n_chunks:
            done.set()

    cntl = brpc.Controller()
    stream = brpc.stream_create(cntl, on_reply, max_buf_size=256 * 1024)
    print("open:", ch.call_sync("StreamEcho", "Open", {}, serializer="json",
                                cntl=cntl))
    for i in range(n_chunks):
        stream.write(b"chunk-%03d" % i)
    assert done.wait(10), f"got {len(got)}/{n_chunks}"
    print(f"received {len(got)} echoed chunks, first={got[0]!r} "
          f"last={got[-1]!r}")
    stream.close()

    # --- part 2: device tensors on an ordinary call ride the ICI rail ---
    x = jax.device_put(jnp.arange(1 << 18, dtype=jnp.float32), devs[0])
    host_copies_before = rail.host_copy_count()
    out = ch.call_sync("StreamEcho", "Scale", x, serializer="tensor")
    assert bool(jnp.array_equal(out, x * 2))
    assert out.devices() == {devs[0]}, "response must land on the caller's chip"
    hc = rail.host_copy_count() - host_copies_before
    print(f"rail: {x.nbytes} tensor bytes moved {devs[0]}->{devs[-1]}->"
          f"{devs[0]} with {hc} host copies "
          f"(payloads so far: {rail.rail_payloads.get_value()})")
    assert hc == 0

    # --- part 3: the SAME StreamWrite carries device tensors zero-copy ---
    tensors_back = []
    tdone = threading.Event()

    def on_tensor(stream, payload):
        tensors_back.append(payload)
        if len(tensors_back) == 8:
            tdone.set()

    cntl2 = brpc.Controller()
    tstream = brpc.stream_create(cntl2, on_tensor, device=devs[0])
    print("open tensor stream:",
          ch.call_sync("StreamEcho", "OpenTensor", {}, serializer="json",
                       cntl=cntl2))
    before = rail.host_copy_count()
    chunks = [jax.device_put(jnp.full((1 << 16,), i, dtype=jnp.float32),
                             devs[0]) for i in range(8)]
    for c in chunks:
        tstream.write(c)                 # same API as the byte writes
    assert tdone.wait(30), f"got {len(tensors_back)}/8 tensors"
    for i, t in enumerate(tensors_back):
        assert isinstance(t, jax.Array) and t.devices() == {devs[0]}
        assert bool(jnp.array_equal(t, chunks[i]))
    hc = rail.host_copy_count() - before
    total = sum(c.nbytes for c in chunks)
    print(f"stream: {total} tensor bytes {devs[0]}->{devs[-1]}->{devs[0]} "
          f"through StreamWrite with {hc} host copies")
    assert hc == 0
    tstream.close()

    server.stop()
    server.join()


if __name__ == "__main__":
    main()
