"""Inference serving demo (brpc_tpu/serving): deadline-aware dynamic
batching + continuous-decode streaming on one server.

Part 1 — batched scoring: concurrent `Serving.Score` RPCs coalesce into
bucket-padded jit calls; a request with a hopeless deadline is
ELIMIT-shed before the batch even forms.

Part 2 — continuous decode: `Serving.Generate` streams tokens per step
over the credit-windowed stream layer; a second request joins the step
loop while the first is mid-flight (no restart, no static batch).

Browse http://127.0.0.1:<port>/serving while it runs for batch
occupancy, the decode slot map, and shed/pad stats — or
/serving/generate?prompt=5&max_new_tokens=8 for the chunked-HTTP
decode stream.
"""
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

import jax.numpy as jnp
import numpy as np

import brpc_tpu as brpc
from brpc_tpu import errors
from brpc_tpu.serving import DecodeEngine, DynamicBatcher, register_serving


def main():
    # ---- the "model": a jitted scorer and a jitted decode step ----
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((64, 64)).astype(np.float32))

    @jax.jit
    def score(x):                       # [batch, 64] -> [batch]
        return jnp.tanh(x @ w).sum(axis=1)

    @jax.jit
    def step(tokens, positions):        # toy LM: next = last + 1
        return tokens + 1

    batcher = DynamicBatcher(score, max_batch_size=8, max_delay_us=5000,
                             length_buckets=(64,), name="demo")
    engine = DecodeEngine(step, num_slots=4, kv_bytes_per_slot=4096,
                          name="demo")
    server = brpc.Server()
    register_serving(server, batcher=batcher, engine=engine)
    server.start("127.0.0.1", 0)
    ch = brpc.Channel(f"127.0.0.1:{server.port}", timeout_ms=10_000)

    # ---- part 1: batched scoring + deadline shed ----
    results = []

    def score_one(i):
        y = ch.call_sync("Serving", "Score",
                         {"x": [float(i)] * 64}, serializer="json")
        results.append((i, y["y"]))

    ts = [threading.Thread(target=score_one, args=(i,)) for i in range(8)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    print(f"scored {len(results)} concurrent requests; "
          f"stats={batcher.stats()}")
    try:
        ch.call_sync("Serving", "Score", {"x": [1.0] * 64},
                     serializer="json", cntl=brpc.Controller(timeout_ms=1))
    except errors.RpcError as e:
        print(f"hopeless deadline shed up front: E{e.code} ({e.text})")

    # ---- part 2: continuous decode, two overlapping streams ----
    def generate(prompt, max_new):
        toks, done = [], threading.Event()

        def on_msg(stream, data):
            d = json.loads(data)
            if d.get("done"):
                done.set()
            else:
                toks.append(d["token"])

        cntl = brpc.Controller()
        brpc.stream_create(cntl, on_msg)
        ch.call_sync("Serving", "Generate",
                     {"prompt": prompt, "max_new_tokens": max_new},
                     serializer="json", cntl=cntl)
        return toks, done

    a_toks, a_done = generate([100], 400)
    while len(a_toks) < 5:              # A demonstrably mid-flight...
        time.sleep(0.001)
    b_toks, b_done = generate([900], 10)   # ...when B joins the loop
    assert a_done.wait(30) and b_done.wait(30)
    print(f"A streamed {len(a_toks)} tokens (first {a_toks[:3]}...), "
          f"B joined mid-flight and streamed {b_toks}")
    print(f"engine stats: {engine.stats()}")

    server.stop()
    server.join()
    batcher.close()
    engine.close()


if __name__ == "__main__":
    main()
